"""Engine: plan + stage: host->device puts of parameters a query, median:
`DeviceDispatch.paramPuts`. Since PR 31 a query's [S]-shaped parameters
ride one packed array: a miss of the engine's parameter cache is 1 (one
more a LUT table), a hit 0; before it, 7-8 a miss. `staging_params_ms`
is the time they took."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"], lambda r: dispatch_sum(r["trace"], "paramPuts")))
