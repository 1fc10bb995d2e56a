"""Engine: plan + stage: what a query waited for the engine lock, median:
`DeviceDispatch.lockWaitMs`, from the span's opening to `_engine_lock`
acquired. Under N clients this is the queue for staging; it is no part
of `stagingMs`."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"], lambda r: dispatch_sum(r["trace"], "lockWaitMs")))
