"""Engine: plan + stage: the parameter part of staging, median:
`DeviceDispatch.paramsMs`: literals resolved, the tiny arrays built and
`device_put` one by one (`paramPuts` of them). With `planMs` and
`blocksMs` it sums to `stagingMs` (engine_staging_ms)."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"], lambda r: dispatch_sum(r["trace"], "paramsMs")))
