"""Engine, plan + stage: `DeviceDispatch.stagingMs` a query, median."""
from metrics import median_or_none, per_query, span_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"],
        lambda r: span_sum(r["trace"], "DeviceDispatch", "stagingMs")))
