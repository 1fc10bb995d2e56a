"""Dispatch ring: the device->host copy of the packed result, median:
`DeviceDispatch.d2hMs`, `np.asarray` of a result that is ready."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"], lambda r: dispatch_sum(r["trace"], "d2hMs")))
