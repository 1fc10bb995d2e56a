"""Dispatch ring: launch returned to result ready, median:
`DeviceDispatch.deviceWaitMs`, a HOST clock round
`jax.block_until_ready`: queueing on the device behind other launches
and the wake-up are in it, so it bounds device time from above and is
never kernel time (kernel_roofline reads the device's own)."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"], lambda r: dispatch_sum(r["trace"], "deviceWaitMs")))
