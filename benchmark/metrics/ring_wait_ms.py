"""Dispatch ring: what a staged launch waited in the ring, median:
`DeviceDispatch.queueWaitMs`, submit to popped for launch (the window
for coalescing is in it; inline: submit to launch, near 0)."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"], lambda r: dispatch_sum(r["trace"], "queueWaitMs")))
