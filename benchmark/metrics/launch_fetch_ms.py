"""Dispatch ring: launch + fetch a query, median: `DeviceDispatch.kernelMs`
+ `fetchMs`. On the inline path (a lone client) `kernelMs` is the whole
round trip and `fetchMs` 0; on the ring path `kernelMs` is the
asynchronous launch and `fetchMs` the wait for the result. Either way a
HOST clock (ROADMAP C6), never kernel time: the device's own time is
kernel_roofline's."""
from metrics import launch_fetch, median_or_none, per_query


def read(ctx):
    return median_or_none(per_query(ctx["records"],
                                    lambda r: launch_fetch(r["trace"])))
