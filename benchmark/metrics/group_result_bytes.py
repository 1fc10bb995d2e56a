"""Dispatch ring: bytes of grouped result a query fetched from the
device, median: `DeviceDispatch.groupResultBytes`: the group table of
the fetch, [G, slots] where the per-segment partials were folded on the
device (`groupFold` = device), [S, G, slots] where the host folds them.
`d2h_ms` is the time the copy took. A program without the attribute, or
an ungrouped cell, gives None."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"],
        lambda r: dispatch_sum(r["trace"], "groupResultBytes")))
