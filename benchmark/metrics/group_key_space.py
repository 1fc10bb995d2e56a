"""Kernels: the group-key space a grouped launch ran with, median a
query: `DeviceDispatch.groupKeySpace`, the G of the kernel (the product
of the group columns' padded cardinalities, or a compacted plan's own
count). `groupPath` follows from it (`kernels.group_path`), so a change
that shrinks the key space to what the filter leaves shows here first.
A program without the attribute, or an ungrouped cell, gives None."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"], lambda r: dispatch_sum(r["trace"], "groupKeySpace")))
