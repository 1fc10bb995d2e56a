"""Kernels, on a server that holds several chips: bytes a chip hands to
the collectives of the grouped program a launch, median a query:
`DeviceDispatch.meshExchangeBytes`, which the engine reads once a
compiled program from the program's own text (operands of all-reduce,
all-gather, all-to-all, collective-permute, reduce-scatter): the fold's
exchange between chips. `meshGatherBytes`, its all-gather part, rides the
same span (0 is the design). A program without the attribute, a one-chip
or an ungrouped cell gives None."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"],
        lambda r: dispatch_sum(r["trace"], "meshExchangeBytes")))
