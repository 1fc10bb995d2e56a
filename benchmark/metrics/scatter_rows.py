"""Kernels: rows x additive slots a query's grouped launch hands to
XLA's scatter-add, median a query: `DeviceDispatch.scatterRows`, from
the static shapes the kernel runs with (padding included; 0 where every
slot takes a one-hot path). The work of the path `groupPath` names
`scatter`: a change that compacts the rows the filter keeps, or that
takes the key space off the scatter, shows here. A program without the
attribute, or an ungrouped cell, gives None."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"], lambda r: dispatch_sum(r["trace"], "scatterRows")))
