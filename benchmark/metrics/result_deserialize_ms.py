"""Server transport + executor: the answer's DataTable bytes -> result
objects on the broker, median a query: `ServerScatter.deserializeMs`,
the one part of `scatter_wire_ms` the program can name (the rest is
framing, the wire, the scheduler's queue and the span tree). A grouped
result arrives as columns; its dict is built where the reduce first
reads it, under `broker_self_ms`. A program without the attribute
(PR 35 and before) gives None."""
from metrics import median_or_none, per_query, span_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"],
        lambda r: span_sum(r["trace"], "ServerScatter", "deserializeMs")))
