"""The instant of a fleet's readings written in time order (fleet_hour's
layout), as Pinot's standard time column holds it: epoch milliseconds
(`1:MILLISECONDS:EPOCH`) from `origin_ms`, every member of the fleet at
its tick's instant, as TSBS's simulator writes them.

`bucket_seconds` > 1 gives the instant truncated to buckets of that
width from the origin: with the origin on the hour and 3600, what
DATETRUNC('HOUR', ts) gives row by row, the reference's hour axis.

codes are the second (or bucket) since the origin; the domain is every
one of them over the table's `table_segments` stretches."""
import numpy as np

from generators.fleet_hour import layout


def generate(rng, docs, spec, pools, made):
    span, width = spec["segment_seconds"], spec["bucket_seconds"]
    stretch, _fleet, tick, ticks = layout(rng, docs, spec, pools)
    second = stretch * span + tick * span // ticks
    codes = (second // width).astype(np.int32)
    buckets = -(-spec["table_segments"] * span // width)
    domain = spec["origin_ms"] + 1000 * width * np.arange(buckets,
                                                          dtype=np.int64)
    return domain[codes], codes, domain
