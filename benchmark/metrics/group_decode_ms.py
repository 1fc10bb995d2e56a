"""Server transport + executor: fetched group slots -> the server's
group table, median a query: `DeviceDispatch.groupDecodeMs` (present
groups found, keys decoded through the dictionaries, one intermediate a
group and function). It lies inside `ServerRequest.assembleMs`, so the
phases tile as before. A program without the attribute, or an ungrouped
cell, gives None."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"], lambda r: dispatch_sum(r["trace"], "groupDecodeMs")))
