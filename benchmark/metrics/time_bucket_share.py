"""Kernels: share of the traced queries, in %, whose GROUP BY ran its
time bucket inside the grouped kernel and folded its segments on the
device: every grouped `DeviceDispatch` of the query carries
`timeBucket` (DATETRUNC's unit, or FLOOR for the time-series leaf's
bucket) and `groupFold` = "device". Over the queries with a grouped
dispatch; a query whose bucket ran as a host expression, or whose
partials the host folded, counts against it. A program whose spans
never carry `timeBucket` gives None, never 0."""
from judge import spans
from metrics import per_query


def read(ctx):
    def grouped(r):
        return [d for d in spans(r["trace"], "DeviceDispatch")
                if d.get("groupFold") is not None]

    queries = [g for g in per_query(ctx["records"], grouped) if g]
    if not any(d.get("timeBucket") is not None for g in queries for d in g):
        return None
    fused = [all(d.get("timeBucket") is not None
                 and d["groupFold"] == "device" for d in g) for g in queries]
    return 100.0 * sum(fused) / len(fused)
