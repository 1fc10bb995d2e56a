"""Broker: `timeUsedMs` minus the ServerScatter span (parse, route,
reduce), median."""
from metrics import median_or_none, per_query, span_sum


def read(ctx):
    def one(r):
        scatter = span_sum(r["trace"], "ServerScatter")
        if scatter is None or r.get("time_used_ms") is None:
            return None
        return r["time_used_ms"] - scatter
    return median_or_none(per_query(ctx["records"], one))
