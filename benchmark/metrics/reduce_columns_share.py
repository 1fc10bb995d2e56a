"""Broker: share of the traced queries whose GROUP BY the broker
finished as columns, in %: `BrokerReduce.reducePath` = "columns" (one
result held as columns, sorted and sliced a whole column at a time) over
the queries whose span carries the attribute ("rows" is the dict merge
a group at a time). A program without the attribute, or an ungrouped
cell, gives None, never 0."""
from judge import spans
from metrics import per_query


def read(ctx):
    def path(r):
        found = [s["reducePath"] for s in spans(r["trace"], "BrokerReduce")
                 if s.get("reducePath") is not None]
        return found[0] if found else None

    paths = [p for p in per_query(ctx["records"], path) if p is not None]
    if not paths:
        return None
    return 100.0 * paths.count("columns") / len(paths)
