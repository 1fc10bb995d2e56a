"""Per-layer metrics, one reader a metric, found by the metric's name
in BENCHMARK.json: `metrics/<name>.py` with

    read(ctx) -> float | None

ctx holds the traced window's `records` (each with the broker's `trace`
tree and `time_used_ms`), the cell's `config` and `mix`, the table's
`rows`, `device_kind`, `cardinalities`, and from the profiler `busy_s`,
`window_s` and `queries_in_trace`. A reader that finds nothing to read
returns None and the metric is left out of the line; none returns 0 for
a share."""
from __future__ import annotations

import statistics

from judge import spans


def median_or_none(values: list):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def per_query(records: list, fn) -> list:
    """fn(record) over the traced records that brought an answer."""
    return [fn(r) for r in records
            if r.get("trace") is not None and r["rows"] is not None]


def span_sum(trace, name: str, field: str = "durationMs"):
    """Sum of `field` over the spans called `name`; None if there is none
    (a query has one DeviceDispatch span a segment batch)."""
    found = [s.get(field) for s in spans(trace, name)]
    found = [v for v in found if v is not None]
    return sum(found) if found else None


def launch_fetch(trace):
    """Host-clock ms round launch and fetch over a query's dispatches."""
    kernel = span_sum(trace, "DeviceDispatch", "kernelMs")
    if kernel is None:
        return None
    return kernel + (span_sum(trace, "DeviceDispatch", "fetchMs") or 0.0)
