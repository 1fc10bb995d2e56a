"""Client / HTTP: what the client waits beyond the broker's own
`timeUsedMs` (connect, HTTP/1.0 framing, JSON both ways), median."""
from metrics import median_or_none, per_query


def read(ctx):
    return median_or_none(per_query(ctx["records"], lambda r: (
        (r["done_s"] - r["sent_s"]) * 1e3 - r["time_used_ms"]
        if r.get("time_used_ms") is not None else None)))
