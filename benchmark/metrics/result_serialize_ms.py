"""Server transport + executor: the server's results -> DataTable bytes,
median a query: `ServerRequest.serializeMs` (a grouped result's columns
written, a dict-built one transposed first; a scan's few intermediates
as tagged values). One of the phases that tile `ServerRequest`, so it is
inside `server_host_ms`. A program without the attribute gives None."""
from metrics import median_or_none, per_query, span_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"],
        lambda r: span_sum(r["trace"], "ServerRequest", "serializeMs")))
