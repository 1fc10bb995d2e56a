"""Server transport + executor: what lies between the broker's scatter
and the server's own span, median: `ServerScatter.durationMs` minus
`ServerRequest.durationMs`: request framing and the wire, the scheduler's
`queueWaitMs` (transport read to the span's opening), the span tree's
serialization, the answer's wire and its deserialization."""
from metrics import median_or_none, per_query, span_sum


def read(ctx):
    def one(r):
        scatter = span_sum(r["trace"], "ServerScatter")
        request = span_sum(r["trace"], "ServerRequest")
        if scatter is None or request is None:
            return None
        return scatter - request
    return median_or_none(per_query(ctx["records"], one))
