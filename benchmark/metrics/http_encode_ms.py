"""Client / HTTP: the broker's encode of the answer, median a query:
`BrokerEncode.durationMs`, the result table's rows to JSON inside the
request's tree, after `timeUsedMs` is taken: the part of
`http_overhead_ms` the program can name (the rest is the envelope, the
socket, HTTP/1.0 and the client's `json.loads`). Prints the median
`responseBytes` to stderr. A program without the span gives None."""
import sys

from metrics import median_or_none, per_query, span_sum


def read(ctx):
    def encode(field):
        return median_or_none(per_query(
            ctx["records"], lambda r: span_sum(r["trace"], "BrokerEncode",
                                               field)))

    size = encode("responseBytes")
    if size is not None:
        print(f"bench: http_encode_ms: median responseBytes {size:g}",
              file=sys.stderr)
    return encode("durationMs")
