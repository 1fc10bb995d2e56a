"""Server transport + executor: what of a server request no named phase
covers, median: `ServerRequest.durationMs` minus the sum of
span_phases.SERVER_PHASES (parse, lock wait, plan, blocks, params,
submit, ring wait, dispatch, launch, device wait, copy, hand-off,
assemble, serialize). Where it grows, a wait has no name yet. Prints the
median of every phase, and where the rest lies, to stderr, and what the
slowest queries sat in."""
import sys

from metrics import median_or_none, per_query, span_sum
from span_phases import named_phase_ms, phase_report


def read(ctx):
    def one(r):
        request = span_sum(r["trace"], "ServerRequest")
        named = named_phase_ms(r["trace"])
        if request is None or named is None:
            return None
        return request - named
    value = median_or_none(per_query(ctx["records"], one))
    if value is not None:
        for line in phase_report(ctx["records"]):
            print("bench: " + line, file=sys.stderr, flush=True)
    return value
