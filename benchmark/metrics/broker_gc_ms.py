"""Broker: the collector's pauses inside a request, the MEAN a traced
answered query of `BrokerRequest.gcPauseMs` (0 where no collection fell
inside it: collections are rare, and a median would read 0). A program
without the probe (no `gcTotalMs` on the root) gives None.

Prints to stderr, for `gc_report`'s root span: the collections inside
requests by generation, the pauses outside any request (the roots'
running `gcTotalMs` between the first close and the last, less those
inside), and the pauses of 10 ms or more (the roots' `gcLongPauses`):
their lengths, generations and the spans of the same process they fell
in, then the earliest SHOWN one a line with its `startNs`. What confirms
or clears the collector as a stall."""
import statistics
import sys
from collections import Counter

from judge import spans
from metrics import per_query

#: the long pauses listed one a line, the earliest first
SHOWN = 20


def read(ctx):
    return gc_report(ctx, "BrokerRequest", "broker_gc_ms")


def gc_report(ctx, root_name: str, metric: str):
    roots = per_query(ctx["records"], lambda r: spans(r["trace"], root_name))
    probed = [q for q in roots if q and all("gcTotalMs" in s for s in q)]
    if not probed:
        return None

    def say(text: str) -> None:
        print(f"bench: {metric}: {text}", file=sys.stderr)
    every = [s for q in probed for s in q]
    gens = [sum((s.get("gcByGeneration") or (0, 0, 0))[g] for s in every)
            for g in range(3)]
    say(f"{len(probed)} queries, {len(every)} {root_name} spans; "
        f"collections inside them by generation {gens}")
    closed = sorted(every, key=lambda s: s["startNs"] + s["durationMs"] * 1e6)
    between = closed[-1]["gcTotalMs"] - closed[0]["gcTotalMs"]
    inside = sum(s.get("gcPauseMs", 0.0) for s in closed[1:])
    outside = (f"{between - inside:.3f} outside" if inside <= between
               else "requests overlap, so these sums cannot tell what "
                    "fell outside them")
    say(f"paused {between:.3f} ms between the first close and the last, "
        f"{inside:.3f} inside requests (a pause counts once a request "
        f"open across it), {outside}")
    seen = {}
    for s in every:
        for gen, start_ns, ms in s.get("gcLongPauses", ()):
            seen.setdefault((gen, ms), (start_ns, s))
    where = {key: " > ".join(_holding(s, start_ns))
             for key, (start_ns, s) in seen.items()}
    summary = ""
    if seen:
        lengths = sorted(ms for _gen, ms in seen)
        summary = (f": {lengths[0]} to {lengths[-1]} ms, median "
                   f"{statistics.median(lengths)}; by generation "
                   f"{dict(Counter(gen for gen, _ms in seen))}; by span "
                   f"{dict(Counter(where.values()))}")
    say(f"{len(seen)} pauses of 10 ms or more{summary}")
    for (gen, ms), (start_ns, _s) in sorted(seen.items(),
                                            key=lambda kv: kv[1][0])[:SHOWN]:
        say(f"pause {ms} ms, generation {gen}, startNs {start_ns}, in "
            + where[gen, ms])
    return statistics.mean(sum(s.get("gcPauseMs", 0.0) for s in q)
                           for q in probed)


def _holding(node, at_ns: int) -> list:
    """The chain of spans from `node` down that were open at `at_ns`, in
    `node`'s own process (a grafted `ServerRequest` is the server's)."""
    chain = [node["operator"]]
    for child in node.get("children", ()):
        start = child.get("startNs")
        if child.get("operator") == "ServerRequest" or not start:
            continue
        if start <= at_ns <= start + child["durationMs"] * 1e6:
            return chain + _holding(child, at_ns)
    return chain
