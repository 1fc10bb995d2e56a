"""The program's wait attributes, read out of a query's span tree, and
the device's idle gaps laid against them: plain functions over the
records `loadgen` makes, for the readers in metrics/ that PR 27 added.

A span is placed by `startNs` (epoch ns, the clock `done_wall` and the
profiler's xplane share on one machine); its `durationMs` and the `*Ms`
attributes are lengths. A tree of a program that has no such attribute
(the parent of PR 27) gives None everywhere here, never 0."""
from __future__ import annotations

import statistics

from judge import FALLBACK_OUTCOMES, spans

#: the named phases of one server request, in the order they happen:
#: (span, attribute). Together they should tile ServerRequest.durationMs.
SERVER_PHASES = (
    ("ServerRequest", "parseMs"),
    ("DeviceDispatch", "lockWaitMs"),
    ("DeviceDispatch", "planMs"),
    ("DeviceDispatch", "blocksMs"),
    ("DeviceDispatch", "paramsMs"),
    ("DeviceDispatch", "submitMs"),
    ("DeviceDispatch", "queueWaitMs"),
    ("DeviceDispatch", "dispatchMs"),
    ("DeviceDispatch", "launchMs"),
    ("DeviceDispatch", "deviceWaitMs"),
    ("DeviceDispatch", "d2hMs"),
    ("DeviceDispatch", "handoffMs"),
    ("ServerRequest", "assembleMs"),
    ("ServerRequest", "serializeMs"),
)


def dispatch_sum(trace, field: str):
    """Sum of `field` over the DeviceDispatch spans of a query that
    stayed on the device; None unless every one of them carries it."""
    found = [d.get(field) for d in spans(trace, "DeviceDispatch")
             if d.get("outcome") not in FALLBACK_OUTCOMES]
    if not found or any(v is None for v in found):
        return None
    return sum(found)


def named_phase_ms(trace):
    """Sum of SERVER_PHASES over a query's tree; None where the tree has
    no `lockWaitMs` (a program without the attributes)."""
    if dispatch_sum(trace, "lockWaitMs") is None:
        return None
    return sum(s.get(field) or 0.0 for name, field in SERVER_PHASES
               for s in spans(trace, name))


def sent_wall_ns(record: dict) -> int:
    return int((record["done_wall"]
                - (record["done_s"] - record["sent_s"])) * 1e9)


def _chain(node, name: str, path=()):
    """Paths (root ... span) to every span called `name`."""
    if not isinstance(node, dict):
        return []
    path = path + (node,)
    found = [path] if node.get("operator") == name else []
    for child in node.get("children", ()):
        found += _chain(child, name, path)
    return found


def launches(records: list) -> list:
    """[(record, path root..DeviceDispatch)] of every dispatch that
    carries the launch's wall-clock stamps."""
    out = []
    for r in records:
        for path in _chain(r.get("trace"), "DeviceDispatch"):
            d = path[-1]
            if d.get("launchNs") and d.get("readyNs") and d.get("startNs"):
                out.append((r, path))
    return out


def busy_and_gaps(found: list):
    """(busy ns, [(gap start, gap end)], window ns) of the union of the
    launches' [launchNs, readyNs]: the program's own view of when the
    device had work, an upper bound on when it ran."""
    spans_ns = sorted({(p[-1]["launchNs"], p[-1]["readyNs"])
                       for _r, p in found})
    busy, gaps, end = 0, [], None
    for a, b in spans_ns:
        if end is None or a > end:
            if end is not None:
                gaps.append((end, a))
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    window = (end - spans_ns[0][0]) if spans_ns else 0
    return busy, gaps, window


def _ms(span: dict, field: str) -> int:
    return int((span.get(field) or 0.0) * 1e6)


def before_launch(record: dict, path: tuple) -> list:
    """[(phase, start ns, end ns)] of what a query did up to its launch,
    earliest first: client (before it was sent), http, broker, wire,
    scheduler_wait, parse, lock_wait, plan, blocks, params, submit,
    ring_wait, dispatch (ring path). What lies between two of them has no name."""
    dispatch = path[-1]
    by_name = {s.get("operator"): s for s in path}
    sent = sent_wall_ns(record)
    segs = [("client", float("-inf"), sent)]
    broker = path[0] if path[0].get("startNs") else None
    scatter = by_name.get("ServerScatter")
    request = by_name.get("ServerRequest")
    if broker is not None:
        segs.append(("http", sent, broker["startNs"]))
        if scatter is not None and scatter.get("startNs"):
            segs.append(("broker", broker["startNs"], scatter["startNs"]))
    if request is not None and request.get("startNs"):
        read = request["startNs"] - _ms(request, "queueWaitMs")
        if scatter is not None and scatter.get("startNs"):
            segs.append(("wire", scatter["startNs"], read))
        segs.append(("scheduler_wait", read, request["startNs"]))
        segs.append(("parse", request["startNs"],
                     request["startNs"] + _ms(request, "parseMs")))
    t = dispatch["startNs"]
    for phase, field in (("lock_wait", "lockWaitMs"), ("plan", "planMs"),
                         ("blocks", "blocksMs"), ("params", "paramsMs")):
        segs.append((phase, t, t + _ms(dispatch, field)))
        t += _ms(dispatch, field)
    launch = dispatch["launchNs"]
    popped = launch - _ms(dispatch, "dispatchMs")
    ring = popped - _ms(dispatch, "queueWaitMs")
    segs.append(("submit", ring - _ms(dispatch, "submitMs"), ring))
    segs.append(("ring_wait", ring, popped))
    segs.append(("dispatch", popped, launch))
    return segs


def after_ready(record: dict, path: tuple) -> list:
    """[(phase, start, end)] of what a query did once its result was
    ready: prev:d2h, prev:handoff, prev:assemble, prev:serialize,
    prev:return (the
    server's answer on the wire, the broker's reduce, HTTP out, up to
    the client's clock), then client: the load generator between that
    answer and its next query."""
    dispatch = path[-1]
    request = {s.get("operator"): s for s in path}.get("ServerRequest")
    t = dispatch["readyNs"]
    segs = [("prev:d2h", t, t + _ms(dispatch, "d2hMs"))]
    t = dispatch["startNs"] + _ms(dispatch, "durationMs")
    segs.append(("prev:handoff", t - _ms(dispatch, "handoffMs"), t))
    done = int(record["done_wall"] * 1e9)
    if request is not None and request.get("startNs"):
        segs.append(("prev:assemble", t, t + _ms(request, "assembleMs")))
        end = request["startNs"] + _ms(request, "durationMs")
        segs.append(("prev:serialize", end - _ms(request, "serializeMs"), end))
        t = end
    segs.append(("prev:return", t, done))
    segs.append(("client", done, float("inf")))
    return segs


def lay(gap: tuple, segs: list, into: dict, floor: float = float("-inf")):
    """Add to `into` the length of `gap` under each phase of `segs`, none
    counted twice and none before `floor`; returns the length laid."""
    lo, hi = gap
    cursor, laid = max(lo, floor), 0
    for phase, a, b in sorted(segs, key=lambda s: s[1]):
        a, b = max(a, cursor), min(b, hi)
        if b > a:
            into[phase] = into.get(phase, 0) + (b - a)
            laid += b - a
            cursor = b
    return laid


def idle_by_phase(records: list):
    """{"busy_ns", "window_ns", "idle_ns", "phases": {phase: ns}}: every
    idle gap of the program's own busy union laid against the phases of
    the query whose launch ends it (of a coalesced launch: the member
    that reached the ring last), and, before that query was sent,
    against what the query did whose result opened the gap. None where
    no dispatch carries the stamps."""
    found = launches(records)
    if not found:
        return None
    busy, gaps, window = busy_and_gaps(found)
    by_launch, by_ready = {}, {}
    for r, path in found:
        d = path[-1]
        best = by_launch.get(d["launchNs"])
        if best is None or (d.get("queueWaitMs") or 0.0) \
                < (best[1][-1].get("queueWaitMs") or 0.0):
            by_launch[d["launchNs"]] = (r, path)
        by_ready.setdefault(d["readyNs"], (r, path))
    phases = {}
    for gap in gaps:
        r, path = by_launch[gap[1]]
        segs = before_launch(r, path)
        sent = segs[0][2]
        lay(gap, segs[1:], phases, floor=sent)
        if sent > gap[0]:
            # before it was sent: what the query did whose result opened
            # the gap, then the client; without one, all the client's
            opener = by_ready.get(gap[0])
            lay((gap[0], min(sent, gap[1])),
                after_ready(*opener) if opener else segs[:1], phases)
    return {"busy_ns": busy, "window_ns": window,
            "idle_ns": sum(b - a for a, b in gaps), "phases": phases}


def _request_phases(trace) -> dict:
    """{phase: ms} of one query's server request(s): SERVER_PHASES by
    attribute name, and the three places the rest can lie."""
    out = {field: sum(s.get(field) or 0.0 for s in spans(trace, name))
           for name, field in SERVER_PHASES}
    for path in _chain(trace, "DeviceDispatch"):
        dispatch = path[-1]
        request = {s.get("operator"): s for s in path}.get("ServerRequest")
        if request is None or "lockWaitMs" not in dispatch:
            continue
        on_dispatch = sum(dispatch.get(f) or 0.0 for n, f in SERVER_PHASES
                          if n == "DeviceDispatch")
        before = (dispatch["startNs"] - request["startNs"]) / 1e6
        out["(before dispatch)"] = out.get("(before dispatch)", 0.0) \
            + before - (request.get("parseMs") or 0.0)
        out["(inside dispatch)"] = out.get("(inside dispatch)", 0.0) \
            + dispatch["durationMs"] - on_dispatch
        out["(after dispatch)"] = out.get("(after dispatch)", 0.0) \
            + request["durationMs"] - before - dispatch["durationMs"] \
            - (request.get("assembleMs") or 0.0) \
            - (request.get("serializeMs") or 0.0)
    return out


def phase_report(records: list, slowest: int = 3) -> list:
    """Lines for a person: the median of every phase of a server request
    over the window, with the unnamed rest by where it lies; and the
    slowest queries at the client, each with its three longest phases."""
    traced = [r for r in records if r.get("trace") is not None
              and r["rows"] is not None
              and named_phase_ms(r["trace"]) is not None]
    if not traced:
        return []
    each = [_request_phases(r["trace"]) for r in traced]
    lines = ["server request by phase (median ms): " + " ".join(
        f"{k} {statistics.median(p.get(k, 0.0) for p in each):.3f}"
        for k in each[0])]
    by_latency = sorted(zip(traced, each),
                        key=lambda re: re[0]["sent_s"] - re[0]["done_s"])
    for r, phases in by_latency[:slowest]:
        outside = (r["done_s"] - r["sent_s"]) * 1e3 - sum(
            s["durationMs"] for s in spans(r["trace"], "ServerRequest"))
        top = sorted([*phases.items(), ("(outside the server request)",
                                        outside)],
                     key=lambda kv: -kv[1])[:3]
        lines.append(
            f"slow query {(r['done_s'] - r['sent_s']) * 1e3:.1f} ms at "
            f"{r['sent_s']:.2f} s: " + ", ".join(f"{k} {v:.1f}"
                                                 for k, v in top))
    return lines
