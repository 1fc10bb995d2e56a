"""Per-query distributed tracing: span trees + cross-process propagation.

Reference parity: pinot-spi trace/Tracing.java:45 — a registry holding one
Tracer; every operator wraps nextBlock() in an InvocationScope
(core/operator/BaseOperator.java:47) recording operator class + rows/docs;
enabled per query via the trace=true query option and returned in the
broker response. The reference stops at process edges; here the tree
crosses them:

* ``TraceContext`` (traceId, parent spanId, sampled) travels on every
  wire hop — broker→server requests, MSE ``submit_stage``, cache-fabric
  ops, minion task params — and each remote side opens its OWN span tree
  (``RequestTrace`` with the inherited trace id), shipping it back in
  response metadata so the broker stitches ONE cross-process tree
  (``SpanHandle.graft``).
* ``SpanHandle`` is the explicit thread-safe span API for code that runs
  OFF the request thread (the dispatch ring's launch/fetch pools, the
  broker's scatter fan-out): capture a handle where the contextvar is
  live (``capture()``), attach children/attrs from any thread later.
  Contextvar-scoped ``Scope``/``annotate`` stay for same-thread code.

All tree mutation goes through one module lock: span operations are rare
(tens per query) relative to the work they time, so a coarse lock is
cheaper than per-node locks and makes cross-thread appends race-free.

Two clocks a span: ``perf_counter`` times it (``durationMs``), and
``time.time_ns()`` at its opening PLACES it (``startNs``, epoch ns).
CLOCK_REALTIME is what the load generator, the broker, the server and
the jax profiler's xplane all stamp with on one machine, so that one
integer lays client, broker, server and device on one axis. Stamps
inside a span (``launchNs``/``readyNs`` on DeviceDispatch) are the same
clock.

The collector is charged to the spans it stops: once ``install_gc_probe``
has run in a process, a span that closes gets ``gcPauseMs`` /
``gcCollections`` for the collections inside its interval (absent when
there were none). A collection holds the interpreter, so every span open
at that moment was stopped by it.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import contextvars
import gc
import threading
import time
import uuid
from dataclasses import dataclass, field
from operator import itemgetter
from typing import Any, Callable, Dict, List, Optional

from pinot_tpu.utils.metrics import get_registry

_current: contextvars.ContextVar[Optional["TraceNode"]] = \
    contextvars.ContextVar("pinot_tpu_trace", default=None)
_request: contextvars.ContextVar[Optional["RequestTrace"]] = \
    contextvars.ContextVar("pinot_tpu_trace_req", default=None)

#: one lock for ALL tree mutation (child appends, attr updates): handles
#: attach spans from pool threads while the request thread keeps building
_tree_lock = threading.Lock()


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    return uuid.uuid4().hex[:8]


@dataclass
class TraceContext:
    """What crosses a wire hop: enough for the remote side to join the
    trace (trace id), parent its tree (span id), and know whether the
    client asked for the trace back (sampled) — tail capture collects
    either way; sampled only controls the client-visible traceInfo."""

    trace_id: str
    span_id: str = ""
    sampled: bool = False

    def to_wire(self) -> dict:
        return {"traceId": self.trace_id, "spanId": self.span_id,
                "sampled": self.sampled}

    @classmethod
    def from_wire(cls, d: Optional[dict]) -> Optional["TraceContext"]:
        if not d or not d.get("traceId"):
            return None
        return cls(trace_id=str(d["traceId"]),
                   span_id=str(d.get("spanId", "")),
                   sampled=bool(d.get("sampled")))


@dataclass
class TraceNode:
    operator: str
    start_ms: float = 0.0
    duration_ms: float = 0.0
    #: wall-clock opening (time.time_ns()); 0 = never opened
    start_ns: int = 0
    attrs: Dict[str, Any] = field(default_factory=dict)
    children: List["TraceNode"] = field(default_factory=list)

    def to_dict(self) -> dict:
        with _tree_lock:
            return self._to_dict_locked()

    def _to_dict_locked(self) -> dict:
        return {"operator": self.operator,
                "durationMs": round(self.duration_ms, 3),
                **({"startNs": self.start_ns} if self.start_ns else {}),
                **self.attrs,
                **({"children": [c._to_dict_locked()
                                 for c in self.children]}
                   if self.children else {})}

    @classmethod
    def from_dict(cls, d: dict) -> "TraceNode":
        """Inverse of to_dict — rebuilds a remote side's shipped tree so
        the broker can graft it into its own."""
        attrs = {k: v for k, v in d.items()
                 if k not in ("operator", "durationMs", "startNs",
                              "children")}
        node = cls(operator=str(d.get("operator", "?")),
                   duration_ms=float(d.get("durationMs", 0.0) or 0.0),
                   start_ns=int(d.get("startNs", 0) or 0),
                   attrs=attrs)
        node.children = [cls.from_dict(c) for c in d.get("children", ())]
        return node


class SpanHandle:
    """Explicit thread-safe handle on one span: the capture-and-attach
    API for code paths where contextvars don't flow (the dispatch ring's
    pools, broker fan-out threads, MSE stage threads)."""

    __slots__ = ("node", "trace_id")

    def __init__(self, node: TraceNode, trace_id: Optional[str] = None):
        self.node = node
        #: the enclosing request's trace id, for code off the request
        #: thread that tags side channels with it (the dispatch ring's
        #: profiler annotations); children inherit it
        self.trace_id = trace_id

    def child(self, operator: str, **attrs) -> "SpanHandle":
        """Open a child span (timing starts now); end it with .end()."""
        n = TraceNode(operator, attrs=dict(attrs))
        n.start_ms = time.perf_counter() * 1000.0
        n.start_ns = time.time_ns()
        with _tree_lock:
            self.node.children.append(n)
        return SpanHandle(n, self.trace_id)

    def end(self, **attrs) -> None:
        with _tree_lock:
            if attrs:
                self.node.attrs.update(attrs)
            if self.node.duration_ms == 0.0 and self.node.start_ms:
                end_ms = time.perf_counter() * 1000.0
                self.node.duration_ms = end_ms - self.node.start_ms
                probe = _gc_probe
                if probe is not None \
                        and probe.last_stop_ms > self.node.start_ms:
                    self.node.attrs.update(
                        probe.attrs(self.node.start_ms, end_ms))

    def set(self, **attrs) -> None:
        with _tree_lock:
            self.node.attrs.update(attrs)

    def get(self, name: str, default: Any = None) -> Any:
        with _tree_lock:
            return self.node.attrs.get(name, default)

    @contextlib.contextmanager
    def scope(self, operator: str, **attrs):
        """Context-manager child span on THIS handle (no contextvar):
        thread-safe timing for worker-thread code."""
        h = self.child(operator, **attrs)
        try:
            yield h
        finally:
            h.end()

    def graft(self, tree: Optional[dict]) -> None:
        """Attach a remote side's shipped span tree (to_dict form) as a
        child — the stitch point for cross-process traces."""
        if not tree:
            return
        try:
            node = TraceNode.from_dict(tree)
        except Exception:  # noqa: BLE001 — a torn tree must not fail a query
            return
        with _tree_lock:
            self.node.children.append(node)

    @contextlib.contextmanager
    def activate(self):
        """Make this span the contextvar-current node for the calling
        thread, so same-thread Scope/annotate instrumentation (cache
        tiers, segment executors) lands under it."""
        token = _current.set(self.node)
        try:
            yield self
        finally:
            _current.reset(token)


class Scope:
    """Ref InvocationScope (try-with-resources around nextBlock)."""

    def __init__(self, operator: str, **attrs):
        self.node = TraceNode(operator, attrs=dict(attrs))
        self._token = None
        self._active = False

    def __enter__(self) -> "Scope":
        parent = _current.get()
        if parent is not None:
            with _tree_lock:
                parent.children.append(self.node)
            self._token = _current.set(self.node)
            self._active = True
            self.node.start_ms = time.perf_counter() * 1000.0
            self.node.start_ns = time.time_ns()
        return self

    def set(self, **attrs) -> None:
        if self._active:
            with _tree_lock:
                self.node.attrs.update(attrs)

    def __exit__(self, *exc):
        if self._active:
            end_ms = time.perf_counter() * 1000.0
            self.node.duration_ms = end_ms - self.node.start_ms
            _current.reset(self._token)
            probe = _gc_probe
            if probe is not None and probe.last_stop_ms > self.node.start_ms:
                paused = probe.attrs(self.node.start_ms, end_ms)
                if paused:
                    with _tree_lock:
                        self.node.attrs.update(paused)


class RequestTrace:
    """Root span for one request (broker query, server request, MSE
    stage, minion task); activates contextvar tracing for the opening
    thread and carries the trace identity."""

    def __init__(self, request_id: Any = 0, operator: str = "BrokerRequest",
                 trace_id: Optional[str] = None, sampled: bool = True,
                 **attrs):
        self.trace_id = trace_id or new_trace_id()
        #: did the CLIENT ask for the trace back (trace=true)? Tail
        #: capture stores the tree either way; this gates traceInfo.
        self.sampled = sampled
        self.root = TraceNode(operator,
                              attrs={"requestId": request_id,
                                     "traceId": self.trace_id, **attrs})
        self._token = None
        self._req_token = None

    def __enter__(self) -> "RequestTrace":
        self.root.start_ms = time.perf_counter() * 1000.0
        self.root.start_ns = time.time_ns()
        self._token = _current.set(self.root)
        self._req_token = _request.set(self)
        return self

    def __exit__(self, *exc):
        root = self.root
        end_ms = time.perf_counter() * 1000.0
        root.duration_ms = end_ms - root.start_ms
        _current.reset(self._token)
        _request.reset(self._req_token)
        probe = _gc_probe
        if probe is not None:
            # the root also says where the process's running total stood,
            # so pauses between requests show as the difference, and lists
            # each long pause with its wall-clock stamp
            paused = probe.attrs(root.start_ms, end_ms, root.start_ns)
            with _tree_lock:
                root.attrs.update(paused, gcTotalMs=round(probe.total_ms, 3))
            probe.feed()

    def handle(self) -> SpanHandle:
        return SpanHandle(self.root, self.trace_id)

    def wire_context(self) -> dict:
        """The TraceContext dict shipped on outgoing hops."""
        return TraceContext(self.trace_id, new_span_id(),
                            self.sampled).to_wire()

    def to_dict(self) -> dict:
        return self.root.to_dict()


def active() -> bool:
    return _current.get() is not None


def capture() -> Optional[SpanHandle]:
    """Thread-safe handle on the CURRENT span (None when tracing is off)
    — capture on the request thread, attach from any thread later."""
    node = _current.get()
    return None if node is None else SpanHandle(node, current_trace_id())


def current_trace_id() -> Optional[str]:
    """Trace id of the enclosing RequestTrace (None when untraced) —
    side channels (cache-op headers, task params) stamp it on requests
    so remote logs correlate back to the query."""
    req = _request.get()
    return None if req is None else req.trace_id


def current_request() -> Optional["RequestTrace"]:
    """The enclosing RequestTrace, if the calling thread runs under one
    — lets deep layers (the MSE dispatcher parsing its own options) flip
    `sampled` on the request they ride."""
    return _request.get()


def get_attr(name: str, default: Any = None) -> Any:
    """Read an attr off the CURRENT trace node (default when tracing is
    off or the attr is unset) — lets cross-cutting annotators implement
    set-if-absent / dominance rules."""
    node = _current.get()
    if node is None:
        return default
    with _tree_lock:
        return node.attrs.get(name, default)


def annotate(**attrs) -> None:
    """Attach attrs to the CURRENT trace node (no-op when tracing is off).
    Used for cross-cutting marks like cacheHit that belong to whichever
    operator is running, not to a new child scope."""
    node = _current.get()
    if node is not None:
        with _tree_lock:
            node.attrs.update(attrs)


# -- the collector -------------------------------------------------------------
#: a pause at least this long is listed by itself on its request's root
GC_LONG_MS = 10.0
#: collections the ring keeps (cut back to this many at twice as many): a
#: span that outlives them is charged only the newest
_GC_RING = 4096

_stop_ms = itemgetter(1)


class GcProbe:
    """The process's one `gc.callbacks` entry. Each collection's
    generation, start and stop (ms on the spans' perf_counter clock) go
    into a bounded ring that closing spans bisect, its length into the
    running total and into `pending`, which the role's registry takes in
    as `gc_pause_ms{generation=}` when it is read or a request closes.
    The callback takes no lock and calls no registry: a collection can
    start anywhere, a registry's own lock held by the same thread.
    `annotation(generation)`, where given, is a context manager entered
    at the start and left at the stop (the server's `pinot:gc`)."""

    def __init__(self, role: str,
                 annotation: Optional[Callable[[int], Any]] = None):
        self.role = role
        self.annotation = annotation
        #: (start_ms, stop_ms, generation), oldest first; replaced when
        #: cut, never cut in place, so a reader's reference stays whole
        self.ring: List[tuple] = []
        #: the newest collection's stop: a span that opened after it was
        #: stopped by none, which is all a closing span asks most often
        self.last_stop_ms = float("-inf")
        self.total_ms = 0.0
        self.pending: collections.deque = collections.deque(maxlen=_GC_RING)
        self._start_ms = 0.0
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        now = time.perf_counter() * 1000.0
        gen = info["generation"]
        if phase == "start":
            self._start_ms = now
            if self.annotation is not None:
                opened = self.annotation(gen)
                opened.__enter__()
                self._open = opened
            return
        opened, self._open = self._open, None
        if opened is not None:
            opened.__exit__(None, None, None)
        start = self._start_ms
        ring = self.ring
        if len(ring) >= 2 * _GC_RING:
            ring = self.ring = ring[-_GC_RING:]
        ring.append((start, now, gen))
        self.last_stop_ms = now
        self.total_ms += now - start
        self.pending.append((gen, now - start))

    def attrs(self, start_ms: float, end_ms: float,
              start_ns: int = 0) -> dict:
        """`gcPauseMs` / `gcCollections` for the pauses inside [start_ms,
        end_ms], {} where none fell there. Given the span's `start_ns` (a
        root's), also `gcByGeneration` ([n0, n1, n2]) and, where there
        were any, `gcLongPauses`: [generation, startNs, ms] of each pause
        of GC_LONG_MS or more."""
        ring = self.ring
        paused, gens, long_pauses = 0.0, [0, 0, 0], []
        for k in range(bisect.bisect_right(ring, start_ms, key=_stop_ms),
                       len(ring)):
            s, e, gen = ring[k]
            if s >= end_ms:
                break
            paused += min(e, end_ms) - max(s, start_ms)
            gens[gen] += 1
            if start_ns and e - s >= GC_LONG_MS:
                long_pauses.append(
                    [gen, start_ns + int((s - start_ms) * 1e6),
                     round(e - s, 3)])
        n = sum(gens)
        if not n:
            return {}
        out = {"gcPauseMs": round(paused, 3), "gcCollections": n}
        if start_ns:
            out["gcByGeneration"] = gens
        if long_pauses:
            out["gcLongPauses"] = long_pauses
        return out

    def feed(self) -> None:
        """Hand the pauses since the last feed to the role's registry."""
        pending = self.pending
        if not pending:
            return
        reg = get_registry(self.role)
        while True:
            try:
                gen, ms = pending.popleft()
            except IndexError:
                return
            reg.add_timing("gc_pause_ms", ms,
                           labels={"generation": str(gen)})


_gc_probe: Optional[GcProbe] = None
_gc_install_lock = threading.Lock()


def install_gc_probe(role: str,
                     annotation: Optional[Callable[[int], Any]] = None
                     ) -> GcProbe:
    """The process's one collector probe, installed on the first call and
    returned by every later one, which registers nothing more: the first
    role's registry keeps it, and an annotation given later is taken
    where there was none."""
    global _gc_probe
    with _gc_install_lock:
        probe = _gc_probe
        if probe is None:
            probe = GcProbe(role, annotation)
            get_registry(role).add_feed(probe.feed)
            gc.callbacks.append(probe)
            _gc_probe = probe
        elif annotation is not None and probe.annotation is None:
            probe.annotation = annotation
        return probe
