"""Metrics registry: meters, gauges, timers + Prometheus text exposition.

Reference parity: pinot-spi metrics/PinotMetricsRegistry.java + the typed
role registries over AbstractMetrics (pinot-common metrics/ —
ServerMetrics/BrokerMetrics/ControllerMetrics/MinionMetrics with per-role
meter/gauge/timer enums, exported via JMX). Here one thread-safe registry
with the same meter/gauge/timer trio, exported as Prometheus text
(the modern equivalent of the JMX reporter).
"""
from __future__ import annotations

import math
import random
import threading
import time
from collections import defaultdict
from typing import Callable, Dict, Iterator, List, Optional, Tuple

_Key = Tuple[str, Tuple[Tuple[str, str], ...]]


def _key(name: str, labels: Optional[Dict[str, str]]) -> _Key:
    return (name, tuple(sorted((labels or {}).items())))


class Timer:
    """count/sum/max plus p50/p95/p99 from a fixed-size reservoir
    (Vitter's algorithm R — every observation has equal probability of
    being sampled, so tails survive long runs; a keep-last-N window
    would forget cold-start latencies the moment traffic warms up)."""

    __slots__ = ("count", "total_ms", "max_ms", "_reservoir", "_rng")

    RESERVOIR_SIZE = 256

    def __init__(self):
        self.count = 0
        self.total_ms = 0.0
        self.max_ms = 0.0
        self._reservoir: List[float] = []
        # private PRNG: seeded for reproducible tests, and never touches
        # the global random state
        self._rng = random.Random(0x5EED)

    def update(self, ms: float) -> None:
        self.count += 1
        self.total_ms += ms
        self.max_ms = max(self.max_ms, ms)
        if len(self._reservoir) < self.RESERVOIR_SIZE:
            self._reservoir.append(ms)
        else:
            j = self._rng.randrange(self.count)
            if j < self.RESERVOIR_SIZE:
                self._reservoir[j] = ms

    def snapshot(self) -> "Timer":
        """A detached consistent copy (counters + reservoir). Callers
        must take it under whatever lock serializes update() — the
        registry does (MetricsRegistry.timer); standalone Timers (the
        adaptive selector's reservoirs) snapshot under their owner's
        lock."""
        t = Timer.__new__(Timer)
        t.count = self.count
        t.total_ms = self.total_ms
        t.max_ms = self.max_ms
        t._reservoir = list(self._reservoir)
        t._rng = random.Random(0x5EED)
        return t

    def quantile(self, q: float) -> float:
        """Empirical quantile estimate from the reservoir (0 when no
        observations yet)."""
        if not self._reservoir:
            return 0.0
        s = sorted(self._reservoir)
        idx = min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))
        return s[idx]

    @property
    def samples(self) -> Tuple[float, ...]:
        """Snapshot of the reservoir's per-observation samples (ms) —
        consumers pooling tails across several timers (e.g. the broker's
        adaptive hedge delay over per-server reservoirs) read the raw
        samples instead of mixing already-collapsed quantiles."""
        return tuple(self._reservoir)


class MetricsRegistry:
    """Ref PinotMetricsRegistry — meters (counters), gauges, timers."""

    def __init__(self, role: str = "server"):
        self.role = role
        self._meters: Dict[_Key, float] = defaultdict(float)
        self._gauges: Dict[_Key, float] = {}
        self._timers: Dict[_Key, Timer] = defaultdict(Timer)
        #: per-timer last trace id (exemplar): links a /metrics tail to
        #: the stored trace at /debug/traces/<id>
        self._exemplars: Dict[_Key, str] = {}
        self._lock = threading.Lock()
        #: called before each read of the whole registry, outside its
        #: lock: sources that cannot write as things happen (the
        #: collector's probe, tracing.GcProbe) hand over what they hold
        self._feeds: List[Callable[[], None]] = []

    def add_feed(self, feed: Callable[[], None]) -> None:
        with self._lock:
            self._feeds.append(feed)

    def _pull_feeds(self) -> None:
        with self._lock:
            feeds = list(self._feeds)
        for feed in feeds:
            feed()

    # -- write side ---------------------------------------------------------
    def add_meter(self, name: str, value: float = 1,
                  labels: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            self._meters[_key(name, labels)] += value

    def set_gauge(self, name: str, value: float,
                  labels: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            self._gauges[_key(name, labels)] = value

    def remove_gauge(self, name: str,
                     labels: Optional[Dict[str, str]] = None) -> bool:
        """Drop one labeled gauge series entirely. A gauge whose subject
        is GONE (a removed ingestion partition, an unloaded segment) must
        leave the exposition — zeroing it keeps the stale labeled series
        on /metrics forever, and dashboards aggregate it as live data.
        Returns whether the series existed."""
        with self._lock:
            return self._gauges.pop(_key(name, labels), None) is not None

    def add_timing(self, name: str, ms: float,
                   labels: Optional[Dict[str, str]] = None,
                   exemplar: Optional[str] = None) -> None:
        """exemplar: the trace id of the request this observation came
        from — the timer remembers the LAST one, so a tail spike on
        /metrics names a concrete stored trace to pull."""
        with self._lock:
            k = _key(name, labels)
            self._timers[k].update(ms)
            if exemplar:
                self._exemplars[k] = exemplar

    class _TimeCtx:
        def __init__(self, reg, name, labels):
            self.reg, self.name, self.labels = reg, name, labels

        def __enter__(self):
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self.reg.add_timing(self.name,
                                (time.perf_counter() - self.t0) * 1000.0,
                                self.labels)

    def time(self, name: str, labels: Optional[Dict[str, str]] = None):
        return MetricsRegistry._TimeCtx(self, name, labels)

    # -- read side ----------------------------------------------------------
    def meter(self, name: str, labels: Optional[Dict[str, str]] = None) -> float:
        with self._lock:
            return self._meters.get(_key(name, labels), 0.0)

    def gauge(self, name: str, labels: Optional[Dict[str, str]] = None):
        with self._lock:
            return self._gauges.get(_key(name, labels))

    def timer(self, name: str, labels: Optional[Dict[str, str]] = None) -> Timer:
        """A consistent SNAPSHOT of the timer (empty on miss). Taken
        under the registry lock: the previous implementation handed out
        the live Timer, whose reservoir list a concurrent update()
        mutates while quantile()/samples iterate it — and a detached
        EMPTY Timer on miss, silently dropping updates made through it.
        A snapshot is race-free either way; writes go through
        add_timing()."""
        with self._lock:
            t = self._timers.get(_key(name, labels))
            return t.snapshot() if t is not None else Timer()

    def set_exemplar(self, name: str,
                     labels: Optional[Dict[str, str]] = None,
                     trace_id: str = "") -> None:
        """Stamp a timer's exemplar out of band (wrappers that own the
        trace id but not the timing call)."""
        if not trace_id:
            return
        with self._lock:
            self._exemplars[_key(name, labels)] = trace_id

    def exemplar(self, name: str,
                 labels: Optional[Dict[str, str]] = None) -> Optional[str]:
        """Last trace id recorded against the timer (None when never)."""
        with self._lock:
            return self._exemplars.get(_key(name, labels))

    def sample(self) -> dict:
        """One timestamped FLAT snapshot of the whole registry — the
        unit the metrics history ring stores and the cluster rollup
        scrapes. Keys are ``name`` or ``name{k="v",...}`` (the exposition
        label syntax, so history consumers and /metrics agree on series
        identity); timers collapse to count/sum/max plus the reservoir
        quantiles. Taken under the registry lock: one sample is
        internally consistent."""
        self._pull_feeds()
        with self._lock:
            counters = {f"{n}{_fmt(ls)}": v
                        for (n, ls), v in self._meters.items()}
            gauges = {f"{n}{_fmt(ls)}": v
                      for (n, ls), v in self._gauges.items()}
            timers = {}
            for (n, ls), t in self._timers.items():
                timers[f"{n}{_fmt(ls)}"] = {
                    "count": t.count,
                    "sum_ms": round(t.total_ms, 3),
                    "max_ms": round(t.max_ms, 3),
                    "p50": round(t.quantile(0.5), 3),
                    "p95": round(t.quantile(0.95), 3),
                    "p99": round(t.quantile(0.99), 3),
                }
        return {"ts": time.time(), "role": self.role,
                "counters": counters, "gauges": gauges, "timers": timers}

    def prometheus_text(self) -> str:
        """Prometheus exposition format (the JMX-reporter analog).

        `# TYPE` is emitted once per metric NAME — two label sets of the
        same metric share one family header (duplicate TYPE lines are
        invalid exposition and make scrapers reject the whole page).
        `# HELP` rides beside it from the metric-name catalog
        (utils/metrics_catalog.py) for every cataloged family."""
        from pinot_tpu.utils.metrics_catalog import METRICS
        self._pull_feeds()
        out: List[str] = []
        prefix = f"pinot_tpu_{self.role}_"
        typed: set = set()

        def type_line(base: str, kind: str, name: str = "") -> None:
            if base not in typed:
                typed.add(base)
                desc = METRICS.get(name)
                if desc:
                    out.append(f"# HELP {base} {_escape_help(desc)}")
                out.append(f"# TYPE {base} {kind}")

        with self._lock:
            for (name, labels), v in sorted(self._meters.items()):
                type_line(f"{prefix}{name}", "counter", name)
                out.append(f"{prefix}{name}{_fmt(labels)} {_num(v)}")
            for (name, labels), v in sorted(self._gauges.items()):
                type_line(f"{prefix}{name}", "gauge", name)
                out.append(f"{prefix}{name}{_fmt(labels)} {_num(v)}")
            for (name, labels), t in sorted(self._timers.items()):
                base = f"{prefix}{name}"
                type_line(base, "summary", name)
                for q in (0.5, 0.95, 0.99):
                    qlabels = labels + (("quantile", f"{q:g}"),)
                    out.append(f"{base}{_fmt(qlabels)} {t.quantile(q):g}")
                out.append(f"{base}_count{_fmt(labels)} {t.count}")
                out.append(f"{base}_sum_ms{_fmt(labels)} {t.total_ms:g}")
                out.append(f"{base}_max_ms{_fmt(labels)} {t.max_ms:g}")
                ex = self._exemplars.get((name, labels))
                if ex:
                    # exemplar as a comment line: Prometheus text parsers
                    # skip non-HELP/TYPE comments, humans and tooling get
                    # the /metrics-tail -> /debug/traces/<id> link
                    out.append(f"# EXEMPLAR {base}{_fmt(labels)} "
                               f'trace_id="{_escape(ex)}"')
        return "\n".join(out) + "\n"


def _escape(v: str) -> str:
    """Label-value escaping per the exposition spec: backslash, quote,
    newline."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(v: str) -> str:
    """HELP-text escaping per the exposition spec: backslash, newline
    (quotes stay literal in HELP lines)."""
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


def _num(v: float) -> str:
    """Exact sample value: `:g` keeps six digits, which hides a
    kilobyte re-upload behind a gigabyte byte counter."""
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)


def _fmt(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape(v)}"' for k, v in labels)
    return "{" + inner + "}"


# role-level singletons (ref ServerMetrics.get() style accessors)
_registries: Dict[str, MetricsRegistry] = {}
_reg_lock = threading.Lock()


def get_registry(role: str = "server") -> MetricsRegistry:
    with _reg_lock:
        reg = _registries.get(role)
        if reg is None:
            reg = MetricsRegistry(role)
            _registries[role] = reg
        return reg
