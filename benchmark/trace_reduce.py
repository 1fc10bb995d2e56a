"""From a profiler trace to numbers: plain functions over lists of
(name, start_ns, duration_ns) device events, and one adapter that reads
them out of an .xplane.pb with jax.profiler.ProfileData.

Run as a program (`trace_reduce.py <dir> <out.json> [--cpu-rehearsal]`)
it reduces the trace under <dir>: the harness does that in a process of
its own, with JAX held to the CPU, once the server has let go of the
chip."""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys

#: idle gaps are bucketed by length, in ms
GAP_EDGES_MS = (0.01, 0.1, 1.0, 10.0, 100.0)


def busy_union_ns(events: list) -> int:
    """Nanoseconds in which at least one of the events ran."""
    busy, end = 0, None
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            busy += dur
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy


def idle_gaps_ns(events: list) -> list:
    """Lengths of the gaps between the events' union's intervals."""
    gaps, end = [], None
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        if end is not None and start > end:
            gaps.append(start - end)
        end = max(end or 0, start + dur)
    return gaps


def gap_buckets(gaps_ns: list) -> list:
    """[[label, seconds]] by decade of length, longest total first. The
    program has no span on the profiler's clock, so what the host did in
    a gap is not attributed."""
    edges = [0.0, *GAP_EDGES_MS, float("inf")]
    out = []
    for lo, hi in zip(edges, edges[1:]):
        inside = [g for g in gaps_ns if lo <= g / 1e6 < hi]
        if inside:
            span = f"{lo:g}-{hi:g}_ms" if hi != float("inf") else f"over_{lo:g}_ms"
            out.append([f"host:not_attributed_{len(inside)}_gaps_of_{span}",
                        sum(inside) / 1e9])
    return sorted(out, key=lambda b: -b[1])[:10]


def top_ops(events: list, n: int = 10) -> list:
    """[[name, seconds]] of the n operations that took most device time."""
    total = {}
    for name, _start, dur in events:
        total[name] = total.get(name, 0) + dur
    return [[k, v / 1e9] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_share(busy_s: float, window_s: float) -> float:
    return 1.0 - busy_s / window_s


def queries_in_window(records: list, lo_wall: float, hi_wall: float) -> float:
    """Queries' worth of work done inside [lo, hi] on time.time()'s
    clock: each query counts by the share of its own life that fell
    inside, so that seconds-long queries cut by the edge count in part."""
    n = 0.0
    for r in records:
        done = r["done_wall"]
        sent = done - (r["done_s"] - r["sent_s"])
        inside = min(done, hi_wall) - max(sent, lo_wall)
        if inside > 0 and done > sent:
            n += inside / (done - sent)
    return n


def reduce_events(by_device: dict) -> dict:
    """The per-device events to busy seconds (mean over the chips) and
    the breakdown."""
    busy = [busy_union_ns(ev) / 1e9 for ev in by_device.values()]
    every = [e for ev in by_device.values() for e in ev]
    first = min(by_device, key=str)
    return {
        "busy_s": sum(busy) / len(busy),
        "events": len(every),
        "device_ops": top_ops(every),
        "idle_gaps": gap_buckets(idle_gaps_ns(by_device[first])),
    }


# -- the adapter ------------------------------------------------------------
def name_ops(ops: list, modules: list) -> list:
    """An operation's event is named by its whole HLO text: keep what
    stands before ` = `, and put the program (the `XLA Modules` event it
    ran inside) in front: `jit_kernel(123)/multiply_select_fusion`."""
    modules = sorted(modules, key=lambda e: e[1])
    starts = [m[1] for m in modules]
    out = []
    for text, start, dur in ops:
        name = text.split(" = ")[0].lstrip("%")
        k = bisect.bisect_right(starts, start) - 1
        if k >= 0 and start < modules[k][1] + modules[k][2]:
            name = f"{modules[k][0]}/{name}"
        out.append((name, start, dur))
    return out


def device_lines(planes: dict, rehearsal: bool) -> dict:
    """{plane: {line: events}} -> {device plane: named operation events}.
    Device planes are `/device:TPU:<n>`; of their lines `XLA Ops` holds
    one event an operation (`XLA Modules` spans whole programs and would
    count every nanosecond twice). A profile without such a plane gives
    {}: the run then fails, it never reads the host's threads as a
    device. Only the rehearsal on XLA:CPU, which has no device plane and
    prints no result, lets the host's XLA threads stand in, to exercise
    the code."""
    by_device = {}
    for plane, lines in planes.items():
        if plane.startswith("/device:TPU:") and lines.get("XLA Ops"):
            by_device[plane] = name_ops(lines["XLA Ops"],
                                        lines.get("XLA Modules", []))
    if by_device or not rehearsal:
        return by_device
    for plane, lines in planes.items():
        if plane.startswith("/host:"):
            ev = [e for name, events in lines.items()
                  if "xla" in name.lower() or "tf_" in name.lower()
                  for e in events]
            if ev:
                by_device[plane] = ev
    return by_device


def read_xplane(path: str) -> dict:
    """{plane: {line: [(name, start_ns, dur_ns)]}} of an .xplane.pb."""
    from jax.profiler import ProfileData
    return {plane.name: {line.name: [(ev.name, int(ev.start_ns),
                                      int(ev.duration_ns))
                                     for ev in line.events]
                         for line in plane.lines}
            for plane in ProfileData.from_file(path).planes}


def main(argv: list) -> int:
    trace_dir, out, *flags = argv
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        print(f"trace_reduce: no .xplane.pb under {trace_dir}",
              file=sys.stderr)
        return 1
    planes = read_xplane(found[0])
    by_device = device_lines(planes, "--cpu-rehearsal" in flags)
    result = {"planes": {p: {k: len(v) for k, v in lines.items()}
                         for p, lines in planes.items()},
              "xplane_bytes": os.path.getsize(found[0])}
    if by_device:
        result.update(reduce_events(by_device))
    with open(out, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
