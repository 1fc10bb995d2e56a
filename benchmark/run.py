#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the served path:

  client -> broker HTTP -> framed-TCP server -> QueryExecutor ->
  TpuOperatorExecutor -> dispatch ring -> device -> broker reduce

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time: builds the configuration's table from --seed,
starts controller, broker and ONE `StartServer --tpu` (JAX_PLATFORMS=tpu:
no chip, no run) from this parent, which never imports jax; loads, warms
the cell's own query shapes (that is set-up), drives the cell's traffic
for --seconds, stops the cluster, then makes the numpy reference from
the same seed and checks every answer the window got against it. Nothing about any one cell is written here: the cell, its
configuration, its traffic mix and each per-layer metric are files found
by the names in BENCHMARK.json.

Last line of stdout: {"correct", "attempted", "failed", "metrics",
"device", ["breakdown"], "checks"}; with --trace 0 the cell's end-to-end
metrics, with --trace 1 its per-layer metrics. --cpu-rehearsal runs toy
sizes on XLA:CPU to debug the harness: "correct" is false and the exit
code REHEARSAL_EXIT, never 0."""
from __future__ import annotations

import argparse
import importlib
import json
import multiprocessing
import os
import shutil
import signal
import statistics
import subprocess
import sys
import time

T_START = time.monotonic()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import numpy as np  # noqa: E402

import datagen  # noqa: E402
import judge  # noqa: E402
import loadgen  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
from cluster import Cluster, HarnessFailure, series_delta  # noqa: E402

REHEARSAL_EXIT = 10
HOST = "127.0.0.1"


def say(text: str) -> None:
    print(f"bench: {text}", flush=True)


# -- end-to-end metrics, at the client, over the whole window ---------------
def latencies_ms(records: list) -> list:
    """Of every query of the window that was answered: one that failed
    is counted under `failed`, and its quick error is no latency."""
    answered = [r for r in records if not r["failure"]]
    if not answered:
        raise HarnessFailure("no query of the window was answered")
    return [(r["done_s"] - r["sent_s"]) * 1e3 for r in answered]


def queries_per_s(records: list, seconds: float) -> float:
    """Every query answered inside the window over the whole window,
    --seconds: a stall anywhere in it, its last second too, costs the
    rate in full."""
    return sum(1 for r in records
               if not r["failure"] and r["done_s"] <= seconds) / seconds


END_TO_END = {
    "latency_p50_ms": lambda rec, s: statistics.median(latencies_ms(rec)),
    "latency_p95_ms": lambda rec, s: float(np.percentile(latencies_ms(rec), 95)),
    "queries_per_s": queries_per_s,
}


def cell_metrics(bench: dict, section: str, cell: str) -> list:
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


# -- set-up ------------------------------------------------------------------
def native_library() -> str:
    """Build the native library HERE unless it is there, or run without.
    It is compiled -march=native, so one built elsewhere must never
    load: git ignores it and the chip tool leaves it out of its copy, so
    one that is here was built here."""
    from pinot_tpu.native import build
    if os.path.exists(build.OUT):
        return "kept"
    if shutil.which("g++") is None:
        return "absent"
    try:
        build.build(verbose=False)
    except subprocess.CalledProcessError:
        return "absent"
    return "built"


def add_table(cluster: Cluster, work: str, config: dict) -> None:
    from pinot_tpu.tools import admin
    tc, schema = datagen.table_and_schema(config)
    paths = []
    for kind, obj in (("table", tc), ("schema", schema)):
        paths.append(os.path.join(work, "tables", f"{kind}.json"))
        with open(paths[-1], "w") as f:
            json.dump(obj.to_dict(), f)
    with open(os.devnull, "w") as quiet:
        stdout, sys.stdout = sys.stdout, quiet
        try:
            rc = admin.main(["AddTable", "--coordinator", cluster.coordinator,
                             "--table", paths[0], "--schema", paths[1]])
        finally:
            sys.stdout = stdout
    if rc != 0:
        raise HarnessFailure(f"AddTable {config['table']} failed")


def check_device(args, cell: dict, device: dict) -> None:
    say(f"server reports {json.dumps(device)}")
    if args.cpu_rehearsal:
        return
    if device.get("platform") != "tpu":
        raise HarnessFailure(f"server is not on a TPU: {device}")
    if device["count"] != cell["chips"]:
        raise HarnessFailure(f"the cell asks for {cell['chips']} chips, "
                             f"the server holds {device['count']}")
    peaks.peak(device["device_kind"])  # an unknown kind is an error


def build_table(args, cell, cluster, work, config, segments, docs) -> None:
    """Segments in pool workers, which make and build their first ones
    while the server claims its chip; none uploads before the table is
    there."""
    table_ready = os.path.join(work, "table_ready")
    jobs = [(config, args.seed, i, docs, os.path.join(work, "build"),
             cluster.coordinator, table_ready) for i in range(segments)]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(config["build_workers"], segments)) as pool:
        done = pool.imap_unordered(datagen.segment_job, jobs)
        check_device(args, cell, cluster.wait_server())
        add_table(cluster, work, config)
        open(table_ready, "w").close()
        for _i, worker_has_jax in done:
            if worker_has_jax:
                raise HarnessFailure("a segment worker imported jax")


def make_reference(config: dict, seed: int, segments: int, docs: int):
    """The plain reference of the table, from the seed alone: run once
    the window has closed and the cluster is stopped, so that it costs
    the set-up and the timed host nothing."""
    ref = reference.Reference(config, datagen.domains(config))
    jobs = [(config, seed, i, docs) for i in range(segments)]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(os.cpu_count() or 2, segments)) as pool:
        for share in pool.imap_unordered(datagen.share_job, jobs):
            ref.add(share)
    return ref


def wait_loaded(cluster: Cluster, config: dict, docs: int) -> None:
    """A LIMIT-only selection stays on the host and reports totalDocs
    over the segments the server has loaded so far."""
    sql = (f"SELECT {config['columns'][0]['name']} FROM {config['table']} "
           f"LIMIT 1 OPTION(skipCache=true)")

    def loaded():
        resp = loadgen.post(HOST, cluster.broker_port, sql, timeout=30)
        return resp.get("totalDocs") == docs and not resp.get("exceptions")
    cluster.wait(loaded, f"{config['table']} to load {docs} docs",
                 timeout=600.0)


def bucket(n: int) -> int:
    """The dispatch ring pads a batch to the next power of two and
    compiles one batched kernel a bucket, the first time it occurs."""
    return 1 << (n - 1).bit_length()


def warm_up(cluster: Cluster, mix: dict, queries: list) -> list:
    """The cell's own shapes and no others, traced (which costs nothing
    that is timed: every warm-up query has to show a DeviceDispatch span
    that stayed on the device). Each template `alone` times, one query
    after the other; then the cell's own loop in rounds of
    `loop_seconds`, until the server's compile counter has stood still
    for `quiet_loops` rounds AND a batch has formed that fills the
    largest bucket the cell's clients can: eight clients form a batch of
    five or more only once in thousands of queries, nothing from outside
    the program forms one sooner (a crowd of 16 or 24 clients did not,
    PERF.md section 6), and its first compile would else stall a window
    by a third of a second. `max_seconds` ends the wait; what compiles
    in the window after all is printed with it. Returns the records."""
    w = mix["warmup"]
    t0 = time.monotonic()
    port = cluster.broker_port
    records = []

    def batch_sizes(got: list) -> set:
        return {d["batchSize"] for r in got for d in
                judge.spans(r.get("trace"), "DeviceDispatch")
                if d.get("batchSize")}

    for t, template in enumerate(mix["templates"]):
        before = cluster.counters()
        got = [loadgen.one_query(HOST, port, q, True, time.perf_counter())
               for q in [q for q in queries if q[0] == t][:w["alone"]]]
        records += got
        compiles = series_delta(before, cluster.counters(), "kernel_retrace")
        say(f"warm-up {template['name']}: {len(got)} queries, {compiles:g} "
            f"compiles")
    rest, quiet, met = queries[::-1], 0, {1}  # literals not yet used
    while w["loop_seconds"] and time.monotonic() - t0 < w["max_seconds"] \
            and (quiet < w["quiet_loops"]
                 or bucket(max(met)) < bucket(mix["clients"])):
        before = cluster.counters()
        got = loadgen.closed_loop(HOST, port, rest, mix["clients"],
                                  w["loop_seconds"], traced=True)
        if not got:
            raise HarnessFailure("the warm-up ran out of queries")
        records += got
        rest = rest[len(got):]
        met |= batch_sizes(got)
        compiles = series_delta(before, cluster.counters(), "kernel_retrace")
        quiet = 0 if compiles else quiet + 1
        say(f"warm-up loop: {len(got)} queries, {compiles:g} compiles, "
            f"batch sizes met {sorted(met)}")
    bad = [r for r in records if r["failure"]]
    if bad:
        raise HarnessFailure(f"{len(bad)} of {len(records)} warm-up queries "
                             f"failed; first: {bad[0]['failure']}")
    return records


# -- the run -----------------------------------------------------------------
def run(args) -> dict:
    try:
        bench, cell, config, mix = traffic.load_cell(ROOT, args.workload)
    except KeyError as e:
        raise HarnessFailure(str(e)) from e
    import pinot_tpu  # noqa: F401 — without the program, fail before making anything

    segments, docs = config["segments"], config["docs_per_segment"]
    if args.cpu_rehearsal:
        segments = config["rehearsal"]["segments"]
        docs = config["rehearsal"]["docs_per_segment"]
    rows = segments * docs
    work = os.path.join(BENCH, "work", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    for sub in ("logs", "tmp", "build", "tables"):
        os.makedirs(os.path.join(work, sub))
    os.environ["TMPDIR"] = os.path.join(work, "tmp")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(BENCH, "cache", "jax")
    os.makedirs(cache_dir, exist_ok=True)
    native = native_library()  # before anything here imports the loader
    say(f"cell {cell['name']} seed {args.seed} seconds {args.seconds:g} "
        f"trace {args.trace}: {segments} x {docs} = {rows} rows, native "
        f"library {native}"
        + (" — CPU REHEARSAL, not a result" if args.cpu_rehearsal else ""))

    cluster = Cluster(work, cache_dir, args.cpu_rehearsal)
    try:
        return drive(args, bench, cell, config, mix, cluster, work,
                     segments, docs)
    finally:
        cluster.stop_all()
        if not args.keep:
            shutil.rmtree(work, ignore_errors=True)


def drive(args, bench, cell, config, mix, cluster, work, segments, docs):
    """Set-up: the chip looked for, the cluster started, the table built
    and loaded; then measure()."""
    rows = segments * docs
    t0 = time.monotonic()
    cluster.spawn_all()
    build_table(args, cell, cluster, work, config, segments, docs)
    t1 = time.monotonic()
    wait_loaded(cluster, config, rows)
    say(f"table built and uploaded in {t1 - t0:.1f}s, loaded "
        f"{time.monotonic() - t1:.1f}s later")

    def make_ref():
        t = time.monotonic()
        ref = make_reference(config, args.seed, segments, docs)
        say(f"reference made in {time.monotonic() - t:.1f}s")
        return ref
    return measure(args, bench, cell, config, mix, cluster, make_ref, rows,
                   work)


def measure(args, bench, cell, config, mix, cluster, make_ref, rows,
            work) -> dict:
    """Warm-up, the window, the comparison with the reference
    (`make_ref()`, called once the cluster is stopped), the result's
    line: everything of a run past the look for a chip and the loading
    of the table (tests/test_faults.py drives it with the timed path
    broken underneath)."""
    traced = bool(args.trace)
    warm_queries = traffic.make_queries(mix, config["table"], args.seed, 0,
                                        min(mix["max_queries"], 12000), True)
    window_queries = traffic.make_queries(mix, config["table"], args.seed, 1,
                                          mix["max_queries"], traced)
    t2 = time.monotonic()
    warm_records = warm_up(cluster, mix, warm_queries)
    say(f"warm-up took {time.monotonic() - t2:.1f}s")

    # -- the window ----------------------------------------------------------
    tw = mix["trace_window"]
    go, halt = (os.path.join(cluster.profile_dir, n)
                for n in ("go", "halt")) if traced else (None, None)
    state = {"go": not traced, "halt": not traced}
    stop_at = min(tw["start_s"] + tw["seconds"], args.seconds - 0.5)

    def on_tick(elapsed: float) -> None:
        if not state["go"] and elapsed >= tw["start_s"]:
            open(go, "w").close()
            state["go"] = True
        if not state["halt"] and elapsed >= stop_at:
            open(halt, "w").close()
            state["halt"] = True

    before = cluster.counters()
    setup_s = time.monotonic() - T_START
    records = loadgen.closed_loop(
        HOST, cluster.broker_port, window_queries, mix["clients"],
        args.seconds, traced, on_tick)
    after = cluster.counters()
    on_tick(float("inf"))
    compiles = series_delta(before, after, "kernel_retrace")
    upload = series_delta(before, after, "hbm_transfer_bytes")
    late = sorted(records, key=lambda r: r["sent_s"] - r["done_s"])[:5]
    say(f"window: {len(records)} queries, kernel_retrace +{compiles:g} "
        f"(compiles inside the window), hbm_transfer_bytes +{upload:g} "
        f"({upload / max(len(records), 1):.0f} a query)")
    say("longest latencies (ms at offset s): " + ", ".join(
        f"{(r['done_s'] - r['sent_s']) * 1e3:.1f}@{r['sent_s']:.2f}"
        for r in late))

    profile = None
    if traced:
        done = os.path.join(cluster.profile_dir, "done")
        cluster.wait(lambda: os.path.exists(done), "the profiler to stop",
                     timeout=150.0)
        with open(done) as f:
            profile = json.load(f)
    device = cluster.device()
    peak_bytes = max((m.get("peak_bytes_in_use") or 0)
                     for m in device["memory"])
    cluster.stop_all()  # the chip is free; the reference may take its time

    # -- correct: every answer of the window beside the reference's ----------
    if not records:
        raise HarnessFailure("the window sent no query")
    verdict = judge.judge(config, mix["templates"], make_ref(),
                          records + warm_records)
    failed = sum(1 for r in records if r["failure"])
    first_failure = next((r["failure"] for r in records if r["failure"]), None)
    if first_failure:
        say(f"first failure: {first_failure}")
    if verdict["first_wrong"]:
        say(f"first wrong answer: {json.dumps(verdict['first_wrong'])}")

    metrics, breakdown = {}, None
    dev = {"platform": device["platform"], "kind": device["device_kind"],
           "count": device["count"], "memory_peak_bytes": peak_bytes}
    if not traced:
        for m in cell_metrics(bench, "end_to_end", cell["name"]):
            value = setup_s if m["name"] == "setup_s" \
                else END_TO_END[m["name"]](records, args.seconds)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        reduced = reduce_trace(cluster.profile_dir, work, args.cpu_rehearsal)
        window_s = profile["stopping_wall"] - profile["started_wall"]
        n_in = trace_reduce.queries_in_window(
            records, profile["started_wall"], profile["stopping_wall"])
        say(f"trace: {reduced.get('events', 0)} device events, busy "
            f"{reduced.get('busy_s')} of {window_s:.3f}s, {n_in:.2f} "
            f"queries inside, xplane {reduced.get('xplane_bytes')} bytes, "
            f"lines {json.dumps(reduced['planes'])[:600]}")
        if not reduced.get("busy_s"):
            raise HarnessFailure("the traced window holds no device operation")
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = window_s
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        cards = {name: len(dom)
                 for name, dom in datagen.domains(config).items()}
        ctx = {"records": records, "config": config, "mix": mix,
               "rows": rows, "device_kind": device["device_kind"],
               "cardinalities": cards, "busy_s": reduced["busy_s"],
               "window_s": window_s, "queries_in_trace": n_in,
               "rehearsal": args.cpu_rehearsal}
        for m in cell_metrics(bench, "per_layer", cell["name"]):
            reader = importlib.import_module("metrics." + m["name"])
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    for name, c in verdict["checks"].items():
        print(f"bench: compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"bench: compared {verdict['compared']} answers; correct = "
          f"{verdict['correct']}", file=sys.stderr, flush=True)
    line = {"correct": verdict["correct"] and not args.cpu_rehearsal,
            "attempted": len(records), "failed": failed,
            "metrics": metrics, "device": dev}
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = verdict["compared"]
    line["checks"] = verdict["checks"]
    return line


def reduce_trace(profile_dir: str, work: str, rehearsal: bool) -> dict:
    """In a process of its own, JAX held to the CPU: this parent never
    imports jax."""
    out = os.path.join(work, "trace_reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "trace_reduce.py"),
         os.path.join(profile_dir, "trace"), out,
         *(["--cpu-rehearsal"] if rehearsal else [])],
        env=env, capture_output=True, text=True, timeout=200)
    if proc.returncode != 0:
        raise HarnessFailure(f"trace reduction failed: {proc.stderr[-2000:]}")
    with open(out) as f:
        return json.load(f)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--keep", action="store_true",
                   help="keep benchmark/work/<cell>/ (logs, trace)")
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="toy sizes on XLA:CPU to debug the harness; never "
                        "a result")
    args = p.parse_args(argv)

    def give_up(signum, _frame):
        raise HarnessFailure(f"signal {signum}: time limit or termination")
    signal.signal(signal.SIGALRM, give_up)
    signal.signal(signal.SIGTERM, give_up)
    signal.alarm(1100)
    try:
        line = run(args)
    except HarnessFailure as e:
        print(f"bench FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
    if "jax" in sys.modules:
        print("bench FAILED: the parent imported jax", file=sys.stderr)
        return 1
    if args.cpu_rehearsal:
        say("CPU REHEARSAL finished — not a chip result, never correct")
    print(json.dumps(line), flush=True)
    return REHEARSAL_EXIT if args.cpu_rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
