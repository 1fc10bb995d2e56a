#!/usr/bin/env python3
"""The control of `correct`: the reference put in the program's place,
computed one step below what the configuration states (reference.CONTROLS:
each segment's sums rounded to f32 where the configuration says exact;
inputs rounded to bf16 where it says f32) or with a guarantee broken (a
segment left out), judged by the same judge.judge as a run. It has to
come out NOT correct. Pure numpy: it needs no chip, and a benchmark run
never runs it.

  python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--queries 600]
      [--segments N --docs N]      (default: the cell's own size)

Prints one JSON line a seed and control with every number compared."""
from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH]

import datagen  # noqa: E402
import judge  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402


def shares_job(job):
    config, seed, i, docs, lowers = job
    made = datagen.make_columns(config, seed, i, docs)
    return i, {lower: reference.segment_share(config, made, lower)
               for lower in lowers}


def readings(config, mix, seed, segments, docs, n_queries, workers):
    """{control: judge's verdict} with the exact reference as yardstick."""
    lowers = [None, "sums_f32", "inputs_bf16"]
    doms = datagen.domains(config)
    refs = {lower: reference.Reference(config, doms) for lower in lowers}
    refs["segment_dropped"] = reference.Reference(config, doms)
    jobs = [(config, seed, i, docs, lowers) for i in range(segments)]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(workers, segments)) as pool:
        for i, shares in pool.imap_unordered(shares_job, jobs):
            for lower, share in shares.items():
                refs[lower].add(share)
            if i != segments - 1:
                refs["segment_dropped"].add(shares[None])
    queries = traffic.make_queries(mix, config["table"], seed, 1, n_queries,
                                   False)
    out = {}
    for name, stand_in in refs.items():
        records = [{"template": t, "literals": lit, "traced": False,
                    "served": False,
                    "rows": stand_in.answer(mix["templates"][t], lit)}
                   for t, lit, _sql in queries]
        verdict = judge.judge(config, mix["templates"], refs[None], records)
        out[name or "reference_itself"] = {
            "correct": verdict["correct"], "compared": verdict["compared"],
            **{k: c["value"] for k, c in verdict["checks"].items()}}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--queries", type=int, default=600)
    p.add_argument("--segments", type=int, default=None)
    p.add_argument("--docs", type=int, default=None)
    p.add_argument("--workers", type=int, default=os.cpu_count() or 2)
    args = p.parse_args(argv)
    _bench, cell, config, mix = traffic.load_cell(ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        got = readings(config, mix, seed,
                       args.segments or config["segments"],
                       args.docs or config["docs_per_segment"],
                       args.queries, args.workers)
        print(json.dumps({"workload": cell["name"], "seed": seed, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
