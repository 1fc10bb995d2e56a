"""The one general traffic generator: a traffic mix is a data file
(`traffic/<name>.json`: clients of the closed loop, warm-up, the name of
a templates file) and a templates file (`templates/<name>.json`: SQL
templates, the pools their literals are drawn from, and the same query
written as structure for the reference). Queries are made from the
seed, templates round-robin, so every seed does the same work with
other literals."""
from __future__ import annotations

import json
import os

import numpy as np


def load(bench_dir: str, name: str) -> dict:
    with open(os.path.join(bench_dir, "traffic", name + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(bench_dir, "templates",
                           mix["templates"] + ".json")) as f:
        flight = json.load(f)
    mix["pools"] = flight["pools"]
    mix["templates"] = flight["templates"]
    return mix


def load_cell(root: str, name: str) -> tuple:
    """(BENCHMARK.json, the cell's entry, its configuration's file, its
    traffic mix), all found by the names in BENCHMARK.json."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, bench["paths"][0])
    return bench, cell, config, load(bench_dir, cell["traffic"])


def make_queries(mix: dict, table: str, seed: int, stream: int, n: int,
                 trace: bool) -> list:
    """n queries as (template index, literals, sql); `stream` keeps the
    warm-up's draws apart from the window's."""
    rng = np.random.default_rng([seed, 7, stream])
    templates = mix["templates"]
    per = -(-n // len(templates))
    draws = [{var: rng.integers(0, len(mix["pools"][pool]), per)
              for var, pool in t["draw"].items()} for t in templates]
    options = mix["options"] + (", trace=true" if trace else "")
    out = []
    for i in range(n):
        t = i % len(templates)
        template = templates[t]
        literals = {var: mix["pools"][pool][draws[t][var][i // len(templates)]]
                    for var, pool in template["draw"].items()}
        sql = template["sql"].format(table=table, **literals)
        out.append((t, literals, f"{sql} OPTION({options})"))
    return out
