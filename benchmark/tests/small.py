"""A cell at a size a test run can hold: its configuration, its mix and
the reference histogram of a small table made from a seed."""
import json
import os

import datagen
import reference
import traffic
from conftest import ROOT

SEGMENTS, DOCS = 4, 30000


def load_cell(name: str):
    return traffic.load_cell(ROOT, name)


def cell_names() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def small_reference(config: dict, seed: int, segments=range(SEGMENTS),
                    lower=None) -> reference.Reference:
    ref = reference.Reference(config, datagen.domains(config))
    for i in segments:
        made = datagen.make_columns(config, seed, i, DOCS)
        ref.add(reference.segment_share(config, made, lower))
    return ref
