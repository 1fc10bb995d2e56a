"""The table of a configuration, made from the seed: columns by the
generators its file names, segments built by the program's
SegmentCreator in pool workers and pushed through the controller's deep
store. The reference's histogram is made from the same seed in workers
of its own, after the window (`share_job`).

No jax here or in anything this imports before the program's own
modules: the workers run beside the one process that holds the chip."""
from __future__ import annotations

import importlib
import os
import shutil
import sys
import time

import numpy as np

import reference


def make_columns(config: dict, seed: int, segment: int, docs: int) -> dict:
    """{column: (values, codes, domain)} of one segment."""
    rng = np.random.default_rng([seed, segment])
    made = {}
    for col in config["columns"]:
        gen = importlib.import_module(
            "generators." + col["generator"]["kind"])
        made[col["name"]] = gen.generate(rng, docs, col["generator"],
                                         config["pools"], made)
    return made


def domains(config: dict) -> dict:
    """{column: domain} without making a table's worth of rows."""
    return {name: dom for name, (_v, _c, dom)
            in make_columns(config, 0, 0, 1).items()}


def table_and_schema(config: dict):
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    fields = [FieldSpec(c["name"], DataType[c["type"]], FieldType[c["role"]])
              for c in config["columns"]]
    tc = TableConfig(config["table"], TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = [
        c["name"] for c in config["columns"] if not c["dictionary"]]
    tc.indexing.compression = "PASS_THROUGH"
    return tc, Schema(config["table"], fields)


def segment_job(job):
    """Pool worker: make segment i, build it with the program's
    SegmentCreator and push it through the controller's deep store."""
    config, seed, i, docs, build_dir, coordinator, table_ready = job
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.tools import admin
    made = make_columns(config, seed, i, docs)
    tc, schema = table_and_schema(config)
    name = f"{config['table']}_{i}"
    seg_dir = os.path.join(build_dir, name)
    SegmentCreator(tc, schema).build(
        {k: v[0] for k, v in made.items()}, seg_dir, name)
    while not os.path.exists(table_ready):  # the cluster starts meanwhile
        time.sleep(0.05)
    with open(os.devnull, "w") as quiet:
        stdout, sys.stdout = sys.stdout, quiet
        try:
            rc = admin.main(["UploadSegment", "--coordinator", coordinator,
                             "--table", config["table"],
                             "--segment-dir", seg_dir])
        finally:
            sys.stdout = stdout
    shutil.rmtree(seg_dir)  # the deep store holds it now
    if rc != 0:
        raise RuntimeError(f"UploadSegment {name} returned {rc}")
    return i, "jax" in sys.modules


def share_job(job):
    """Pool worker: segment i's share of the reference's histogram, from
    the seed alone."""
    config, seed, i, docs = job
    return reference.segment_share(config, make_columns(config, seed, i, docs))
