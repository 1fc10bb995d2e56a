"""The reader PR 33 added, on the hand-made span sample of
test_span_readers.py (beside `param_puts`'s test in
test_held_and_puts_readers.py): `lock_held_ms` reads `lockHeldMs`,
which the sample's spans lack (None, never 0) until the test gives
them one."""
import copy
import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "span_sample.json")) as f:
    SAMPLE = json.load(f)


def read(records: list):
    return importlib.import_module("metrics.lock_held_ms").read(
        {"records": records})


def dispatch_of(record):
    return record["trace"]["children"][0]["children"][0]["children"][0]


def test_lock_held_ms_is_the_median_a_query():
    records = copy.deepcopy(SAMPLE["records"])
    for record, held in zip(records, (0.41, 0.38, 2.5)):  # one block miss
        dispatch_of(record)["lockHeldMs"] = held
    assert read(records) == 0.41


def test_a_program_without_the_attribute_reads_none_never_zero():
    assert read(SAMPLE["records"]) is None       # PR 32's spans
    assert read(SAMPLE["parent_records"]) is None
    assert read([]) is None
    records = copy.deepcopy(SAMPLE["records"])
    dispatch_of(records[0])["lockHeldMs"] = 0.4  # the others lack it
    assert read(records) == 0.4


def test_fallbacks_and_unanswered_queries_are_left_out():
    records = copy.deepcopy(SAMPLE["records"])
    for record, held in zip(records, (0.3, 0.5, 0.7)):
        dispatch_of(record)["lockHeldMs"] = held
    dispatch_of(records[2])["outcome"] = "hostFallback"
    assert read(records) == 0.4
    records[1]["rows"] = None
    assert read(records) == 0.3
