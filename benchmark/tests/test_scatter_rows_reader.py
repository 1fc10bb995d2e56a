"""`scatter_rows` on the hand-made span sample of test_span_readers.py:
the median a query of `DeviceDispatch.scatterRows`, which the sample's
spans lack (None, never 0) until the test gives them one; a 0 that a
program writes where every slot took a one-hot path is read as 0."""
import copy
import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "span_sample.json")) as f:
    SAMPLE = json.load(f)


def read(records: list):
    return importlib.import_module("metrics.scatter_rows").read(
        {"records": records})


def dispatch_of(record):
    return record["trace"]["children"][0]["children"][0]["children"][0]


def with_rows(values):
    records = copy.deepcopy(SAMPLE["records"])
    for record, value in zip(records, values):
        dispatch_of(record)["scatterRows"] = value
    return records


def test_the_median_a_query():
    # 16 segments x 2^23 padded docs x SUM + COUNT
    assert read(with_rows((268435456, 268435456, 134217728))) == 268435456


def test_a_program_without_the_attribute_reads_none():
    assert read(SAMPLE["records"]) is None
    assert read(SAMPLE["parent_records"]) is None
    assert read([]) is None


def test_one_hot_launches_read_zero_and_fallbacks_are_left_out():
    assert read(with_rows((0, 0, 0))) == 0
    records = with_rows((10.0, 30.0, 50.0))
    dispatch_of(records[2])["outcome"] = "hostFallback"
    assert read(records) == 20.0
    records[1]["rows"] = None
    assert read(records) == 10.0
