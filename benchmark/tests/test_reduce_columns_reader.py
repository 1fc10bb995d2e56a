"""The reader ISSUE 38 added, on the hand-made span sample of
test_span_readers.py: `reduce_columns_share` reads the
`BrokerReduce.reducePath` the broker's GROUP BY reduce carries, which the
sample's spans lack (None, never 0) until the test gives them one."""
import copy
import json
import os

from metrics import reduce_columns_share

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "span_sample.json")) as f:
    SAMPLE = json.load(f)


def read(records: list):
    return reduce_columns_share.read({"records": records})


def reduce_of(record):
    span, = [c for c in record["trace"]["children"]
             if c["operator"] == "BrokerReduce"]
    return span


def with_paths(*paths):
    records = copy.deepcopy(SAMPLE["records"])
    for record, path in zip(records, paths):
        if path is not None:
            reduce_of(record).update(reducePath=path, reduceRows=48000)
    return records


def test_every_query_reduced_as_columns_reads_100():
    assert read(with_paths("columns", "columns", "columns")) == 100.0


def test_a_mix_reads_the_share_of_the_queries_that_carry_the_attribute():
    assert read(with_paths("columns", "rows", "rows")) == 100.0 / 3
    # a query without the attribute (an ungrouped template) is left out
    assert read(with_paths("columns", None, "rows")) == 50.0
    assert read(with_paths("rows", "rows", "rows")) == 0.0


def test_a_program_without_the_attribute_reads_none_never_zero():
    assert read(SAMPLE["records"]) is None
    assert read(SAMPLE["parent_records"]) is None
    assert read([]) is None


def test_unanswered_queries_are_left_out():
    records = with_paths("columns", "rows", "columns")
    records[1]["rows"] = None
    assert read(records) == 100.0
