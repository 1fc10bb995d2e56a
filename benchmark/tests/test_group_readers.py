"""The three readers PR 35 added, on the hand-made span sample of
test_span_readers.py: each reads one `DeviceDispatch` attribute a
grouped launch carries (`groupKeySpace`, `groupResultBytes`,
`groupDecodeMs`), which the sample's spans lack (None, never 0) until
the test gives them one."""
import copy
import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "span_sample.json")) as f:
    SAMPLE = json.load(f)

READERS = {"group_key_space": "groupKeySpace",
           "group_result_bytes": "groupResultBytes",
           "group_decode_ms": "groupDecodeMs"}


def read(metric: str, records: list):
    return importlib.import_module("metrics." + metric).read(
        {"records": records})


def dispatch_of(record):
    return record["trace"]["children"][0]["children"][0]["children"][0]


@pytest.mark.parametrize("metric,attribute", sorted(READERS.items()))
def test_the_median_a_query(metric, attribute):
    records = copy.deepcopy(SAMPLE["records"])
    for record, value in zip(records, (288000, 2304000, 7000)):
        dispatch_of(record)[attribute] = value
    assert read(metric, records) == 288000


@pytest.mark.parametrize("metric,attribute", sorted(READERS.items()))
def test_a_program_without_the_attribute_reads_none_never_zero(
        metric, attribute):
    assert read(metric, SAMPLE["records"]) is None       # PR 33's spans
    assert read(metric, SAMPLE["parent_records"]) is None
    assert read(metric, []) is None
    records = copy.deepcopy(SAMPLE["records"])
    dispatch_of(records[0])[attribute] = 41.5   # the others lack it
    assert read(metric, records) == 41.5


@pytest.mark.parametrize("metric,attribute", sorted(READERS.items()))
def test_fallbacks_and_unanswered_queries_are_left_out(metric, attribute):
    records = copy.deepcopy(SAMPLE["records"])
    for record, value in zip(records, (3.0, 5.0, 7.0)):
        dispatch_of(record)[attribute] = value
    dispatch_of(records[2])["outcome"] = "hostFallback"
    assert read(metric, records) == 4.0
    records[1]["rows"] = None
    assert read(metric, records) == 3.0
