"""The two readers PR 36 added, on the hand-made span sample of
test_span_readers.py: `result_serialize_ms` reads
`ServerRequest.serializeMs` (the sample's spans carry it since PR 27,
its parent's do not) and `result_deserialize_ms` reads
`ServerScatter.deserializeMs`, which no span of the sample has (None,
never 0) until the test gives it one."""
import copy
import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "span_sample.json")) as f:
    SAMPLE = json.load(f)

#: metric -> (the span's operator, its attribute)
READERS = {"result_serialize_ms": ("ServerRequest", "serializeMs"),
           "result_deserialize_ms": ("ServerScatter", "deserializeMs")}


def read(metric: str, records: list):
    return importlib.import_module("metrics." + metric).read(
        {"records": records})


def span_of(record, operator: str):
    node = record["trace"]
    while node["operator"] != operator:
        node = node["children"][0]
    return node


def stripped(records: list, operator: str, attribute: str) -> list:
    records = copy.deepcopy(records)
    for record in records:
        span_of(record, operator).pop(attribute, None)
    return records


@pytest.mark.parametrize("metric", sorted(READERS))
def test_the_median_a_query(metric):
    operator, attribute = READERS[metric]
    records = copy.deepcopy(SAMPLE["records"])
    for record, value in zip(records, (271.2, 3.1, 2.9)):
        span_of(record, operator)[attribute] = value
    assert read(metric, records) == 3.1


def test_the_sample_states_its_serialize_phase():
    assert read("result_serialize_ms", SAMPLE["records"]) == 0.1


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_program_without_the_attribute_reads_none_never_zero(metric):
    operator, attribute = READERS[metric]
    assert read(metric, SAMPLE["parent_records"]) is None
    assert read(metric, []) is None
    records = stripped(SAMPLE["records"], operator, attribute)
    assert read(metric, records) is None
    span_of(records[0], operator)[attribute] = 41.5  # the others lack it
    assert read(metric, records) == 41.5


@pytest.mark.parametrize("metric", sorted(READERS))
def test_unanswered_queries_are_left_out_and_attempts_add_up(metric):
    operator, attribute = READERS[metric]
    records = stripped(SAMPLE["records"], operator, attribute)
    for record, value in zip(records, (3.0, 5.0, 7.0)):
        span_of(record, operator)[attribute] = value
    assert read(metric, records) == 5.0
    records[2]["rows"] = None
    assert read(metric, records) == 4.0
    # a retried scatter is a second span of the same query: both count
    first = records[0]["trace"]
    first["children"].append(copy.deepcopy(first["children"][0]))
    assert read(metric, records[:1]) == 6.0
