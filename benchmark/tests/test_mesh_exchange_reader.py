"""The reader PR 37 added, on the hand-made span sample of
test_span_readers.py: `mesh_exchange_bytes` reads the
`DeviceDispatch.meshExchangeBytes` a mesh engine's grouped launch
carries, which the sample's spans lack (None, never 0) until the test
gives them one."""
import copy
import json
import os

from metrics import mesh_exchange_bytes

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "span_sample.json")) as f:
    SAMPLE = json.load(f)


def read(records: list):
    return mesh_exchange_bytes.read({"records": records})


def dispatch_of(record):
    return record["trace"]["children"][0]["children"][0]["children"][0]


def test_the_median_a_query():
    records = copy.deepcopy(SAMPLE["records"])
    for record, value in zip(records, (98432, 196864, 98432)):
        dispatch_of(record)["meshExchangeBytes"] = value
    assert read(records) == 98432


def test_a_program_without_the_attribute_reads_none_never_zero():
    assert read(SAMPLE["records"]) is None
    assert read(SAMPLE["parent_records"]) is None
    assert read([]) is None
    records = copy.deepcopy(SAMPLE["records"])
    # the engine could not read its program: the attribute is there, None
    for record in records:
        dispatch_of(record)["meshExchangeBytes"] = None
    assert read(records) is None
    dispatch_of(records[0])["meshExchangeBytes"] = 98432
    assert read(records) == 98432


def test_fallbacks_and_unanswered_queries_are_left_out():
    records = copy.deepcopy(SAMPLE["records"])
    for record, value in zip(records, (3, 5, 7)):
        dispatch_of(record)["meshExchangeBytes"] = value
    dispatch_of(records[2])["outcome"] = "hostFallback"
    assert read(records) == 4
    records[1]["rows"] = None
    assert read(records) == 3
