"""The two readers PR 31 added, on the hand-made span sample of
test_span_readers.py: `param_puts` reads what the spans carry since PR
27; `ring_held_share` reads `heldMs`, which the sample's inline
dispatches lack (None, never 0) until the test gives some of them one."""
import copy
import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "span_sample.json")) as f:
    SAMPLE = json.load(f)


def read(name: str, records: list):
    return importlib.import_module("metrics." + name).read(
        {"records": records})


def dispatch_of(record):
    return record["trace"]["children"][0]["children"][0]["children"][0]


def with_attrs(values: list, **common):
    """The sample's queries, each dispatch given one of `values` as
    `heldMs` (None: left as it is, an inline dispatch)."""
    records = copy.deepcopy(SAMPLE["records"])
    for record, held in zip(records, values):
        if held is not None:
            dispatch_of(record).update(heldMs=held, **common)
    return records


def test_param_puts_is_the_median_a_query():
    assert read("param_puts", SAMPLE["records"]) == 7
    records = copy.deepcopy(SAMPLE["records"])
    for record, puts in zip(records, (1, 0, 0)):  # a miss, two hits
        dispatch_of(record)["paramPuts"] = puts
    assert read("param_puts", records) == 0
    assert read("param_puts", SAMPLE["parent_records"]) is None
    assert read("param_puts", []) is None


@pytest.mark.parametrize("values,expected", [
    ((None, None, None), None),      # all inline: nothing rode the ring
    ((0.0, 0.0, 0.0), 0.0),          # on the ring, never held: a true 0
    ((0.0, 7.5, 3.25), 100 * 2 / 3),
    ((None, 4.0, None), 100.0),      # inline dispatches are not counted
])
def test_ring_held_share(values, expected):
    got = read("ring_held_share", with_attrs(list(values)))
    assert got == expected if expected is None \
        else got == pytest.approx(expected)


def test_ring_held_share_leaves_out_fallbacks_and_the_parents_spans():
    records = with_attrs([2.0, 0.0, 0.0])
    dispatch_of(records[0])["outcome"] = "hostFallback"
    assert read("ring_held_share", records) == 0.0
    assert read("ring_held_share", SAMPLE["parent_records"]) is None
    assert read("ring_held_share", []) is None
