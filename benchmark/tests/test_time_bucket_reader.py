"""The `time_bucket_share` reader, on the hand-made span sample of
test_span_readers.py: it reads the grouped
`DeviceDispatch` attributes `timeBucket` and `groupFold`, which the
sample's spans lack (None, never 0) until the test gives them."""
import copy
import json
import os

from metrics import time_bucket_share

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "span_sample.json")) as f:
    SAMPLE = json.load(f)


def read(records: list):
    return time_bucket_share.read({"records": records})


def dispatch_of(record):
    return record["trace"]["children"][0]["children"][0]["children"][0]


def grouped(*spans):
    """The sample's records, each dispatch given (groupFold, timeBucket)
    where the pair is not None."""
    records = copy.deepcopy(SAMPLE["records"])
    for record, pair in zip(records, spans):
        if pair is not None:
            fold, unit = pair
            dispatch_of(record).update(groupPath="onehot2", groupFold=fold)
            if unit is not None:
                dispatch_of(record).update(timeBucket=unit, bucketCount=12,
                                           bucketPad=16)
    return records


def test_every_query_fused_and_folded_on_the_device_reads_100():
    assert read(grouped(*[("device", "HOUR")] * 3)) == 100.0


def test_a_host_fold_or_a_bucket_off_the_kernel_counts_against_it():
    assert read(grouped(("device", "HOUR"), ("host", "HOUR"),
                        ("device", "HOUR"))) == 200.0 / 3
    # grouped, folded, but the bucket ran as a host expression
    assert read(grouped(("device", "HOUR"), ("device", None),
                        ("device", "FLOOR"))) == 200.0 / 3
    assert read(grouped(("host", "DAY"), ("host", "DAY"),
                        ("host", "DAY"))) == 0.0


def test_a_program_without_the_attribute_reads_none_never_zero():
    assert read(SAMPLE["records"]) is None           # no grouped dispatch
    assert read(SAMPLE["parent_records"]) is None
    assert read([]) is None
    # grouped and folded, but no span carries timeBucket: the parent
    assert read(grouped(*[("device", None)] * 3)) is None


def test_ungrouped_and_unanswered_queries_are_left_out():
    records = grouped(("device", "HOUR"), None, ("host", "HOUR"))
    assert read(records) == 50.0
    records[2]["rows"] = None
    assert read(records) == 100.0
