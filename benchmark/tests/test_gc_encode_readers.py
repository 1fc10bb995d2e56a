"""The three readers of the answer's way back and the collector:
`http_encode_ms` (median `BrokerEncode.durationMs`), `broker_gc_ms` and
`server_gc_ms` (the MEAN a query of `BrokerRequest.gcPauseMs` / the sum
of its `ServerRequest.gcPauseMs`). On hand-made trees; the span sample of
test_span_readers.py is a program without either attribute."""
import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "span_sample.json")) as f:
    SAMPLE = json.load(f)

T0 = 1_800_000_000_000_000_000


def read(metric: str, records: list):
    return importlib.import_module("metrics." + metric).read(
        {"records": records})


def record(i: int, encode_ms=None, broker=None, server=None) -> dict:
    """One answered traced query i (100 ms apart): broker root, a scatter
    with the server's grafted root, and a BrokerEncode span. `broker` /
    `server`: the root's gc attributes, None for a program without the
    probe."""
    start = T0 + i * 100_000_000
    server_root = {"operator": "ServerRequest", "durationMs": 20.0,
                   "startNs": start + 2_000_000, **(server or {})}
    children = [{"operator": "ServerScatter", "durationMs": 25.0,
                 "startNs": start + 1_000_000, "children": [server_root]}]
    if encode_ms is not None:
        children.append({"operator": "BrokerEncode", "durationMs": encode_ms,
                         "startNs": start + 30_000_000,
                         "responseBytes": 1000 + i})
    trace = {"operator": "BrokerRequest", "durationMs": 60.0,
             "startNs": start, **(broker or {}), "children": children}
    return {"trace": trace, "rows": [[1]], "time_used_ms": 28.0}


def test_http_encode_ms_is_the_median_encode(capsys):
    records = [record(i, encode_ms=ms) for i, ms in enumerate((41.0, 2.5, 39.0))]
    assert read("http_encode_ms", records) == 39.0
    assert "median responseBytes 1001" in capsys.readouterr().err


@pytest.mark.parametrize("metric", ["http_encode_ms", "broker_gc_ms",
                                    "server_gc_ms"])
def test_a_program_without_the_attribute_reads_none_never_zero(metric):
    assert read(metric, SAMPLE["records"]) is None
    assert read(metric, [record(i) for i in range(3)]) is None
    assert read(metric, []) is None


@pytest.mark.parametrize("metric,root", [("broker_gc_ms", "broker"),
                                         ("server_gc_ms", "server")])
def test_the_gc_readers_take_the_mean_over_queries_with_and_without_pauses(
        metric, root):
    pauses = [{"gcTotalMs": 10.0},
              {"gcTotalMs": 16.0, "gcPauseMs": 6.0, "gcCollections": 3,
               "gcByGeneration": [3, 0, 0]},
              {"gcTotalMs": 16.0},
              {"gcTotalMs": 30.0, "gcPauseMs": 12.0, "gcCollections": 1,
               "gcByGeneration": [0, 0, 1]}]
    records = [record(i, **{root: p}) for i, p in enumerate(pauses)]
    # the median of (0, 6, 0, 12) would read 3; the mean says what they cost
    assert read(metric, records) == pytest.approx(4.5)


def test_a_query_that_failed_is_not_counted():
    records = [record(0, broker={"gcTotalMs": 1.0, "gcPauseMs": 8.0}),
               record(1, broker={"gcTotalMs": 1.0})]
    records[1]["rows"] = None
    assert read("broker_gc_ms", records) == 8.0


def test_the_gc_report_names_generations_outside_time_and_long_pauses(
        capsys):
    long_at = T0 + 100_000_000 + 31_000_000  # inside the second encode
    broker = [{"gcTotalMs": 100.0},
              {"gcTotalMs": 130.0, "gcPauseMs": 25.0, "gcCollections": 2,
               "gcByGeneration": [1, 0, 1],
               "gcLongPauses": [[2, long_at, 24.0]]},
              {"gcTotalMs": 131.0}]
    records = [record(i, encode_ms=40.0, broker=p)
               for i, p in enumerate(broker)]
    assert read("broker_gc_ms", records) == pytest.approx(25.0 / 3)
    err = capsys.readouterr().err
    assert "collections inside them by generation [1, 0, 1]" in err
    # 31 ms paused between the first close and the last, 25 inside
    assert "paused 31.000 ms" in err and "6.000 outside" in err
    assert ("1 pauses of 10 ms or more: 24.0 to 24.0 ms, median 24.0; by "
            "generation {2: 1}; by span {'BrokerRequest > BrokerEncode': 1}"
            ) in err
    assert (f"pause 24.0 ms, generation 2, startNs {long_at}, in "
            "BrokerRequest > BrokerEncode") in err


def test_a_broker_pause_is_not_placed_in_the_servers_spans(capsys):
    at = T0 + 5_000_000  # inside ServerScatter and the grafted ServerRequest
    records = [record(0, broker={"gcTotalMs": 12.0, "gcPauseMs": 12.0,
                                 "gcLongPauses": [[0, at, 12.0]]})]
    read("broker_gc_ms", records)
    assert "in BrokerRequest > ServerScatter\n" in capsys.readouterr().err


def test_overlapping_requests_leave_the_outside_unsaid(capsys):
    """Under several clients one pause is charged to every request open
    across it: more inside than between says so, and no outside."""
    records = [record(i, broker={"gcTotalMs": 12.0 * i, "gcPauseMs": 12.0})
               for i in range(3)]
    read("broker_gc_ms", records)
    err = capsys.readouterr().err
    assert "paused 24.000 ms" in err and "24.000 inside" in err
    records[2]["trace"]["gcPauseMs"] = 20.0
    read("broker_gc_ms", records)
    assert "requests overlap" in capsys.readouterr().err
