"""The readers of the program's wait attributes (PR 27), on a span sample
made by hand in round numbers (data/span_sample.json: three queries of
one closed-loop client; the third stalls 6 ms between staging and the
ring, which no phase covers) and on the same trees as a program without
the attributes ships them, which read None, never 0."""
import importlib
import json
import os

import pytest

import span_phases

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "span_sample.json")) as f:
    SAMPLE = json.load(f)


def read(name: str, records: list):
    return importlib.import_module("metrics." + name).read(
        {"records": records})


@pytest.mark.parametrize("name,expected", [
    ("engine_lock_wait_ms", 5.0),      # the known lock wait
    ("staging_params_ms", 2.0),
    ("ring_wait_ms", 0.1),
    ("device_wait_ms", 4.0),
    ("d2h_ms", 0.1),
    ("scatter_wire_ms", 2.5),          # 16.5 of scatter round 14.0 of request
    ("server_unattributed_ms", 0.2),   # 0.2, 0.2 and the stalled 6.2
    ("idle_attributed_share", 100 * 30.8 / 37.2),
])
def test_reader_on_the_sample(name, expected):
    assert read(name, SAMPLE["records"]) == pytest.approx(expected, abs=1e-4)


@pytest.mark.parametrize("name,expected", [
    ("engine_lock_wait_ms", None), ("staging_params_ms", None),
    ("device_wait_ms", None), ("d2h_ms", None),
    ("server_unattributed_ms", None), ("idle_attributed_share", None),
    # these two read what the parent's spans already carry
    ("ring_wait_ms", 0.1), ("scatter_wire_ms", 2.5),
])
def test_reader_without_the_attributes_reads_none_never_zero(name, expected):
    got = read(name, SAMPLE["parent_records"])
    assert got == expected if expected is None \
        else got == pytest.approx(expected)
    assert read(name, []) is None


def test_gaps_are_laid_against_the_phases_of_the_launch_that_ends_them():
    out = span_phases.idle_by_phase(SAMPLE["records"])
    ms = {k: v / 1e6 for k, v in out["phases"].items()}
    assert out["busy_ns"] == 3 * 4_400_000
    assert out["window_ns"] == 50_400_000 and out["idle_ns"] == 37_200_000
    # two gaps, each ended by a query that waited 5 ms for the lock and
    # staged for 3.5: plan 1, blocks 0.5, params 2
    assert ms["lock_wait"] == pytest.approx(10.0)
    assert (ms["plan"], ms["blocks"], ms["params"]) == \
        pytest.approx((2.0, 1.0, 4.0))
    assert (ms["submit"], ms["ring_wait"]) == pytest.approx((0.1, 0.2))
    assert ms["wire"] + ms["scheduler_wait"] == pytest.approx(2.0)
    # before the query was sent: the answer before it on its way back (3 ms
    # from the server's span to the client's clock), then the client
    assert ms["prev:return"] == pytest.approx(6.0, abs=1e-3)
    assert ms["client"] == pytest.approx(1.0, abs=1e-3)
    # the 6 ms stall has no name, nor have the 0.2 ms of holes a query
    assert (out["idle_ns"] - sum(out["phases"].values())) / 1e6 == \
        pytest.approx(6.4, abs=1e-3)


def test_a_coalesced_launch_is_laid_against_its_last_member():
    first, second = (json.loads(json.dumps(r)) for r in SAMPLE["records"][:2])

    def dispatch(r):
        return r["trace"]["children"][0]["children"][0]["children"][0]
    # the second query rides the first one's launch, having waited 0.05 ms
    # in the ring where the first waited 0.1
    dispatch(second).update(launchNs=dispatch(first)["launchNs"],
                            readyNs=dispatch(first)["readyNs"],
                            queueWaitMs=0.05)
    found = span_phases.launches([first, second])
    assert len(found) == 2
    busy, gaps, window = span_phases.busy_and_gaps(found)
    assert busy == window == 4_400_000 and gaps == []


def test_named_phases_of_a_server_request():
    trace = SAMPLE["records"][0]["trace"]
    assert span_phases.named_phase_ms(trace) == pytest.approx(13.8)
    assert span_phases.named_phase_ms(
        SAMPLE["parent_records"][0]["trace"]) is None
    assert span_phases.dispatch_sum(trace, "noSuchMs") is None


def test_phase_report_says_where_the_rest_lies_and_what_the_slowest_sat_in():
    lines = span_phases.phase_report(SAMPLE["records"])
    assert lines[0].startswith("server request by phase (median ms): "
                               "parseMs 0.300 lockWaitMs 5.000 ")
    assert lines[0].endswith("(before dispatch) 0.100 (inside dispatch) "
                             "0.000 (after dispatch) 0.100")
    # the stalled query first: 6 ms inside its dispatch that no phase names
    assert lines[1].startswith("slow query 25.5 ms at 0.04 s: "
                               "(inside dispatch) 6.0, ")
    assert "(outside the server request) 5.5" in lines[2]
    assert span_phases.phase_report(SAMPLE["parent_records"]) == []
