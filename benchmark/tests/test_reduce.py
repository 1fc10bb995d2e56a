"""The yardstick's arithmetic: trace -> busy/idle/gaps/ops, on a trace
recorded on the chip (data/trace_sample.json: the first device events of
a traced ssb1_scan_c8 run) and on one made by hand; bytes a query on both
configurations' files; the table of peaks."""
import json
import os

import pytest

import datagen
import peaks
import small
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_busy_union_gaps_and_buckets_by_hand():
    events = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("a", 31, 2),
              ("d", 2_000_000, 1000)]
    assert tr.busy_union_ns(events) == 15 + 5 + 1000
    assert tr.idle_gaps_ns(events) == [15, 2_000_000 - 35]
    buckets = tr.gap_buckets(tr.idle_gaps_ns(events))
    assert buckets[0][0] == "host:not_attributed_1_gaps_of_1-10_ms"
    assert buckets[0][1] == pytest.approx((2_000_000 - 35) / 1e9)
    assert tr.top_ops(events, 2) == [["d", 1e-6], ["a", 12e-9]]
    assert tr.idle_share(0.25, 1.0) == 0.75


def test_ops_are_named_by_program_and_operation():
    ops = [("%fusion.7 = (s32[2,32]{1,0}) fusion(s32[32,8388608] %x)", 12, 5),
           ("%copy.1 = s8[32] copy(s8[32] %y)", 40, 1)]
    modules = [("jit_kernel(17)", 10, 20)]
    assert tr.name_ops(ops, modules) == [("jit_kernel(17)/fusion.7", 12, 5),
                                         ("copy.1", 40, 1)]


def test_a_profile_without_a_tpu_plane_gives_no_device_events():
    """Host threads never stand in for the device on a chip run, so
    run.py fails it ("the traced window holds no device operation")."""
    ops = [("%fusion.7 = f32[8]{0} fusion(f32[8] %x)", 12, 5)]
    host = {"/host:CPU": {"tf_XLACpuClient/123": [("dot", 0, 9)],
                          "python": [("wait", 0, 99)]}}
    assert tr.device_lines(host, rehearsal=False) == {}
    # a TPU plane whose `XLA Ops` line is missing or empty is none either
    lost = dict(host, **{"/device:TPU:0": {"XLA Modules": [("jit_k", 0, 9)],
                                           "XLA Ops": []}})
    assert tr.device_lines(lost, rehearsal=False) == {}
    assert tr.device_lines(host, rehearsal=True) == {
        "/host:CPU": [("dot", 0, 9)]}
    chip = dict(host, **{"/device:TPU:0": {"XLA Ops": ops, "Steps": []}})
    for rehearsal in (False, True):
        assert tr.device_lines(chip, rehearsal) == {
            "/device:TPU:0": [("fusion.7", 12, 5)]}


def test_queries_in_window_counts_cut_queries_in_part():
    rec = [{"done_wall": 10.0, "done_s": 4.0, "sent_s": 2.0},   # inside
           {"done_wall": 12.0, "done_s": 6.0, "sent_s": 2.0},   # half
           {"done_wall": 30.0, "done_s": 9.0, "sent_s": 8.0}]   # outside
    assert tr.queries_in_window(rec, 7.0, 10.0) == pytest.approx(1.5)


def test_recorded_trace_reduces():
    with open(os.path.join(HERE, "data", "trace_sample.json")) as f:
        events = [tuple(e) for e in json.load(f)]
    assert len(events) >= 100
    busy = tr.busy_union_ns(events)
    span = max(s + d for _n, s, d in events) - min(s for _n, s, _d in events)
    assert 0 < busy <= span
    assert busy + sum(tr.idle_gaps_ns(events)) == span
    out = tr.reduce_events({"/device:TPU:0": events})
    assert out["busy_s"] == busy / 1e9 and len(out["device_ops"]) <= 10
    assert all(name and seconds > 0 for name, seconds in out["device_ops"])


@pytest.mark.parametrize("cell_name,expected", [
    # Q1.x: i16 + i8 + i8 + i32 = 8 B a row over 256M rows
    ("ssb1_scan_c8", [2_048_000_000] * 3),
    ("ssb1_scan_c1", [2_048_000_000] * 3),
    # Q2.1 reads all five columns (1+2+1+1+4), Q2.2/Q2.3 not p_category
    ("ssb2_q2_c1", [9 * 128_000_000, 8 * 128_000_000, 8 * 128_000_000]),
])
def test_bytes_per_query_from_the_configuration_files(cell_name, expected):
    _bench, _cell, config, mix = small.load_cell(cell_name)
    cards = {n: len(d) for n, d in datagen.domains(config).items()}
    rows = config["segments"] * config["docs_per_segment"]
    assert [peaks.bytes_per_query(config, cards, t, rows)
            for t in mix["templates"]] == expected


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.least_seconds("TPU v5 lite", 819e9) == (1.0, "hbm")
    with pytest.raises(KeyError):
        peaks.peak("cpu")
    with pytest.raises(KeyError):
        peaks.least_seconds("TPU v9", 1.0)
