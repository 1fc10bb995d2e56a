"""The end-to-end metrics' arithmetic: a rate is taken over the whole
window, a stall at its end included; latencies are those of answered
queries, and a quick failure is none."""
import pytest

import run as bench_run
from cluster import HarnessFailure


def rec(sent_s, done_s, failure=None):
    return {"sent_s": sent_s, "done_s": done_s, "failure": failure,
            "rows": None if failure else [[1]]}


def test_rate_is_over_the_whole_window_and_a_stall_at_its_end_counts():
    steady = [rec(i * 0.1, i * 0.1 + 0.1) for i in range(100)]  # 10 s
    assert bench_run.queries_per_s(steady, 10.0) == pytest.approx(10.0)
    # every client held from 8 s past the close: the last 2 s bring nothing
    stalled = steady[:80] + [rec(8.0, 11.0)]
    assert bench_run.queries_per_s(stalled, 10.0) == pytest.approx(8.0)
    # answered after the close, or failed: not completed in the window
    late = steady[:50] + [rec(9.5, 10.5), rec(6.0, 6.001, "HTTP 500")]
    assert bench_run.queries_per_s(late, 10.0) == pytest.approx(5.0)


def test_latencies_leave_out_quick_failures():
    records = [rec(0.0, 0.050), rec(0.1, 0.160), rec(0.2, 0.270),
               rec(0.3, 0.3001, "ConnectionResetError"), rec(9.9, 10.4)]
    assert sorted(bench_run.latencies_ms(records)) == pytest.approx(
        [50.0, 60.0, 70.0, 500.0])
    assert bench_run.END_TO_END["latency_p50_ms"](records, 10.0) == \
        pytest.approx(65.0)
    with pytest.raises(HarnessFailure):
        bench_run.latencies_ms([rec(0.0, 0.001, "HTTP 500")])
