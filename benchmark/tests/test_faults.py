"""The rest of a run, past the look for a chip, with the timed path
broken underneath: `correct` has to come out false. A stub stands where
the broker's HTTP port is and answers from the reference — whole (the
one case that has to come out correct), with one answer altered where it
is produced, with half of the segments left out, and in the lower
precision of the control. (A step that returns its state unchanged and
an exchange between chips left out are faults these cells cannot have:
they keep no state from query to query and run on one chip.)"""
import json
import threading
import types
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import run as bench_run
import small
import traffic

SEED = 2_600_000_011  # past 2**31, as the driver's are


class StubCluster:
    """What measure() asks of a cluster, with the reference behind it."""

    def __init__(self, config, mix, stand_in, alter_nth=None):
        by_sql = {}
        # the two streams as measure() draws them
        for stream, n, traced in ((0, min(mix["max_queries"], 12000), True),
                                  (1, mix["max_queries"], False)):
            for t, literals, sql in traffic.make_queries(
                    mix, config["table"], SEED, stream, n, traced):
                by_sql[sql] = (t, literals)
        stub = self
        self.window_answers = 0
        lock = threading.Lock()

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                sql = json.loads(self.rfile.read(n))["sql"]
                t, literals = by_sql[sql]
                rows = stand_in.answer(mix["templates"][t], literals)
                traced = "trace=true" in sql
                if not traced and rows:
                    with lock:
                        stub.window_answers += 1
                        if stub.window_answers == alter_nth:
                            # the answer, altered: a thousandth off (a
                            # grouped f32 SUM is held to 4e-6, not to a unit)
                            rows[0][0] += max(1, abs(rows[0][0]) // 1000)
                resp = {"resultTable": {"rows": rows}, "exceptions": [],
                        "numServersQueried": 1, "numServersResponded": 1,
                        "timeUsedMs": 1.0}
                if traced:
                    resp["traceInfo"] = {
                        "operator": "BrokerRequest", "children": [
                            {"operator": "DeviceDispatch", "batchSize": 1,
                             "kernelMs": 1.0, "stagingMs": 0.1}]}
                body = json.dumps(resp).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.http = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.broker_port = self.http.server_address[1]
        self.profile_dir = None
        threading.Thread(target=self.http.serve_forever, daemon=True).start()

    def counters(self):
        return {}

    def device(self):
        return {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1,
                "memory": [{"peak_bytes_in_use": 1}]}

    def stop_all(self):
        self.http.shutdown()
        self.http.server_close()


FAULTS = {
    # name: (segments the stand-in holds, its precision, altered answer)
    "none": (range(small.SEGMENTS), None, None),
    "answer_altered": (range(small.SEGMENTS), None, 5),
    "half_left_out": (range(small.SEGMENTS // 2), None, None),
}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell_name", small.cell_names())
def test_correct_is_false_with_the_timed_path_broken(cell_name, fault):
    bench, cell, config, mix = small.load_cell(cell_name)
    mix["warmup"] = dict(mix["warmup"], loop_seconds=0)
    segments, lower, alter_nth = FAULTS[fault]
    ref = small.small_reference(config, SEED)
    stand_in = small.small_reference(config, SEED, segments, lower)
    cluster = StubCluster(config, mix, stand_in, alter_nth)
    args = types.SimpleNamespace(trace=0, seed=SEED, seconds=1.0,
                                 cpu_rehearsal=False)
    try:
        line = bench_run.measure(args, bench, cell, config, mix, cluster,
                                 lambda: ref, small.SEGMENTS * small.DOCS,
                                 work=None)
    finally:
        cluster.stop_all()
    assert line["attempted"] > 5 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {
        m["name"] for m in bench_run.cell_metrics(bench, "end_to_end",
                                                  cell_name)}
    assert line["correct"] is (fault == "none"), line["checks"]
    over = [k for k, c in line["checks"].items() if c["value"] > c["limit"]]
    assert bool(over) is (fault != "none"), line["checks"]
