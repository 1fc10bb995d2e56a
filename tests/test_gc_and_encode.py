"""The answer's way back inside the broker, and the collector inside the
spans: the HTTP edge encodes the result table under a `BrokerEncode`
span of the same answer, byte for byte the body it always sent; the
process's one `gc.callbacks` probe charges each span the pauses inside
it, lists long ones on the roots, feeds `/metrics` `gc_pause_ms` and, in
the server, a running profile's `pinot:gc` annotations."""
import gc
import glob
import json
import time
import urllib.request

import numpy as np
import pytest

from pinot_tpu.cluster.mini import MiniCluster
from pinot_tpu.ops import dispatch
from pinot_tpu.query.reduce import BrokerResponse, ResultTable
from pinot_tpu.query.results import CodedColumn
from pinot_tpu.utils import tracing
from pinot_tpu.utils.metrics import get_registry
from tests.queries.harness import (
    build_segments, synthetic_columns, synthetic_schema,
    synthetic_table_config)


def _spans(tree, name):
    found = [tree] if tree.get("operator") == name else []
    for child in tree.get("children", ()):
        found += _spans(child, name)
    return found


# -- the collector, charged to the spans it stops ------------------------------
def _scope(body):
    with tracing.RequestTrace():
        with tracing.Scope("Inner") as scope:
            body()
    return scope.node.to_dict()


def _handle(body):
    with tracing.RequestTrace() as rt:
        span = rt.handle().child("Inner")
        body()
        span.end()
    return span.node.to_dict()


def _root(body):
    with tracing.RequestTrace() as rt:
        body()
    return rt.to_dict()


SPANS = {"Scope": _scope, "SpanHandle": _handle, "RequestTrace": _root}


@pytest.fixture
def probe():
    return tracing.install_gc_probe("broker")


@pytest.mark.parametrize("kind", sorted(SPANS))
def test_a_collection_inside_a_span_is_charged_to_it(probe, kind):
    span = SPANS[kind](gc.collect)
    assert span["gcPauseMs"] > 0 and span["gcCollections"] >= 1
    assert span["gcPauseMs"] <= span["durationMs"] + 0.002


@pytest.mark.parametrize("kind", sorted(SPANS))
def test_a_span_closed_before_the_collection_is_not_charged(probe, kind):
    gc.disable()  # no collection of its own inside the span
    try:
        span = SPANS[kind](lambda: None)
    finally:
        gc.enable()
    gc.collect()
    assert "gcPauseMs" not in span and "gcCollections" not in span


def test_a_root_carries_the_running_total_and_its_long_pauses(probe):
    before = probe.total_ms
    tree = _root(gc.collect)
    assert before < tree["gcTotalMs"] <= probe.total_ms + 0.001
    assert sum(tree["gcByGeneration"]) == tree["gcCollections"]
    assert tree["gcByGeneration"][2] >= 1


def test_pauses_are_clipped_to_the_span_and_long_ones_stamped():
    probe = tracing.GcProbe("test")
    probe.ring = [(5.0, 6.0, 0), (10.0, 25.0, 2), (30.0, 31.0, 1),
                  (50.0, 60.0, 0)]
    assert probe.attrs(8.0, 40.0, start_ns=1_000_000_000) == {
        "gcPauseMs": 16.0, "gcCollections": 2, "gcByGeneration": [0, 1, 1],
        "gcLongPauses": [[2, 1_002_000_000, 15.0]]}
    assert probe.attrs(20.0, 30.5) == {"gcPauseMs": 5.5, "gcCollections": 2}
    assert probe.attrs(61.0, 70.0) == {} == probe.attrs(0.0, 4.0)


def test_installing_the_probe_twice_registers_one_callback():
    first = tracing.install_gc_probe("broker")
    assert tracing.install_gc_probe("server") is first
    assert sum(isinstance(cb, tracing.GcProbe) for cb in gc.callbacks) == 1


def test_gc_pause_ms_shows_on_metrics(probe):
    gc.collect()
    text = get_registry(probe.role).prometheus_text()
    base = f"pinot_tpu_{probe.role}_gc_pause_ms"
    assert f"# HELP {base} " in text
    assert f'{base}_count{{generation="2"}}' in text


def test_the_annotation_opens_and_closes_round_each_collection():
    events = []

    class Annotation:
        def __init__(self, generation):
            self.generation = generation

        def __enter__(self):
            events.append(("enter", self.generation))

        def __exit__(self, *exc):
            events.append(("exit", self.generation))

    probe = tracing.GcProbe("test", Annotation)
    gc.callbacks.append(probe)
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(probe)
    assert events[-2:] == [("enter", 2), ("exit", 2)]
    assert len(probe.ring) == len(events) // 2 and probe.ring[-1][2] == 2


def test_a_running_profile_holds_pinot_gc_on_the_host(tmp_path):
    import jax
    from jax.profiler import ProfileData
    probe = tracing.GcProbe("test", dispatch.gc_annotation)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    gc.callbacks.append(probe)
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(probe)
        jax.profiler.stop_trace()
    found, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    stats = [{k: v for k, v in ev.stats}
             for plane in ProfileData.from_file(found).planes
             for line in plane.lines for ev in line.events
             if ev.name == "pinot:gc"]
    assert stats and any(int(s["generation"]) == 2 for s in stats)


# -- the answer's encode on the HTTP path --------------------------------------
@pytest.fixture(scope="module")
def http_cluster(tmp_path_factory):
    segs = build_segments(tmp_path_factory.mktemp("encode"),
                          synthetic_schema(), synthetic_table_config(),
                          [synthetic_columns(400, seed=3)])
    c = MiniCluster(num_servers=1)
    c.start(with_http=True)
    c.add_table("testTable")
    c.add_segment("testTable", segs[0], server_idx=0)
    yield c
    c.stop()


def _post(cluster, sql: str) -> bytes:
    req = urllib.request.Request(
        f"http://127.0.0.1:{cluster.http.port}/query/sql",
        data=json.dumps({"sql": sql}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.read()


NAMES = ["ts_hour", "hostname", "sum(usage_user)", "count(*)"]
TYPES = ["LONG", "STRING", "DOUBLE", "LONG"]


def _grouped(rows):
    return _answer(ResultTable(NAMES, TYPES, rows))


def _answer(table):
    return BrokerResponse(result_table=table, time_used_ms=12.5,
                          num_servers_queried=1, num_servers_responded=1)


def _held(n: int):
    """`tsbs_dgb1_c1`'s answer as the columns path holds it: n of
    48,000 rows (hour, a host of 4,000, a sum, a count), in a shuffled
    order."""
    i = np.arange(48_000)
    hosts = CodedColumn([f"host_{h}" for h in range(4000)],
                        (i % 4000).astype(np.int16))
    kept = np.random.default_rng(5).permutation(48_000)[:n]
    return _answer(ResultTable.held(
        NAMES, TYPES, [458_000 + i // 4000, hosts, (i % 101) * 360.5,
                       np.full(48_000, 360)], kept))


ANSWERS = {
    "no_table": BrokerResponse,
    "zero_rows": lambda: _grouped([]),
    "one_row": lambda: _grouped([(458_000, "host_7", 1234.5, 360)]),
    "48000_rows": lambda: _grouped(
        [(458_000 + i // 4000, f"host_{i % 4000}", float(i % 101) * 360.5,
          360) for i in range(48_000)]),
    "held_zero_rows": lambda: _held(0),
    "held_48000_rows": lambda: _held(48_000),
}
GROUP_SQL = ("SELECT groupCol, SUM(intCol) FROM testTable GROUP BY groupCol "
             "ORDER BY groupCol LIMIT 100")


@pytest.mark.parametrize("traced", [True, False])
@pytest.mark.parametrize("answer", sorted(ANSWERS))
def test_the_http_body_is_the_dict_dumped_byte_for_byte(
        http_cluster, answer, traced, monkeypatch):
    """traced: pinot.trace.enabled, the shadow tree (traceInfo is left
    out: the client did not ask for it)."""
    resp = ANSWERS[answer]()
    monkeypatch.setattr(http_cluster.broker, "_handle_inner",
                        lambda sql: resp)
    monkeypatch.setattr(http_cluster.broker, "_trace_enabled", traced)
    body = _post(http_cluster, GROUP_SQL)
    assert resp.trace is None
    assert body == json.dumps(resp.to_dict(), default=str).encode()
    assert resp.encode_path == ("columns" if answer.startswith("held")
                                else "rows")


ENCODED = {"columns": GROUP_SQL,
           "rows": "SELECT COUNT(*), SUM(intCol) FROM testTable"}


@pytest.mark.parametrize("path", sorted(ENCODED))
def test_a_traced_http_answer_names_its_encode(http_cluster, monkeypatch,
                                               path):
    """The span times whichever encoder the table's form takes: a GROUP
    BY's table held as columns writes `rows_json`, an aggregation's
    rows-built table dumps `to_dict`."""
    sent = []
    encode = BrokerResponse.encode

    def spy(self, table):
        sent.append((self, table))
        return encode(self, table)
    monkeypatch.setattr(BrokerResponse, "encode", spy)
    encoder = {"columns": "rows_json", "rows": "to_dict"}[path]
    fast = getattr(ResultTable, encoder)

    def slow(self):
        time.sleep(0.2)
        return fast(self)
    monkeypatch.setattr(ResultTable, encoder, slow)
    meters = http_cluster.broker._metrics
    before = meters.meter("broker_encode", labels={"path": path})
    body = _post(http_cluster, "SET trace = true; " + ENCODED[path])
    (resp, table), = sent
    assert body == json.dumps(resp.to_dict(), default=str).encode()
    answer = json.loads(body)
    assert answer["resultTable"]["rows"] and not answer["exceptions"]
    tree = answer["traceInfo"]
    enc, = _spans(tree, "BrokerEncode")
    assert enc in tree["children"]
    assert enc["encodePath"] == resp.encode_path == path
    assert meters.meter("broker_encode", labels={"path": path}) == before + 1
    assert enc["responseBytes"] == len(table) == len(
        json.dumps(resp.result_table.to_dict(), default=str).encode())
    # timeUsedMs still ends after the reduce: the encode lies after it,
    # inside the root
    assert enc["durationMs"] >= 200 > answer["timeUsedMs"]
    assert enc["startNs"] >= tree["startNs"] + (
        answer["timeUsedMs"] - 1) * 1e6
    assert tree["durationMs"] >= answer["timeUsedMs"] + enc["durationMs"] - 1
    # both processes' roots say where their collector stood
    server, = _spans(tree, "ServerRequest")
    assert "gcTotalMs" in tree and "gcTotalMs" in server


def test_in_process_handle_encodes_nothing(http_cluster, monkeypatch):
    monkeypatch.setattr(BrokerResponse, "encode_table", lambda self:
                        pytest.fail("handle() encoded the answer"))
    resp = http_cluster.query("SET trace = true; " + GROUP_SQL)
    assert not resp.exceptions and resp.rows
    assert not _spans(resp.trace, "BrokerEncode")
