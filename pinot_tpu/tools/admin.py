"""pinot-tpu administrator CLI.

Reference parity: pinot-tools PinotAdministrator.java:93 — subcommand
front door (StartServer/StartBroker, AddTable, LaunchDataIngestionJob,
PostQuery, Quickstart...). Usage:

  python -m pinot_tpu.tools.admin Quickstart [--port 8099]
  python -m pinot_tpu.tools.admin LaunchDataIngestionJob \\
      --table table.json --schema schema.json \\
      --input 'data/*.csv' --output segments/
  python -m pinot_tpu.tools.admin StartCluster --table table.json \\
      --schema schema.json --segments 'segments/*' [--port 8099]
  python -m pinot_tpu.tools.admin PostQuery --broker localhost:8099 \\
      --query 'SELECT ...'
  python -m pinot_tpu.tools.admin CreateSegment ... (alias of ingestion job)
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time


def _load_table_schema(args):
    from pinot_tpu.models import Schema, TableConfig
    with open(args.table) as f:
        table_config = TableConfig.from_dict(json.load(f))
    with open(args.schema) as f:
        schema = Schema.from_dict(json.load(f))
    return table_config, schema


def cmd_ingest(args) -> int:
    from pinot_tpu.ingest.batch import IngestionJobSpec, run_ingestion_job
    table_config, schema = _load_table_schema(args)
    spec = IngestionJobSpec(
        input_pattern=args.input, output_dir=args.output,
        table_config=table_config, schema=schema,
        input_format=args.format,
        rows_per_segment=args.rows_per_segment)
    out = run_ingestion_job(spec)
    print(f"created {len(out)} segment(s):")
    for d in out:
        print(" ", d)
    return 0


def cmd_start_cluster(args) -> int:
    from pinot_tpu.cluster.mini import MiniCluster
    from pinot_tpu.segment.loader import load_segment
    table_config, schema = _load_table_schema(args)
    cluster = MiniCluster(num_servers=args.servers, use_tpu=not args.no_tpu)
    cluster.start(with_http=False)
    cluster.http = _http_on_port(cluster, args.port)
    cluster.add_table(table_config.name, table_config.table_type.value,
                      time_column=table_config.retention.time_column)
    n = 0
    for i, seg_dir in enumerate(sorted(glob.glob(args.segments))):
        if not os.path.isdir(seg_dir):
            continue
        cluster.add_segment(table_config.name, load_segment(seg_dir),
                            server_idx=i % args.servers,
                            table_type=table_config.table_type.value)
        n += 1
    print(f"serving {n} segment(s) of table {table_config.name!r} "
          f"on http://127.0.0.1:{cluster.http.port}/query/sql")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        cluster.stop()
    return 0


def _http_on_port(cluster, port: int):
    from pinot_tpu.broker.http_api import BrokerHttpServer
    http = BrokerHttpServer(cluster.broker, port=port)
    http.start()
    return http


def cmd_post_query(args) -> int:
    import urllib.request
    req = urllib.request.Request(
        f"http://{args.broker}/query/sql",
        data=json.dumps({"sql": args.query}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as f:
        body = json.loads(f.read())
    print(json.dumps(body, indent=2, default=str))
    return 0


def cmd_quickstart(args) -> int:
    """Ref Quickstart.java — synthesize a demo table, serve it, run a
    sample query."""
    import numpy as np
    from pinot_tpu.cluster.mini import MiniCluster
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    import tempfile

    schema = Schema("baseballStats", [
        FieldSpec("playerID", DataType.STRING),
        FieldSpec("teamID", DataType.STRING),
        FieldSpec("yearID", DataType.INT),
        FieldSpec("league", DataType.STRING),
        FieldSpec("runs", DataType.INT, FieldType.METRIC),
        FieldSpec("hits", DataType.INT, FieldType.METRIC),
        FieldSpec("homeRuns", DataType.INT, FieldType.METRIC),
    ])
    tc = TableConfig("baseballStats", TableType.OFFLINE)
    rng = np.random.default_rng(1887)
    n = args.rows
    cols = {
        "playerID": [f"player_{v}" for v in rng.integers(0, n // 20 + 1, n)],
        "teamID": [f"team_{v}" for v in rng.integers(0, 30, n)],
        "yearID": rng.integers(1871, 2024, n).astype(np.int32),
        "league": [("AL", "NL")[v] for v in rng.integers(0, 2, n)],
        "runs": rng.integers(0, 150, n).astype(np.int32),
        "hits": rng.integers(0, 250, n).astype(np.int32),
        "homeRuns": rng.integers(0, 60, n).astype(np.int32),
    }
    tmp = tempfile.mkdtemp(prefix="pinot_tpu_quickstart_")
    creator = SegmentCreator(tc, schema)
    segs = []
    per_seg = max(n // 4, 1)
    for i in range(4):
        sl = slice(i * per_seg, (i + 1) * per_seg if i < 3 else n)
        seg_cols = {k: (v[sl] if hasattr(v, "__getitem__") else v)
                    for k, v in cols.items()}
        d = os.path.join(tmp, f"seg_{i}")
        creator.build(seg_cols, d, f"baseballStats_{i}")
        segs.append(load_segment(d))

    cluster = MiniCluster(num_servers=2, use_tpu=not args.no_tpu)
    cluster.start(with_http=False)
    cluster.http = _http_on_port(cluster, args.port)
    cluster.add_table("baseballStats")
    for i, seg in enumerate(segs):
        cluster.add_segment("baseballStats", seg, server_idx=i % 2)
    print(f"quickstart cluster up: http://127.0.0.1:{cluster.http.port}/query/sql")
    for sql in (
            "SELECT COUNT(*) FROM baseballStats",
            "SELECT SUM(runs) FROM baseballStats",
            "SELECT league, SUM(homeRuns) FROM baseballStats "
            "GROUP BY league ORDER BY league LIMIT 10"):
        resp = cluster.query(sql)
        print(f"  {sql}\n    -> {resp.rows}")
    if args.exit_after_queries:
        cluster.stop()
        return 0
    print("Ctrl-C to stop")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        cluster.stop()
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="pinot-tpu-admin")
    sub = p.add_subparsers(dest="command", required=True)

    q = sub.add_parser("Quickstart", help="demo table + cluster + queries")
    q.add_argument("--port", type=int, default=8099)
    q.add_argument("--rows", type=int, default=100_000)
    q.add_argument("--no-tpu", action="store_true")
    q.add_argument("--exit-after-queries", action="store_true")
    q.set_defaults(fn=cmd_quickstart)

    for name in ("LaunchDataIngestionJob", "CreateSegment"):
        j = sub.add_parser(name, help="files -> segments")
        j.add_argument("--table", required=True)
        j.add_argument("--schema", required=True)
        j.add_argument("--input", required=True, help="input file glob")
        j.add_argument("--output", required=True)
        j.add_argument("--format", default=None)
        j.add_argument("--rows-per-segment", type=int, default=None)
        j.set_defaults(fn=cmd_ingest)

    s = sub.add_parser("StartCluster", help="serve segment dirs over HTTP")
    s.add_argument("--table", required=True)
    s.add_argument("--schema", required=True)
    s.add_argument("--segments", required=True, help="segment dir glob")
    s.add_argument("--servers", type=int, default=2)
    s.add_argument("--port", type=int, default=8099)
    s.add_argument("--no-tpu", action="store_true")
    s.set_defaults(fn=cmd_start_cluster)

    pq = sub.add_parser("PostQuery", help="POST sql to a broker")
    pq.add_argument("--broker", default="localhost:8099")
    pq.add_argument("--query", required=True)
    pq.set_defaults(fn=cmd_post_query)

    # separate-process roles (ref StartController/StartServer/StartBroker
    # admin subcommands; the coordination service replaces ZK/Helix)
    sc = sub.add_parser("StartController",
                        help="coordination service + maintenance loops")
    sc.add_argument("--state-dir", required=True)
    # default 0 = resolve through PinotConfiguration (catalog default 9000)
    sc.add_argument("--port", type=int, default=0)
    sc.add_argument("--deep-store", default=None,
                    help="deep-store base URI (e.g. file:///data/store)")
    sc.add_argument("--http-port", type=int, default=None,
                    help="controller REST API port (disabled when unset)")
    sc.add_argument("--config", default=None,
                    help="instance .properties file (PinotConfiguration)")
    sc.set_defaults(fn=cmd_start_controller)

    sst = sub.add_parser("StartStreamServer",
                         help="TCP stream broker (topic partition logs)")
    sst.add_argument("--port", type=int, default=0)
    sst.set_defaults(fn=cmd_start_stream_server)

    ss = sub.add_parser("StartServer", help="query server joined to a "
                                            "controller")
    ss.add_argument("--instance-id", required=True)
    ss.add_argument("--coordinator", required=True, help="host:port")
    ss.add_argument("--query-port", type=int, default=0)
    ss.add_argument("--tpu", action="store_true")
    ss.add_argument("--tenant", default=None,
                    help="tenant pool this server serves (registers the "
                         "tenant:<name> instance tag; tables tagged with "
                         "the same tenant assign only here)")
    ss.add_argument("--plugins-dir", default=None,
                    help="directory of plugin modules to load at startup")
    ss.add_argument("--config", default=None,
                    help="instance .properties file (PinotConfiguration)")
    ss.set_defaults(fn=cmd_start_server)

    scs = sub.add_parser("StartCacheServer",
                         help="shared L2 cache tier (remote cache role)")
    scs.add_argument("--port", type=int, default=0)
    scs.add_argument("--config", default=None,
                     help="instance .properties file (PinotConfiguration)")
    scs.set_defaults(fn=cmd_start_cache_server)

    sm = sub.add_parser("StartMinion", help="background-task worker "
                                            "joined to a controller")
    sm.add_argument("--instance-id", required=True)
    sm.add_argument("--coordinator", required=True, help="host:port")
    sm.add_argument("--task-types", default=None,
                    help="csv of task types to lease (default: all)")
    sm.add_argument("--work-dir", default=None,
                    help="sandbox dir for task builds (default: tempdir)")
    sm.add_argument("--config", default=None,
                    help="instance .properties file (PinotConfiguration)")
    sm.set_defaults(fn=cmd_start_minion)

    lt = sub.add_parser("ListTasks", help="list the controller task queue")
    lt.add_argument("--coordinator", required=True)
    lt.add_argument("--state", default=None,
                    help="filter: PENDING|LEASED|RUNNING|COMPLETED|"
                         "FAILED|CANCELLED")
    lt.set_defaults(fn=cmd_list_tasks)

    ct = sub.add_parser("CancelTask", help="cancel a queued/running task")
    ct.add_argument("--coordinator", required=True)
    ct.add_argument("--task-id", required=True)
    ct.set_defaults(fn=cmd_cancel_task)

    sb = sub.add_parser("StartBroker", help="HTTP broker joined to a "
                                            "controller")
    sb.add_argument("--coordinator", required=True, help="host:port")
    sb.add_argument("--http-port", type=int, default=0)
    sb.add_argument("--config", default=None,
                    help="instance .properties file (PinotConfiguration)")
    sb.set_defaults(fn=cmd_start_broker)

    at = sub.add_parser("AddTable", help="register table config + schema "
                                         "with the controller")
    at.add_argument("--coordinator", required=True)
    at.add_argument("--table", required=True, help="table config json file")
    at.add_argument("--schema", required=True, help="schema json file")
    at.set_defaults(fn=cmd_add_table)

    us = sub.add_parser("UploadSegment", help="assign a built segment dir")
    us.add_argument("--coordinator", required=True)
    us.add_argument("--table", required=True)
    us.add_argument("--segment-dir", required=True)
    us.add_argument("--table-type", default="OFFLINE")
    us.set_defaults(fn=cmd_upload_segment)

    args = p.parse_args(argv)
    return args.fn(args)


def cmd_start_controller(args) -> int:
    from pinot_tpu.cluster.roles import run_controller
    from pinot_tpu.utils.config import PinotConfiguration
    run_controller(args.state_dir, port=args.port,
                   deep_store_uri=args.deep_store,
                   http_port=getattr(args, "http_port", None),
                   config=PinotConfiguration(getattr(args, "config", None)))
    return 0


def cmd_start_stream_server(args) -> int:
    import time as _time

    from pinot_tpu.ingest.tcp_stream import StreamServer
    server = StreamServer(port=args.port)
    server.start()
    print(f"stream server listening on {server.address}", flush=True)
    try:
        while True:
            _time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()
    return 0


def cmd_start_server(args) -> int:
    from pinot_tpu.cluster.roles import run_server
    from pinot_tpu.utils import plugins
    from pinot_tpu.utils.config import PinotConfiguration
    plugins.load_builtin_plugins()
    if getattr(args, "plugins_dir", None):
        loaded = plugins.load_plugin_dir(args.plugins_dir)
        print(f"loaded plugins: {loaded}", flush=True)
    cfg = PinotConfiguration(getattr(args, "config", None))
    run_server(args.instance_id, args.coordinator,
               query_port=args.query_port, use_tpu=args.tpu, config=cfg,
               tenant=getattr(args, "tenant", None))
    return 0


def cmd_start_cache_server(args) -> int:
    from pinot_tpu.cluster.roles import run_cache_server
    from pinot_tpu.utils.config import PinotConfiguration
    run_cache_server(port=args.port,
                     config=PinotConfiguration(getattr(args, "config", None)))
    return 0


def cmd_start_minion(args) -> int:
    from pinot_tpu.cluster.roles import run_minion
    from pinot_tpu.utils.config import PinotConfiguration
    task_types = None
    if getattr(args, "task_types", None):
        task_types = [t.strip() for t in args.task_types.split(",")
                      if t.strip()]
    run_minion(args.instance_id, args.coordinator, task_types=task_types,
               work_dir=getattr(args, "work_dir", None),
               config=PinotConfiguration(getattr(args, "config", None)))
    return 0


def cmd_list_tasks(args) -> int:
    from pinot_tpu.controller.coordination import CoordinationClient
    client = CoordinationClient(args.coordinator)
    r = client.request("task_list", state=getattr(args, "state", None))
    client.close()
    print(json.dumps(r["tasks"], indent=2, default=str))
    return 0


def cmd_cancel_task(args) -> int:
    from pinot_tpu.controller.coordination import CoordinationClient
    client = CoordinationClient(args.coordinator)
    r = client.request("task_cancel", task_id=args.task_id)
    client.close()
    if not r.get("ok"):
        print(f"no task {args.task_id}")
        return 1
    print(f"task {args.task_id}: {r['state']}")
    return 0


def cmd_start_broker(args) -> int:
    from pinot_tpu.cluster.roles import run_broker
    from pinot_tpu.utils.config import PinotConfiguration
    run_broker(args.coordinator, http_port=args.http_port,
               config=PinotConfiguration(getattr(args, "config", None)))
    return 0


def cmd_add_table(args) -> int:
    import json as _json

    from pinot_tpu.controller.coordination import CoordinationClient
    from pinot_tpu.models import Schema, TableConfig
    with open(args.table) as f:
        cfg = TableConfig.from_dict(_json.load(f))
    with open(args.schema) as f:
        schema = Schema.from_dict(_json.load(f))
    client = CoordinationClient(args.coordinator)
    client.add_table(cfg, schema)
    client.close()
    print(f"added table {cfg.name}")
    return 0


def cmd_upload_segment(args) -> int:
    from pinot_tpu.controller.coordination import CoordinationClient
    client = CoordinationClient(args.coordinator)
    store_uri = client.get_state().get("deep_store_uri")
    if store_uri:
        # the controller has a deep store: push the tar there and
        # register its URI, so servers download instead of sharing the
        # build directory
        from pinot_tpu.segment.fs import SegmentDeepStore
        r = client.upload_segment_to_store(
            args.table, args.segment_dir, SegmentDeepStore(store_uri),
            table_type=args.table_type)
    else:
        r = client.upload_segment(args.table, args.segment_dir,
                                  table_type=args.table_type)
    client.close()
    print(f"assigned to {r['segment']['instances']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
