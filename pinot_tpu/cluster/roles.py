"""Separate-process cluster roles: controller, server, broker, minion.

Reference parity: the role starters — BaseControllerStarter.java:150,
BaseServerStarter.java:135 (start():578 joins Helix as PARTICIPANT,
registers the state-model factory reacting to OFFLINE->ONLINE
transitions), BaseBrokerStarter.java:104 (BrokerRoutingManager watching
ExternalView). Each run_* function below is one OS process's main loop;
tools/admin.py exposes them as start-controller / start-server /
start-broker subcommands, and tests/test_multiprocess_cluster.py starts
real processes through them (ref ClusterTest.java:92's embedded cluster,
promoted to actual process isolation).

State flows through the coordination service (controller/coordination.py):
servers watch for segments assigned to them and load/unload to converge
(the Helix state-transition analog); brokers watch and rebuild routing
tables + server connections (the ExternalView routing rebuild).
"""
from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, List, Optional, Set

from pinot_tpu.controller.coordination import CoordinationClient

log = logging.getLogger(__name__)


def _start_admin(cfg, key: str, roles, routes=None) -> Optional[object]:
    """Per-role /metrics + /debug surface (trace_store.DebugHttpServer)
    for roles without an HTTP edge. Knob semantics: 0 = ephemeral port,
    >0 = fixed, <0 = disabled."""
    try:
        port = int(cfg.get(key, 0) or 0)
    except (TypeError, ValueError):
        port = 0
    if port < 0:
        return None
    from pinot_tpu.utils.trace_store import DebugHttpServer
    try:
        srv = DebugHttpServer(roles, port=port, routes=routes)
        srv.start()
    except OSError as e:
        # a debug-only surface must never take the data-serving role
        # down with it (port already owned, bind denied, ...)
        log.warning("admin http (%s=%s) failed to bind: %s — "
                    "continuing without it", key, port, e)
        return None
    return srv


def run_controller(state_dir: str, port: int = 0, host: str = "127.0.0.1",
                   deep_store_uri: Optional[str] = None,
                   http_port: Optional[int] = None, config=None,
                   ready_event: Optional[threading.Event] = None,
                   stop_event: Optional[threading.Event] = None) -> None:
    from pinot_tpu.controller.cluster_state import ClusterState
    from pinot_tpu.controller.controller import Controller
    from pinot_tpu.controller.coordination import CoordinationServer
    from pinot_tpu.controller.maintenance import run_retention
    from pinot_tpu.controller.rebalancer import (Rebalancer,
                                                 make_staged_load_fn)
    from pinot_tpu.controller.repair import (RepairChecker,
                                             update_replication_gauges)
    from pinot_tpu.controller.task_manager import TaskManager
    from pinot_tpu.utils.config import PinotConfiguration

    cfg = config or PinotConfiguration()
    if not port:
        port = cfg.get_int("pinot.controller.port")
    state = ClusterState(persist_dir=state_dir)
    # the minion task fabric: durable (journaled) queue + generator
    # cadence + lease-expiry sweeps, served over the coordination channel
    tasks = TaskManager(
        state, config=cfg,
        journal_path=os.path.join(state_dir, "tasks.journal"))
    server = CoordinationServer(state, host=host, port=port,
                                deep_store_uri=deep_store_uri
                                or cfg.get_str(
                                    "pinot.controller.deep.store.uri")
                                or None,
                                task_manager=tasks)
    server.LIVENESS_TTL_S = cfg.get_float(
        "pinot.coordination.liveness.ttl.seconds")
    server.start()
    tasks.start()
    # self-healing plane: journaled move engine + automatic repair.
    # Watch-driven wiring — load_fn STAGES the replica (servers
    # reconcile+warm staged segments, brokers keep routing by
    # `instances`) and waits for the server's load ack; the source
    # drains via the servers' own reconcile once commit_moves drops it
    # from the assignment, so unload_fn is a no-op here.
    rebalancer = Rebalancer(
        state,
        load_fn=make_staged_load_fn(state, server.segment_is_loaded),
        unload_fn=lambda _inst, _table, _name: None,
        live_fn=lambda iid: server.heartbeat_ages().get(
            iid, 0.0) <= server.LIVENESS_TTL_S,
        config=cfg,
        journal_path=os.path.join(state_dir, "rebalance.journal"))
    repair = RepairChecker(state, rebalancer, server.heartbeat_ages,
                           config=cfg)
    controller_api = Controller(state=state, config=cfg)
    controller_api.rebalancer = rebalancer  # share the journaled engine
    # a restart resumes half-finished move plans from the journal —
    # async: staged loads block on server acks, which need the fleet up
    threading.Thread(target=rebalancer.resume, daemon=True,
                     name="rebalance-resume").start()
    # fleet health plane: the controller samples its OWN registry like
    # every role, and sweeps the fleet (the periodic-health-task analog)
    from pinot_tpu.health.history import start_sampling, stop_sampling
    from pinot_tpu.health.rollup import make_cluster_monitor
    start_sampling("controller", cfg)
    monitor = None
    if cfg.get_bool("pinot.cluster.health.enabled", True):
        monitor = make_cluster_monitor(state, server, config=cfg)
        monitor.start()
    rest = None
    if http_port is not None:
        from pinot_tpu.controller.http_api import ControllerHttpServer
        rest = ControllerHttpServer(state, coordination=server,
                                    host=host, port=http_port,
                                    task_manager=tasks,
                                    health_monitor=monitor,
                                    controller=controller_api)
        rest.start()
        print(f"controller REST on {rest.host}:{rest.port}", flush=True)
    print(f"controller listening on {server.address}", flush=True)
    if ready_event is not None:
        ready_event.set()
    stop = stop_event or threading.Event()
    retention_every = cfg.get_float(
        "pinot.controller.retention.frequency.seconds")
    repair_every = cfg.get_float(
        "pinot.controller.repair.frequency.seconds")
    last_maintenance = time.time()
    last_repair = time.time()
    try:
        while not stop.wait(1.0):
            if time.time() - last_maintenance > retention_every:
                last_maintenance = time.time()
                try:
                    # removals notify watchers: servers reconcile the
                    # expired segments away, brokers rebuild routes (the
                    # routing epoch moves, so cached results for the
                    # dropped segments become unaddressable)
                    run_retention(state)
                except Exception:  # noqa: BLE001 — periodic must survive
                    log.exception("retention pass failed")
            if repair_every > 0 \
                    and time.time() - last_repair > repair_every:
                last_repair = time.time()
                try:
                    # SegmentStatusChecker + RebalanceChecker tick:
                    # refresh the replication gauges, then repair any
                    # debounced-dead instance's segments
                    update_replication_gauges(state)
                    repair.check_once()
                except Exception:  # noqa: BLE001 — periodic must survive
                    log.exception("repair pass failed")
    finally:
        if rest is not None:
            rest.stop()
        if monitor is not None:
            monitor.stop()
        stop_sampling("controller")
        tasks.stop()
        rebalancer.close()
        server.stop()


def run_cache_server(port: int = 0, host: str = "127.0.0.1", config=None,
                     ready_event: Optional[threading.Event] = None,
                     stop_event: Optional[threading.Event] = None) -> None:
    """The cache-server role: one shared LruTtlCache byte budget serving
    GET/SET/DELETE/STATS over TCP (cache/remote.py) — the L2 every
    broker's result cache and server's segment cache mounts when its
    backend knob says `tiered`. Stateless across restarts by design:
    entries are recomputable, so durability would buy nothing."""
    from pinot_tpu.cache.remote import CacheServer
    from pinot_tpu.utils.config import PinotConfiguration
    from pinot_tpu.utils.metrics import get_registry

    cfg = config or PinotConfiguration()
    if not port:
        port = cfg.get_int("pinot.cache.server.port")
    server = CacheServer(
        host=host, port=port,
        max_bytes=cfg.get_int("pinot.cache.server.bytes"),
        ttl_seconds=cfg.get_float("pinot.cache.server.ttl.seconds"),
        metrics=get_registry("cache_server"))
    server.start()
    admin = _start_admin(cfg, "pinot.cache.server.admin.port",
                         ["cache_server"])
    from pinot_tpu.health.history import start_sampling, stop_sampling
    start_sampling("cache_server", cfg)
    if admin is not None:
        print(f"cache server admin http on {admin.host}:{admin.port}",
              flush=True)
    print(f"cache server listening on {server.address}", flush=True)
    if ready_event is not None:
        ready_event.set()
    stop = stop_event or threading.Event()
    try:
        while not stop.wait(2.0):
            pass
    finally:
        stop_sampling("cache_server")
        if admin is not None:
            admin.stop()
        server.stop()


def run_minion(instance_id: str, coordinator: str,
               task_types=None, work_dir=None, config=None,
               ready_event: Optional[threading.Event] = None,
               stop_event: Optional[threading.Event] = None) -> None:
    """The minion role: one background-task worker process leasing work
    from the controller's task queue (minion/worker.py). Modeled on
    run_cache_server — stateless across restarts: in-flight work is
    protected by the lease protocol (an unfinished task's lease expires
    and requeues), and committed work lives in the deep store + cluster
    state, so a killed minion loses nothing."""
    from pinot_tpu.minion.worker import MinionWorker
    from pinot_tpu.utils.config import PinotConfiguration

    cfg = config or PinotConfiguration()
    worker = MinionWorker(instance_id, coordinator, work_dir=work_dir,
                          task_types=task_types, config=cfg)
    worker.start()
    admin = _start_admin(cfg, "pinot.minion.admin.port", ["minion"])
    from pinot_tpu.health.history import start_sampling, stop_sampling
    start_sampling("minion", cfg)
    if admin is not None:
        print(f"minion admin http on {admin.host}:{admin.port}",
              flush=True)
        # re-register with the scrape URL so the controller's
        # cluster-health sweep reads this worker's /debug/health
        try:
            worker.client.register_instance(
                instance_id, "127.0.0.1", 0, tags=["minion"],
                admin_url=f"http://{admin.host}:{admin.port}")
        except (ConnectionError, OSError, RuntimeError):
            pass
    print(f"minion {instance_id} polling {coordinator}", flush=True)
    if ready_event is not None:
        ready_event.set()
    stop = stop_event or threading.Event()
    try:
        while not stop.wait(2.0):
            try:
                worker.client.request("heartbeat", instance_id=instance_id)
            except (ConnectionError, OSError, RuntimeError):
                pass
    finally:
        stop_sampling("minion")
        if admin is not None:
            admin.stop()
        worker.stop()


class ServerRole:
    """One server process: query transport + data manager + state watch."""

    def __init__(self, instance_id: str, coordinator: str,
                 query_port: int = 0, host: str = "127.0.0.1",
                 use_tpu: bool = False,
                 download_dir: Optional[str] = None,
                 config=None, tenant: Optional[str] = None):
        import tempfile

        from pinot_tpu.server.data_manager import InstanceDataManager
        from pinot_tpu.server.query_server import (
            QueryServer, ServerQueryExecutor)
        from pinot_tpu.utils.config import PinotConfiguration

        cfg = config or PinotConfiguration()
        self.config = cfg
        self.instance_id = instance_id
        self.client = CoordinationClient(coordinator)
        self.data_manager = InstanceDataManager(instance_id)
        self.executor = ServerQueryExecutor(self.data_manager,
                                            use_tpu=use_tpu, config=cfg)
        self.transport = QueryServer(
            self.executor, host=host,
            port=query_port or cfg.get_int("pinot.server.query.port"),
            num_threads=cfg.get_int("pinot.server.query.num.threads"),
            scheduler=cfg.get_str("pinot.server.query.scheduler"))
        #: local cache for deep-store segment downloads — deterministic
        #: per instance so restarts REUSE extracted copies instead of
        #: leaking a fresh tempdir per process lifetime
        self.download_dir = download_dir or os.path.join(
            tempfile.gettempdir(), f"pinot-tpu-dl-{instance_id}")
        self._loaded: Set[tuple] = set()  # (physical_table, segment_name)
        #: (physical_table, partition_id) -> RealtimeSegmentDataManager
        self._rt_managers: Dict[tuple, object] = {}
        #: per-TABLE ingestion lag trackers, metrics-wired: gauges
        #: `ingestion_delay_ms{table=,partition=}` feed dashboards, and
        #: the backpressure controller reads them for the lag ceiling.
        #: Per table, not per server — partition ids collide across
        #: tables, and one table's consumer stopping must not zero
        #: another's lag
        self._delay_trackers: Dict[str, object] = {}
        #: physical_table -> (partition ids, discovered-at) — cached so a
        #: watch storm doesn't re-dial the stream broker per notification,
        #: refreshed periodically so added partitions start consuming
        #: (ref KafkaStreamMetadataProvider.fetchPartitionCount re-polls)
        self._rt_partitions: Dict[str, tuple] = {}
        self._stopping = False
        #: tenant pool this server joins (tenant:<name> instance tag);
        #: None = the DefaultTenant pool
        self.tenant = tenant
        self._reconcile_lock = threading.Lock()
        #: per-role ops surface: /metrics + /debug/traces + /debug/queries
        self.admin_http = None
        # admission memory shedding reuses the ingest accounting: the
        # worst partition's non-durable bytes against the per-consumer
        # budget (0 budget = never sheds on ingest memory)
        self.executor.add_memory_pressure_source(self._ingest_pressure)

    def _ingest_pressure(self) -> float:
        """Worst per-partition ingest-memory fraction (mutable + sealed
        pending-build bytes vs pinot.server.ingest.memory.bytes)."""
        budget = self.config.get_int("pinot.server.ingest.memory.bytes")
        if budget <= 0:
            return 0.0
        # lint: unlocked(point-in-time snapshot; dict ops are atomic under the GIL and a racing reconcile add only delays one pressure read)
        managers = list(self._rt_managers.values())
        worst = 0.0
        for mgr in managers:
            try:
                worst = max(worst, mgr.ingest_bytes() / budget)
            except Exception:  # noqa: BLE001 — a dying consumer must
                pass           # not take admission down
        return worst

    #: partition-discovery refresh interval
    RT_PARTITION_TTL_S = 30.0

    def start(self) -> None:
        if self.executor.use_tpu:
            # the device is claimed and named HERE, not at the first
            # query: a server that cannot reach its chip fails to start
            # (JAX_PLATFORMS=tpu makes JAX raise instead of handing back
            # CPU devices), and one that can says what it got
            from pinot_tpu.ops.device import device_line
            print(device_line(self.executor.device_report()), flush=True)
        self.transport.start()
        self.admin_http = _start_admin(
            self.config, "pinot.server.admin.port", ["server"],
            routes={"/debug/device": self.executor.device_report})
        if self.admin_http is not None:
            log.info("server %s admin http on %s:%s", self.instance_id,
                     self.admin_http.host, self.admin_http.port)
        # fleet health plane: the background registry sampler (metrics
        # history + SLO watchdog hook) for this process's server role
        from pinot_tpu.health.history import start_sampling
        start_sampling("server", self.config)
        self.client.register_instance(
            self.instance_id, self.transport.host, self.transport.port,
            tags=[f"tenant:{self.tenant}"] if self.tenant else None,
            admin_url=(f"http://{self.admin_http.host}:"
                       f"{self.admin_http.port}"
                       if self.admin_http is not None else ""))
        self.reconcile()
        self.client.watch(lambda _v: self.reconcile())

    def stop(self) -> None:
        from pinot_tpu.health.history import stop_sampling
        stop_sampling("server")
        if self.admin_http is not None:
            self.admin_http.stop()
            self.admin_http = None
        with self._reconcile_lock:  # no reconcile mid-shutdown
            self._stopping = True
            managers = list(self._rt_managers.values())
        # graceful drain, two-phase so shutdown does not scale with the
        # partition count: request every seal FIRST (the force flags make
        # each consumer thread seal concurrently, builds overlapping on
        # their own pools), then drain+join each — the per-manager waits
        # mostly find the work already done
        for mgr in managers:
            try:
                mgr.force_commit(wait_s=0.0)
            except Exception:  # noqa: BLE001 — drain is best-effort
                pass
        for mgr in managers:
            # force-commit the non-empty mutable (through the completion
            # FSM) and persist the final checkpoint, so a rolling restart
            # loses zero rows
            mgr.stop(timeout=5.0, drain=True)
        self.client.close()
        self.transport.stop()
        self.data_manager.shutdown()
        self.executor.fingerprint_log.close()

    # ------------------------------------------------------------------
    def reconcile(self) -> None:
        """Converge local segments to the coordinator's assignment (the
        OFFLINE->ONLINE / ONLINE->OFFLINE transition handler,
        ref SegmentOnlineOfflineStateModelFactory.java:44)."""
        from pinot_tpu.segment.loader import load_segment
        with self._reconcile_lock:
            if self._stopping:
                return
            try:
                blob = self.client.get_state()
            except (ConnectionError, OSError, RuntimeError):
                log.warning("coordinator unreachable; keeping local state")
                return
            # tenant scheduling weights ride the table configs: push
            # them into the query scheduler so weighted-fair groups are
            # shaped before the tenant's first query arrives
            sched = self.transport.scheduler
            if hasattr(sched, "set_tenant_weight"):
                for cfg_d in blob.get("tables", {}).values():
                    tn = cfg_d.get("tenants") or {}
                    if tn.get("server"):
                        sched.set_tenant_weight(
                            tn["server"], float(tn.get("weight", 1.0)))
            wanted: Set[tuple] = set()
            acks: List[tuple] = []
            for table, segs in blob.get("segments", {}).items():
                for name, st in segs.items():
                    # a STAGED replica (rebalance load-before-route)
                    # loads+warms exactly like an assigned one — brokers
                    # just don't route to it until the move commits
                    staged = self.instance_id in st.get("staged", ())
                    if (self.instance_id in st.get("instances", ())
                            or staged) \
                            and st.get("status") == "ONLINE" \
                            and st.get("dir_path"):
                        wanted.add((table, name))
                        if (table, name) not in self._loaded:
                            tdm = self.data_manager.table(
                                table, create=False)
                            if tdm is not None \
                                    and name in tdm.segment_names:
                                # already serving a local copy (realtime
                                # commit on this server) — leave it to its
                                # owner, don't re-download or track it
                                if staged:
                                    acks.append((table, name))
                                continue
                            try:
                                seg = load_segment(
                                    self._localize(table, st["dir_path"]))
                                self.data_manager.table(table) \
                                    .add_segment(seg)
                                self._loaded.add((table, name))
                                if staged:
                                    acks.append((table, name))
                                log.info("loaded %s/%s", table, name)
                            except Exception:  # noqa: BLE001
                                log.exception("failed to load %s/%s",
                                              table, name)
                        elif staged:
                            # already loaded: re-ack — the controller's
                            # ack book may be fresh after a restart
                            acks.append((table, name))
            for table, name in list(self._loaded - wanted):
                tdm = self.data_manager.table(table, create=False)
                if tdm is not None:
                    tdm.remove_segment(name)
                self._loaded.discard((table, name))
                log.info("unloaded %s/%s", table, name)
            for table, name in acks:
                try:
                    # load ack: the rebalancer's staged-load barrier —
                    # routing only flips once the target reports servable
                    self.client.segment_loaded(table, name,
                                               self.instance_id)
                except Exception:  # noqa: BLE001 — ack is best-effort;
                    pass           # the load barrier times out and retries
            self._ensure_realtime(blob)

    def _ensure_realtime(self, blob: dict) -> None:
        """Start one consumer per (REALTIME table, stream partition) —
        every registered server consumes every partition, the completion
        FSM on the controller elects exactly one committer per segment
        (ref RealtimeTableDataManager + the CONSUMING state transition)."""
        from pinot_tpu.controller.coordination import RemoteCompletionManager
        from pinot_tpu.ingest.realtime_manager import \
            RealtimeSegmentDataManager
        from pinot_tpu.ingest.stream import StreamConfig, get_stream_factory
        from pinot_tpu.models import Schema, TableConfig
        import pinot_tpu.ingest.tcp_stream  # noqa: F401 — registers 'tcp'

        for logical, cfg_d in blob.get("tables", {}).items():
            cfg = TableConfig.from_dict(cfg_d)
            sic = cfg.ingestion.stream
            if cfg.table_type.value != "REALTIME" or sic is None:
                continue
            schema_d = blob.get("schemas", {}).get(logical)
            if schema_d is None:
                continue
            schema = Schema.from_dict(schema_d)
            props = dict(sic.properties)
            stream_cfg = StreamConfig(
                stream_type=sic.stream_type, topic=sic.topic,
                properties=props,
                flush_threshold_rows=int(
                    props.get("flushThresholdRows", 100_000)),
                flush_threshold_time_ms=int(
                    props.get("flushThresholdTimeMs", 6 * 3600 * 1000)))
            physical = cfg.table_name_with_type
            cached = self._rt_partitions.get(physical)
            if cached is not None and \
                    time.time() - cached[1] < self.RT_PARTITION_TTL_S:
                partitions = cached[0]
            else:
                # (re)discover: cheap enough per TTL, and added topic
                # partitions start consuming without a server restart
                try:
                    meta = get_stream_factory(stream_cfg) \
                        .create_metadata_provider(stream_cfg)
                    partitions = meta.partition_ids()
                    close = getattr(meta, "close", None)
                    if close is not None:
                        close()
                except Exception:  # noqa: BLE001 — stream not up yet
                    if cached is None:
                        log.warning("stream metadata unavailable for %s",
                                    physical)
                        continue
                    partitions = cached[0]
                self._rt_partitions[physical] = (partitions, time.time())
            store = None
            if blob.get("deep_store_uri"):
                from pinot_tpu.segment.fs import SegmentDeepStore
                store = SegmentDeepStore(blob["deep_store_uri"])
            for pid in partitions:
                key = (physical, pid)
                if key in self._rt_managers:
                    continue
                tdm = self.data_manager.table(physical)
                seg_store = os.path.join(self.download_dir, "rt", physical)
                # resume AFTER this partition's committed segments: the
                # persisted end_offset/seq are the replay checkpoint (ref
                # StreamPartitionMsgOffset in segment ZK metadata)
                start_offset, start_seq = self._rt_checkpoint(
                    blob, physical, pid)
                holder: Dict[str, object] = {}
                from pinot_tpu.utils.metrics import get_registry
                mgr = RealtimeSegmentDataManager(
                    cfg, schema, stream_cfg, pid, tdm, seg_store,
                    start_offset=start_offset,
                    completion_manager=RemoteCompletionManager(self.client),
                    instance_id=self.instance_id,
                    deep_store=store,
                    on_commit=self._rt_committed(physical, pid, holder),
                    on_open=self._rt_opened(physical, pid),
                    start_seq=start_seq,
                    ingestion_delay_tracker=self.delay_tracker_for(
                        physical),
                    config=self.config, metrics=get_registry("server"),
                    recover_segments=self._rt_recover_segments(
                        blob, physical, pid))
                holder["mgr"] = mgr
                mgr.start()
                self._rt_managers[key] = mgr
                log.info("consuming %s partition %d from %s (seq %d)",
                         physical, pid, start_offset, start_seq)

    def delay_tracker_for(self, physical: str):
        """The (lazily created) lag tracker for one realtime table."""
        from pinot_tpu.ingest.realtime_manager import IngestionDelayTracker
        from pinot_tpu.utils.metrics import get_registry
        tracker = self._delay_trackers.get(physical)
        if tracker is None:
            tracker = IngestionDelayTracker(
                metrics=get_registry("server"),
                labels={"instance": self.instance_id, "table": physical})
            self._delay_trackers[physical] = tracker
        return tracker

    def _rt_recover_segments(self, blob: dict, physical: str,
                             pid: int) -> list:
        """Restart recovery for upsert/dedup tables: the partition's
        already-loaded committed segments, in seq order, so the new
        manager re-registers their rows into the metadata map (upsert
        via the persisted validDocIds snapshots) before consuming —
        resumed consumption then neither replays committed rows as fresh
        duplicates nor forgets which rows already lost their upsert
        battle. Append-only tables skip the scan entirely."""
        from pinot_tpu.models.table_config import base_table_name
        cfg_d = blob.get("tables", {}).get(base_table_name(physical), {}) or {}
        if not cfg_d.get("upsertConfig") and not cfg_d.get("dedupConfig"):
            return []
        tdm = self.data_manager.table(physical, create=False)
        if tdm is None:
            return []
        local = set(tdm.segment_names)
        entries = []
        for name, st in blob.get("segments", {}).get(physical, {}).items():
            if st.get("partition_id") != pid or name not in local:
                continue
            parts = name.split("__")
            try:
                seq = int(parts[2]) if len(parts) >= 3 else 0
            except ValueError:
                seq = 0
            entries.append((seq, name))
        out = []
        for _seq, name in sorted(entries):
            seg = tdm.current_segment(name)
            if seg is not None:
                out.append(seg)
        return out

    @staticmethod
    def _rt_checkpoint(blob: dict, physical: str, pid: int):
        """(resume offset, next seq) from the persisted segment states —
        max committed end_offset and max seen sequence + 1."""
        from pinot_tpu.ingest.stream import LongMsgOffset
        best_off = None
        next_seq = 0
        for name, st in blob.get("segments", {}).get(physical, {}).items():
            if st.get("partition_id") != pid:
                continue
            parts = name.split("__")
            if len(parts) >= 3:
                try:
                    next_seq = max(next_seq, int(parts[2]) + 1)
                except ValueError:
                    pass
            off = st.get("end_offset")
            if st.get("status") == "ONLINE" and off is not None:
                off_i = int(str(off))
                if best_off is None or off_i > best_off:
                    best_off = off_i
        return (LongMsgOffset(best_off) if best_off is not None else None,
                next_seq)

    def _rt_opened(self, physical: str, pid: int):
        def cb(segment_name: str):
            self.client.request("add_segment_replica", segment={
                "name": segment_name, "table": physical,
                "instances": [self.instance_id], "dir_path": None,
                "num_docs": 0, "partition_id": pid,
                "status": "CONSUMING"})
        return cb

    def _rt_committed(self, physical: str, pid: int, holder: dict):
        def cb(segment_name: str, offset):
            mgr = holder.get("mgr")
            uri = getattr(mgr, "last_commit_uri", None)
            from pinot_tpu.segment.fs import is_store_uri
            self.client.request("add_segment_replica", segment={
                "name": segment_name, "table": physical,
                "instances": [self.instance_id],
                # only durable (store) locations are worth persisting —
                # a local build dir dies with this server
                "dir_path": uri if uri and is_store_uri(uri) else None,
                "num_docs": getattr(mgr, "last_commit_docs", 0),
                "partition_id": pid,
                "end_offset": str(offset), "status": "ONLINE"})
        return cb

    def _localize(self, table: str, dir_path: str) -> str:
        """A deep-store URI downloads through PinotFS into the local cache
        (ref BaseTableDataManager.downloadSegmentFromDeepStore); a plain
        path loads in place."""
        from pinot_tpu.segment.fs import localize_segment
        return localize_segment(
            dir_path, os.path.join(self.download_dir, table))


def run_server(instance_id: str, coordinator: str, query_port: int = 0,
               use_tpu: bool = False, config=None,
               ready_event: Optional[threading.Event] = None,
               stop_event: Optional[threading.Event] = None,
               tenant: Optional[str] = None) -> None:
    role = ServerRole(instance_id, coordinator, query_port=query_port,
                      use_tpu=use_tpu, config=config, tenant=tenant)
    role.start()
    print(f"server {instance_id} listening on "
          f"{role.transport.host}:{role.transport.port}", flush=True)
    if ready_event is not None:
        ready_event.set()
    stop = stop_event or threading.Event()
    try:
        while not stop.wait(2.0):
            try:
                # the instance-sweep payload: per-table HBM-resident
                # bytes ride every heartbeat so brokers can prefer the
                # replica whose device memory already holds the columns
                role.client.request(
                    "heartbeat", instance_id=instance_id,
                    residency=role.executor.residency_report())
            except (ConnectionError, OSError, RuntimeError):
                pass
    finally:
        role.stop()


class BrokerRole:
    """One broker process: HTTP edge + routing rebuilt from watches."""

    def __init__(self, coordinator: str, http_port: int = 0,
                 host: str = "127.0.0.1", config=None,
                 instance_id: Optional[str] = None):
        from pinot_tpu.broker.adaptive import AdaptiveServerSelector
        from pinot_tpu.broker.http_api import BrokerHttpServer
        from pinot_tpu.broker.quota import QueryQuotaManager
        from pinot_tpu.broker.request_handler import BrokerRequestHandler
        from pinot_tpu.broker.routing import BrokerRoutingManager
        from pinot_tpu.server.query_server import ServerConnection
        from pinot_tpu.utils.config import PinotConfiguration

        cfg = config or PinotConfiguration()
        self._config = cfg
        self.client = CoordinationClient(coordinator)
        self.routing = BrokerRoutingManager(
            selector=AdaptiveServerSelector(
                mode=cfg.get_str("pinot.broker.adaptive.selector")))
        self.connections: Dict[str, ServerConnection] = {}
        self.quotas = QueryQuotaManager()
        self.handler = BrokerRequestHandler(
            self.routing, self.connections,
            max_fanout_threads=cfg.get_int("pinot.broker.fanout.threads"),
            quota_manager=self.quotas, config=cfg)
        self.http = BrokerHttpServer(self.handler, host=host, port=http_port)
        self._host = host
        self.instance_id = instance_id or f"Broker_{host}_{self.http.port}"
        self._rebuild_lock = threading.Lock()

    def start(self) -> None:
        self.rebuild()
        self.client.watch(lambda _v: self.rebuild())
        self.http.start()
        from pinot_tpu.health.history import start_sampling
        start_sampling("broker", self._config)
        # join the scrapeable fleet: the "broker" role tag keeps segment
        # assignment away (cluster_state.NON_SERVER_TAGS); the broker's
        # own HTTP edge serves /debug/health + /debug/metrics/sample
        self.client.register_instance(
            self.instance_id, self._host, 0, tags=["broker"],
            admin_url=f"http://{self._host}:{self.http.port}")

    def stop(self) -> None:
        from pinot_tpu.health.history import stop_sampling
        stop_sampling("broker")
        self.client.close()
        self.http.stop()
        # snapshot under the rebuild lock: the coordinator-watch thread's
        # rebuild() swaps entries into self.connections under this lock,
        # and iterating the live dict here raced it — a watch firing
        # mid-shutdown grew the dict under the loop (RuntimeError: dict
        # changed size during iteration) and leaked the unclosed swapped-
        # in channels (lock-discipline race found by the static analyzer)
        with self._rebuild_lock:
            conns = list(self.connections.values())
        for c in conns:
            c.close()

    # ------------------------------------------------------------------
    def rebuild(self) -> None:
        """Routing rebuild from coordinator state (the ExternalView-change
        handler, ref BrokerRoutingManager.java:100)."""
        from pinot_tpu.broker.routing import (
            RoutingTable, SegmentInfo, TableRoute)
        from pinot_tpu.models import TableConfig
        from pinot_tpu.server.query_server import ServerConnection
        with self._rebuild_lock:
            try:
                blob = self.client.get_state()
            except (ConnectionError, OSError, RuntimeError):
                log.warning("coordinator unreachable; keeping routes")
                return
            group_selector = getattr(self.routing, "group_selector", None)
            for iid, inst in blob.get("instances", {}).items():
                if group_selector is not None:
                    # instance-sweep residency hints -> replica-choice
                    # tiebreak (heartbeat payload, cluster_state)
                    group_selector.update_residency(
                        iid, inst.get("residency") or {})
                if not inst.get("port"):
                    continue
                cur = self.connections.get(iid)
                if cur is not None and (cur.host, cur.port) == \
                        (inst["host"], inst["port"]):
                    continue
                # new instance OR a restarted one on a fresh port: swap in
                # the new channel; the old object is NOT closed here — a
                # query thread may be mid-request on it, and its own
                # ConnectionError path retires it safely
                self.connections[iid] = ServerConnection(
                    inst["host"], inst["port"])
            for logical, cfg_d in blob.get("tables", {}).items():
                cfg = TableConfig.from_dict(cfg_d)
                self.quotas.set_quota(
                    logical, cfg.query.max_queries_per_second)
                tenant = cfg.tenants.server
                self.quotas.set_table_tenant(logical, tenant)
                self.handler.tenants[logical] = tenant
                # per-tenant QPS ceiling: an operator knob, not a table
                # config (one tenant spans many tables). Applied
                # unconditionally so REMOVING the knob lifts the limit
                # on the next reconcile, symmetric with setting it
                tenant_qps = self._config.get(
                    f"pinot.broker.tenant.quota.qps.{tenant}")
                self.quotas.set_tenant_quota(
                    tenant,
                    float(tenant_qps) if tenant_qps is not None else None)
                physical = cfg.table_name_with_type
                route = TableRoute(
                    physical, time_column=cfg.retention.time_column,
                    num_replica_groups=cfg.routing.num_replica_groups)
                pcol = cfg.routing.partition_column
                nparts = 0
                if pcol and cfg.partition_config.get(pcol):
                    nparts = int(cfg.partition_config[pcol]
                                 .get("numPartitions", 0) or 0)
                for name, st in blob.get("segments", {}) \
                                     .get(physical, {}).items():
                    if st.get("status") == "OFFLINE":
                        continue
                    pid = st.get("partition_id")
                    route.segments[name] = SegmentInfo(
                        name=name, servers=list(st.get("instances", ())),
                        partition_id=pid,
                        partition_column=pcol if pid is not None else None,
                        num_partitions=nparts if pid is not None else 0,
                        start_time=st.get("start_time"),
                        end_time=st.get("end_time"),
                        version=st.get("crc", 0) or 0)
                rt = RoutingTable()
                if cfg.table_type.value == "REALTIME":
                    rt.realtime = route
                else:
                    rt.offline = route
                self.routing.set_route(logical, rt)


def run_broker(coordinator: str, http_port: int = 0, config=None,
               ready_event: Optional[threading.Event] = None,
               stop_event: Optional[threading.Event] = None) -> None:
    from pinot_tpu.utils.config import PinotConfiguration
    cfg = config or PinotConfiguration()
    role = BrokerRole(coordinator,
                      http_port=http_port
                      or cfg.get_int("pinot.broker.http.port"),
                      config=cfg)
    role.start()
    print(f"broker http on 127.0.0.1:{role.http.port}", flush=True)
    if ready_event is not None:
        ready_event.set()
    stop = stop_event or threading.Event()
    try:
        while not stop.wait(2.0):
            try:
                # liveness for the health sweep: a broker that stops
                # heartbeating reads "stale" in /cluster/health
                role.client.request("heartbeat",
                                    instance_id=role.instance_id)
            except (ConnectionError, OSError, RuntimeError):
                pass
    finally:
        role.stop()
