"""BlockStager: the one data path host row -> resident row -> assembled
[S, W] device block, and the cache tiers along it.

The engine's legs (ops/engine.py) say WHAT to stage; where a row lives,
on which chip, and what is evicted is decided here and nowhere else.
Three tiers, each budgeted:

  * HOST, per (segment, row): the padded numpy row (its own pow2
    bucket) — rebuilding any batch skips segment re-read/re-decode.
  * RESIDENT, per (segment, row): the same row in device HBM
    (ops/residency.py) — a changed batch (pruning picked a different
    subset, a new segment sealed) uploads ONLY rows the device has never
    seen. With a resident budget of 0 the tier retains nothing and every
    miss uploads row by row through the same path.
  * ASSEMBLED, per (batch, row family): the [S, W] block the kernel
    consumes, built ON-DEVICE from resident rows
    (kernels.compiled_row_assembler) — steady state is zero transfers
    and zero assembly.

Entries at every level hold strong segment references and verify
identity on hit, so a refreshed segment (same name, new object) can
never serve stale data — id() is not recycled while an entry pins the
old object, and a new object misses.

Beside the tiers sits the predicate-parameter cache: its entries key on
the batch, and die with the batch's last assembled block. An entry's
packed [K, S] parameters are a host (numpy) array: they reach the
device as an argument of the launch, never through a put.

This module knows no query shape and imports nothing from ops/engine.py.
Every `*_locked` method runs under `BlockStager.lock`, and the lock
guards nothing but these methods' state: the engine takes it round one
query's block look-ups and parameter-cache probe, and again, briefly,
to insert what a probe's miss built outside it.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from pinot_tpu.ops import dispatch as dispatch_mod
from pinot_tpu.ops import kernels
from pinot_tpu.ops import residency as residency_mod
from pinot_tpu.utils.config import PinotConfiguration

#: one segment's row of a block: (residency kind, residency name,
#: padded row length, fetch(segment) -> the unpadded numpy row)
RowSpec = Tuple[str, str, int, Callable[[Any], np.ndarray]]


def batch_id(segments) -> tuple:
    """Identity of a segment batch: id() alone can be reused after GC, so
    pair it with the segment name."""
    return tuple((id(s), s.name) for s in segments)


def _entry_nbytes(a) -> int:
    """Bytes of a host-tier payload (array, or (codes, table))."""
    if isinstance(a, tuple):
        return sum(x.nbytes for x in a)
    return a.nbytes


def _segment_shards(mesh) -> List[list]:
    """[segments-shard][its devices] of a mesh, in mesh order (one
    shard of every device where the mesh has no segments axis)."""
    devs = mesh.devices
    if "segments" not in mesh.axis_names:
        return [list(devs.flat)]
    devs = np.moveaxis(devs, mesh.axis_names.index("segments"), 0)
    return [list(d) for d in devs.reshape(devs.shape[0], -1)]


class BlockStager:
    #: LRU capacity of the predicate-parameter cache (entries are tiny)
    PARAMS_CACHE_ENTRIES = 4096

    #: residency miss bursts at/above this many bytes upload in parallel
    #: on the upload pool (below it, thread handoff costs more than the
    #: copies themselves)
    UPLOAD_FANOUT_BYTES = 16 << 20

    def __init__(self, devices: Sequence, mesh=None, config=None,
                 metrics=None, labels=None):
        """devices/mesh: the engine's (a mesh shards every block over its
        `segments` axis and, where it has one, `docs`). config: the
        PinotConfiguration the budgets are read from (None reads
        env/defaults). metrics/labels: the registry the tier meters and
        gauges go to."""
        self.devices = list(devices)
        self._mesh = mesh
        self._doc_axis = 1 if mesh is None else dict(
            zip(mesh.axis_names, mesh.devices.shape)).get("docs", 1)
        #: [segments-shard][its devices], in mesh order: segment slot i
        #: of an [S, ...] block lives on shard i // (S / shards), and a
        #: resident row is put on that shard's first device
        self._shards = _segment_shards(mesh) if mesh is not None else []
        self._metrics = metrics
        self._labels = labels
        #: cache mutation serializes under this lock; kernel dispatch and
        #: result fetch run OUTSIDE it so concurrent queries overlap
        #: their device round trips. Eviction drops cache references
        #: WITHOUT .delete(): the staging query itself and any
        #: concurrently dispatched kernels hold the block as an input,
        #: and JAX refcounting frees the HBM as soon as the last consumer
        #: finishes — an eager delete could invalidate a buffer
        #: mid-flight, and a deferred-until-quiescent delete list would
        #: pin evicted blocks forever under sustained pipelined load
        self.lock = threading.RLock()
        #: bytes of resident rows copied chip to chip at block assembly
        #: (a row found on another chip than its slab's) since start-up
        self.cross_chip_bytes = 0
        #: bytes of rows uploaded host->device by block misses since
        #: start-up; it moves under the lock only, so a holder's diff of
        #: it is that holder's own
        self.uploaded_bytes = 0
        #: ASSEMBLED device blocks, LRU-evicted under a byte budget: the
        #: exact [S, W] arrays kernels consume, keyed by the segment
        #: batch identity (id+name pairs guard against id() reuse)
        self._block_cache: "OrderedDict[tuple, Any]" = OrderedDict()
        self._block_bytes: Dict[tuple, int] = {}
        self._cache_bytes = 0
        #: live block count per batch identity — O(1) detection of "this
        #: batch's LAST block just left", which triggers the params purge
        self._batch_blocks: Dict[tuple, int] = {}
        #: host-side payloads per (segment, row): LRU-evicted under its
        #: own byte budget (entries pin their segment, so eviction also
        #: releases replaced segments)
        self._host_rows: "OrderedDict[tuple, Any]" = OrderedDict()
        self._host_bytes = 0
        #: resolved predicate parameter arrays per (batch, plan, filter)
        #: — repeat queries then cost zero host->device param uploads;
        #: bounded LRU (hot filter parameters survive cache pressure
        #: instead of a wholesale clear dropping them all at once)
        self._params_cache: "OrderedDict[tuple, Any]" = OrderedDict()
        cfg = config or PinotConfiguration()
        # legacy short env names still win for compatibility. The host
        # row cache is this process's memory, one budget a server; the
        # two HBM knobs are bytes PER CHIP, and the pools are the knob
        # times the chips held
        chips = max(len(self.devices), 1)
        self.host_budget_bytes = int(os.environ.get(
            "PINOT_TPU_HOST_ROW_CACHE_BYTES",
            cfg.get_int("pinot.server.host.row.cache.bytes")))
        self.cache_budget_bytes = chips * int(os.environ.get(
            "PINOT_TPU_HBM_CACHE_BYTES",
            cfg.get_int("pinot.server.hbm.cache.bytes")))
        self.residency = residency_mod.ResidencyManager(
            chips * int(os.environ.get(
                "PINOT_TPU_HBM_RESIDENT_BYTES",
                cfg.get_int("pinot.server.hbm.resident.bytes"))),
            admission=cfg.get_bool("pinot.server.hbm.admission.enabled",
                                   True),
            sample_window=cfg.get_int("pinot.server.hbm.admission.sample"),
            metrics=metrics, labels=labels, devices=self.devices)

    def _meter(self, name: str, value: float = 1) -> None:
        if self._metrics is not None:
            self._metrics.add_meter(name, value, labels=self._labels)

    # ------------------------------------------------------------------
    # the one staging path
    # ------------------------------------------------------------------
    def stage_block_locked(self, segments, S: int, W: int, kind: str, name,
                           dtype, row_of: Callable[[int, Any], RowSpec],
                           host_cache: bool = True, stamps=None):
        """The [S, W] device block of one row family over `segments`
        (fewer than S leave zero slots at the end). kind + name key the
        block; `row_of(i, segment)` names segment i's row (RowSpec) and
        is asked only on a miss. A hit is one look-up, the identity
        check and the LRU touch. On a miss, rows the resident tier
        lacks are built on the host first — a fetcher's refusal must
        surface BEFORE any upload, a doomed plan should not churn the
        resident tier — then uploaded one by one, each to the chip that
        owns its slot, offered for retention, and the block is stacked
        on-device.

        host_cache=False: the host tier keeps no padded copy (the
        caller already holds the payload there). stamps: per-segment
        version stamps of a row family whose source mutates in place
        (row kinds `<kind>:<stamp>`); they take the dtype's place in
        the block key, and a miss purges what superseded stamps left in
        every tier."""
        dtype_str = np.dtype(dtype).str
        bkey = (batch_id(segments), kind, name, S, W,
                dtype_str if stamps is None else stamps)
        entry = self._block_cache.get(bkey)
        if entry is not None and all(a is b
                                     for a, b in zip(entry[0], segments)):
            self._block_cache.move_to_end(bkey)
            self._meter("hbm_block_hit")
            return entry[1]
        self._meter("hbm_block_miss")
        specs = [row_of(i, seg) for i, seg in enumerate(segments)]
        dev_rows = [self.residency.get(seg, rkind, rname, dtype_str)
                    for seg, (rkind, rname, _n, _f) in zip(segments, specs)]
        missing = [i for i, row in enumerate(dev_rows) if row is None]
        if stamps is not None:
            self._purge_superseded_locked(
                bkey, [(segments[i],) + specs[i][:2] for i in missing])
        host_rows = [self._host_row_locked(segments[i], *specs[i], dtype,
                                           host_cache) for i in missing]
        uploaded = self._upload_rows(host_rows, missing, S)
        self.uploaded_bytes += sum(a.nbytes for a in host_rows)
        for i, arr, dev in zip(missing, host_rows, uploaded):
            self.residency.admit(segments[i], specs[i][0], specs[i][1],
                                 dtype_str, dev, arr.nbytes,
                                 device=self._dev_label(dev))
            dev_rows[i] = dev
        block = self._assemble_rows_locked(dev_rows, S, W, dtype_str)
        self._insert_block_locked(bkey, (tuple(segments), block),
                                  S * W * np.dtype(dtype).itemsize)
        return block

    def _purge_superseded_locked(self, bkey, fresh_rows) -> None:
        """A stamped block missed: every future look-up carries the new
        stamps, so what the old ones left is unreachable and would
        squat in the budgets. Drops this batch's blocks of the kind
        under other stamps and, for every (segment, row kind, row name)
        about to be staged fresh, the segment's resident and host rows
        under superseded stamps."""
        batch, kind = bkey[0], bkey[1]
        for k in [k for k in self._block_cache
                  if k[0] == batch and k[1] == kind and k != bkey]:
            del self._block_cache[k]
            self._cache_bytes -= self._block_bytes.pop(k)
            self._drop_batch_block_locked(batch)
        prefix = kind + ":"
        for seg, rkind, rname in fresh_rows:
            self.residency.invalidate_superseded_kind(
                seg, prefix, rkind, rname)
            for hk in [k for k, v in self._host_rows.items()
                       if k[0] == id(seg) and v[0] is seg
                       and k[1].startswith(prefix) and k[1] != rkind]:
                _s, payload = self._host_rows.pop(hk)
                self._host_bytes -= _entry_nbytes(payload)

    # ------------------------------------------------------------------
    # host tier
    # ------------------------------------------------------------------
    def host_get_locked(self, rkey: tuple, seg):
        """The host tier's payload under `rkey` (its first element
        id(seg)), or None."""
        entry = self._host_rows.get(rkey)
        if entry is not None and entry[0] is seg:
            self._host_rows.move_to_end(rkey)
            return entry[1]
        return None

    def host_put_locked(self, rkey: tuple, seg, payload) -> None:
        """Insert and trim to the host budget, coldest first."""
        self._host_rows[rkey] = (seg, payload)
        self._host_bytes += _entry_nbytes(payload)
        while self._host_bytes > self.host_budget_bytes \
                and len(self._host_rows) > 1:
            _k, (_s, old) = self._host_rows.popitem(last=False)
            self._host_bytes -= _entry_nbytes(old)
            self._meter("host_row_evicted")
        self._refresh_tier_gauges_locked()

    def _host_row_locked(self, seg, rkind: str, rname: str, row_len: int,
                         fetch, dtype, cache: bool) -> np.ndarray:
        """Padded numpy row for one (segment, row): a length of the
        segment's own (batch-independent, so every batch composition
        shares it), via the host tier."""
        rkey = (id(seg), rkind, rname, row_len, np.dtype(dtype).str)
        arr = self.host_get_locked(rkey, seg)
        if arr is not None:
            self._meter("host_row_hit")
            return arr
        self._meter("host_row_miss")
        raw = fetch(seg)
        arr = np.zeros(row_len, dtype=dtype)
        arr[:len(raw)] = raw
        if cache:
            self.host_put_locked(rkey, seg, arr)
        return arr

    # ------------------------------------------------------------------
    # placement, upload, on-device assembly
    # ------------------------------------------------------------------
    def _slot_device(self, slot: int, S: int):
        """The device that owns segment slot `slot` of an [S, ...] block
        (S a multiple of the segments axis): the first device of the
        slot's segments-shard. None without a mesh."""
        if self._mesh is None:
            return None
        return self._shards[slot // (S // len(self._shards))][0]

    def _upload_rows(self, host_rows, slots, S: int) -> list:
        """One `_put_row` a host row, each to the device that owns its
        segment slot of an [S, ...] block."""
        targets = [self._slot_device(i, S) for i in slots]
        if len(host_rows) > 1 and sum(
                a.nbytes for a in host_rows) >= self.UPLOAD_FANOUT_BYTES:
            # double-buffer big bursts: row N+1's transfer overlaps
            # row N's (and, under execute_async, the previous
            # query's kernel). Small rows stay inline — thread
            # handoff costs more than the copy
            futs = [dispatch_mod.upload_pool().submit(self._put_row, a, d)
                    for a, d in zip(host_rows, targets)]
            # pool-executed device_puts always complete; the cap
            # bounds a wedged-device-link hang (no query deadline
            # here — staging also runs under warmup/prestage)
            return [dispatch_mod.wait_result(
                f, max_wait_s=dispatch_mod.DEFAULT_WAIT_CAP_S)
                for f in futs]
        return [self._put_row(a, d) for a, d in zip(host_rows, targets)]

    def _put_row(self, arr: np.ndarray, device=None):
        """Upload ONE residency row to `device`, the chip that owns its
        segment slot (`_slot_device`): a segment's rows live where its
        shard of every block lives, so blocks assemble per shard with
        no chip-to-chip copy and the per-chip budgets
        (ops/residency.py) fill evenly. None (no mesh): the default
        device. Runs on upload-pool threads for multi-row bursts and
        touches no stager state."""
        residency_mod.note_transfer(arr.nbytes, column=True)
        self._meter("hbm_transfer_bytes", arr.nbytes)
        if device is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, device)

    @staticmethod
    def _dev_label(arr) -> str:
        """`platform:id` label of the device holding a committed row —
        the key the per-chip residency ledger and `device=` gauges use."""
        d = next(iter(arr.devices()))
        return f"{d.platform}:{d.id}"

    def _assemble_rows_locked(self, dev_rows, S: int, W: int,
                              dtype_str: str):
        """The kernel-ready [S, W] block from one resident row a segment
        slot (fewer rows than S leave zero slots at the end), stacked
        on-device (kernels.compiled_row_assembler). On a mesh every
        segments-shard's [S / shards, W] slab is stacked on the device
        that owns it (`_slot_device`), from the rows `_put_row` already
        placed there, and the global array is made from the slabs: no
        device holds more than its shard of the block beside its own
        resident rows. A row found on another chip (a batch recomposed
        after pruning) is copied chip to chip to its slab's device
        only, never over the host link, and metered as
        `hbm_cross_chip_bytes`. On a (segments, docs) mesh the slab is
        then split over `docs` among its own shard's devices."""
        if self._mesh is None:
            assembler = kernels.compiled_row_assembler(
                S, W, tuple(int(r.shape[0]) for r in dev_rows), dtype_str)
            return assembler(tuple(dev_rows))
        sharding = NamedSharding(
            self._mesh, P("segments", "docs") if self._doc_axis > 1
            else P("segments", None))
        # which docs of its slab each device of a shard holds
        index = sharding.devices_indices_map((S, W)) \
            if len(self._shards[0]) > 1 else None
        per = S // len(self._shards)
        pieces = []
        for j, shard in enumerate(self._shards):
            home = shard[0]
            rows = []
            for r in dev_rows[j * per:(j + 1) * per]:
                if home not in r.devices():
                    self.cross_chip_bytes += r.nbytes
                    self._meter("hbm_cross_chip_bytes", r.nbytes)
                    r = jax.device_put(r, home)
                rows.append(r)
            assembler = kernels.compiled_row_assembler(
                per, W, tuple(int(r.shape[0]) for r in rows), dtype_str)
            if rows:
                slab = assembler(tuple(rows))
            else:  # a slab of padding only: no input says where it lives
                with jax.default_device(home):
                    slab = assembler(())
            pieces += [slab if len(shard) == 1
                       else jax.device_put(slab[:, index[d][1]], d)
                       for d in shard]
        return jax.make_array_from_single_device_arrays(
            (S, W), sharding, pieces)

    # ------------------------------------------------------------------
    # assembled tier
    # ------------------------------------------------------------------
    def _insert_block_locked(self, key, entry, nbytes: int) -> None:
        if key not in self._block_cache:
            self._batch_blocks[key[0]] = \
                self._batch_blocks.get(key[0], 0) + 1
        else:
            self._cache_bytes -= self._block_bytes[key]
        self._block_cache[key] = entry
        self._block_bytes[key] = nbytes
        self._cache_bytes += nbytes
        while self._cache_bytes > self.cache_budget_bytes \
                and len(self._block_cache) > 1:
            # drop the reference only — the current query and concurrent
            # dispatches hold evicted blocks as kernel inputs; refcounting
            # frees the HBM when the last consumer finishes
            old_key, _entry = self._block_cache.popitem(last=False)
            self._cache_bytes -= self._block_bytes.pop(old_key)
            self._meter("hbm_evicted")
            self._drop_batch_block_locked(old_key[0])
        self._refresh_tier_gauges_locked()

    def _drop_batch_block_locked(self, batch: tuple) -> None:
        """One block of `batch` left the cache; when it was the LAST,
        the batch's predicate params can never pair with a live block
        again — drop them now instead of stranding them until global
        LRU pressure (params key on (batch, plan, filter)). The
        refcount keeps the common case O(1); the bounded params scan
        runs once per batch death, not per eviction."""
        n = self._batch_blocks.get(batch, 1) - 1
        if n > 0:
            self._batch_blocks[batch] = n
            return
        self._batch_blocks.pop(batch, None)
        for pk in [k for k in self._params_cache if k[0] == batch]:
            del self._params_cache[pk]

    # ------------------------------------------------------------------
    # parameter cache
    # ------------------------------------------------------------------
    def params_get_locked(self, pkey: tuple, segments):
        """The entry `(segments, ...)` cached under `pkey` (its first
        element the batch identity) if it was built over these very
        segment objects, refreshed in the LRU; else None."""
        entry = self._params_cache.get(pkey)
        if entry is not None and all(a is b
                                     for a, b in zip(entry[0], segments)):
            self._params_cache.move_to_end(pkey)
            return entry
        return None

    def params_put_locked(self, pkey: tuple, entry: tuple) -> None:
        self._params_cache[pkey] = entry
        self._params_cache.move_to_end(pkey)
        while len(self._params_cache) > self.PARAMS_CACHE_ENTRIES:
            self._params_cache.popitem(last=False)  # evict coldest only

    # ------------------------------------------------------------------
    # gauges, invalidation
    # ------------------------------------------------------------------
    def _refresh_tier_gauges_locked(self) -> None:
        if self._metrics is None:
            return
        self._metrics.set_gauge(
            "hbm_cache_bytes", self._cache_bytes + self.residency.bytes,
            labels=self._labels)
        self._metrics.set_gauge("host_row_cache_bytes", self._host_bytes,
                                labels=self._labels)
        if len(self.devices) > 1:
            # per-chip split: assembled blocks are sharded evenly over
            # the mesh (equal per-chip share of _cache_bytes); resident
            # rows are committed whole to one chip each, so their bytes
            # attribute exactly (the skew admission control watches)
            by_dev = self.residency.bytes_by_device()
            share = self._cache_bytes // len(self.devices)
            for d in self.devices:
                lab = f"{d.platform}:{d.id}"
                labels = dict(self._labels or {})
                labels["device"] = lab
                self._metrics.set_gauge(
                    "hbm_cache_bytes", share + by_dev.get(lab, 0),
                    labels=labels)
                self._metrics.set_gauge(
                    "hbm_resident_bytes", by_dev.get(lab, 0),
                    labels=labels)

    def invalidate_segment(self, name: str, keep=None) -> None:
        """Drop every cached artifact for a replaced/removed segment
        NAME — resident rows, assembled blocks, host rows, predicate
        params — sparing entries pinned to `keep` (the just-warmed live
        object). Identity keying already makes stale entries
        unreachable; this reclaims their HBM/host bytes promptly, on the
        same epoch-moving events the result caches invalidate on."""
        def stale(seg) -> bool:
            return seg.name == name and (keep is None or seg is not keep)

        with self.lock:
            for k in [k for k, (segs, _d) in self._block_cache.items()
                      if any(map(stale, segs))]:
                del self._block_cache[k]
                self._cache_bytes -= self._block_bytes.pop(k)
                self._drop_batch_block_locked(k[0])
            for k in [k for k, v in self._host_rows.items() if stale(v[0])]:
                _s, payload = self._host_rows.pop(k)
                self._host_bytes -= _entry_nbytes(payload)
            for k in [k for k, v in self._params_cache.items()
                      if any(map(stale, v[0]))]:
                del self._params_cache[k]
            self.residency.invalidate_segment(name, keep=keep)
            self._refresh_tier_gauges_locked()

    def drop_caches(self, host: bool = True) -> None:
        """Bench/test hook: release the device tier (assembled blocks +
        resident rows + params); host=True also drops host rows — the
        fully cold replica state."""
        with self.lock:
            self._block_cache.clear()
            self._block_bytes.clear()
            self._batch_blocks.clear()
            self._cache_bytes = 0
            self._params_cache.clear()
            self.residency.drop_all()
            if host:
                self._host_rows.clear()
                self._host_bytes = 0
            self._refresh_tier_gauges_locked()
