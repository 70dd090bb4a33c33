"""A named memory collection — one tenant's IVF state, id-space, counters.

This is the per-tenant unit the multi-tenant `MemoryService` schedules over:
each collection owns its own `IVFState`, its own external-id allocator, its
own op counters, and its own template thresholds.  Methods here are the raw
synchronous kernels; the service wraps them in scheduler-routed futures.

Concurrency model (lost-update-safe writes, wait-free reads):

* Queries never block on writers.  They read `self.state` — an atomically
  swapped snapshot — under `_lock`, a tiny critical section that only ever
  guards pointer reads/swaps and host counters, never device compute.
* Writers (build / insert / delete / rebuild-swap) serialize on a dedicated
  `_writer_lock`.  Insert/delete run their device compute while holding
  *only* the writer lock, then swap the fresh state in under `_lock`; the
  query path is never stalled behind an insert's GEMM.
* `rebuild()` is delta-replay based: it snapshots the state, recomputes
  off-lock while concurrent writers append their ops to a bounded delta
  log, then re-acquires the writer lock, replays the log onto the rebuilt
  state (`ivf.replay`, donating kernels — in-place on device), and swaps.
  No write that lands during a rebuild is ever lost.  If the log overflows,
  the rebuild restarts from a fresh snapshot; the final attempt runs with
  the writer lock held (writers briefly blocked, queries still served).
  A bulk `build()` bumps `_epoch`, so a rebuild racing it detects that its
  snapshot is obsolete and aborts instead of resurrecting dead state.
* Every swap bumps `_version`; `version()` lets callers assert freshness.

Sharded collections (``shard_db=True`` + a mesh) run the same lifecycle
with *per-shard* maintenance state: the delta log, tombstone/spill pressure
counters, spill floor, and version counter are all tracked per shard, and
`rebuild(shard=i)` compacts shard ``i`` alone — sibling shards' arrays and
versions are untouched, so one hot shard's maintenance never stalls the
rest (see `repro.core.distributed` and docs/ARCHITECTURE.md).  The
unsharded collection is simply the 1-shard special case of the same
machinery.

Persistence: `save_into` / `load_from` write one namespace directory per
collection (Checkpointer step dirs + `collection.json`), and the metadata
write is atomic (temp file + `os.replace`) so a crash mid-write can never
corrupt a restore.  Sharded collections write one `shard_<i>` namespace per
shard plus the mesh shape in the metadata; loading checks the mesh shape
and can re-pack host-side onto a different mesh (``reshard=True``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EngineConfig
from repro.core import index as ivf
from repro.core import locking
from repro.core import metrics
from repro.core import templates
from repro.core import tracing

META_FILE = "collection.json"


def atomic_write_json(path: str, payload: dict) -> None:
    """Crash-safe metadata write: temp file in the same dir + os.replace."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class Collection:
    def __init__(self, name: str, cfg: EngineConfig, *, seed: int = 0,
                 spill_capacity: int = 4096,
                 thresholds: Optional[templates.TemplateThresholds] = None,
                 delta_log_capacity: int = 1024,
                 mesh=None, _alloc_state: bool = True):
        self.name = name
        self.cfg = cfg
        self.mesh = mesh
        if cfg.shard_db and mesh is None:
            raise ValueError(f"collection {name!r}: shard_db=True needs a mesh")
        self.key = jax.random.PRNGKey(seed)
        self.spill_capacity = spill_capacity
        self.delta_log_capacity = delta_log_capacity
        self.thresholds = thresholds or templates.TemplateThresholds.from_profile(cfg)
        self._built = False
        # _lock: snapshot swap + counters + id allocator (tiny sections only)
        self._lock = locking.make_rlock("_lock")
        # _writer_lock: serializes mutators; the query path never takes it
        self._writer_lock = locking.make_rlock("_writer_lock")
        self._version = 0          # bumped on every state swap
        self._epoch = 0            # bumped on bulk build (obsoletes snapshots)
        self._next_id = 0
        # rebuild_restarts: delta-log overflows that started a rebuild over;
        # rebuild_exclusive: attempts that held the writer lock through the
        # recompute (writes stalled); replayed_ops/_rows: writes replayed
        # onto rebuilt states
        self.counters = {"queries": 0, "inserts": 0, "deletes": 0,
                         "rebuilds": 0, "spilled": 0, "rebuild_restarts": 0,
                         "rebuild_exclusive": 0, "rebuild_aborts": 0,
                         "replayed_ops": 0, "replayed_rows": 0}
        # Per-shard maintenance state; the unsharded collection is the
        # 1-shard special case.  Shard i's entries are only ever touched by
        # ops that land on shard i, so the MaintenanceController can
        # schedule shard-local rebuilds independently:
        #   _rebuild_locks   at most one delta-replay rebuild per shard
        #   _delta_logs      write log while shard i's rebuild recomputes
        #   _shard_versions  bumped when shard i's slice changes
        #   _shard_pressure  host-side tombstone/spill counters since the
        #                    last (re)build of shard i — what the service's
        #                    MaintenanceController polls (no device sync)
        #   _spill_floors    residual spill the last rebuild of shard i
        #                    could not drain (e.g. a hot cluster larger than
        #                    its list): pressure below the floor is
        #                    irreducible, so maintenance_due ignores it
        #                    instead of re-triggering a futile rebuild
        n_shards = mesh.size if (cfg.shard_db and mesh is not None) else 1
        self._n_shards = n_shards
        self._rebuild_locks = [locking.make_lock("_rebuild_locks")
                               for _ in range(n_shards)]
        self._delta_logs: List[Optional[List[ivf.DeltaOp]]] = [None] * n_shards
        self._delta_overflow = [False] * n_shards
        self._shard_versions = [0] * n_shards
        self._shard_pressure = [{"tombstones": 0, "spilled": 0}
                                for _ in range(n_shards)]
        self._spill_floors = [0] * n_shards
        # Residency tier (see repro.api.residency): "hot" = device state in
        # _state; "warm" = host numpy state(s) in _host_state (per-shard
        # local states when sharded); "cold" = checkpoint under _cold_dir
        # only.  Transitions go through demote()/promote() under the writer
        # lock; _index_nbytes is the exact static byte size of the device
        # state (what the budget charges), computed without allocation.
        self._residency_tier = "hot"
        self._host_state = None
        self._cold_dir: Optional[str] = None
        self._cold_step: Optional[int] = None
        self._residency_mgr = None     # back-ref set by ResidencyManager
        self._last_used = time.monotonic()
        self._index_nbytes = ivf.state_nbytes(cfg, spill_capacity, n_shards)
        # Recall-adaptive routing (docs/ARCHITECTURE.md): the HNSW graph is
        # a DERIVED host-side accelerator for the "hnsw" index policy — the
        # IVF row store above stays the single source of truth for
        # durability, delta replay, residency, and save/load.  The graph is
        # (re)built lazily from the live rows (`_ensure_graph`),
        # incrementally mirrored by writers under the writer lock
        # (`_graph_apply`), and invalidated whenever a bulk operation
        # republishes the store wholesale (build / rebuild / demote).
        # `_graph_lock` is a leaf: only ever wraps pure graph work, never
        # nests another lock inside it.
        self._graph = None
        self._graph_lock = locking.make_lock("_lock")
        # Replication shipping hook (repro.api.replication): when set, every
        # acked write (build/insert/delete) is reported — host-side rows/ids
        # — from inside the writer critical section, AFTER its state swap,
        # so hook call order == publication order and an op is shipped iff
        # it was acked.  The hook must only descend to _ship_lock (15).
        self._ship_hook = None
        self._approx_live = 0          # host-side live-row estimate (routing)
        self._probe_ops = 0            # ops since the last recall probe
        self._probe_seq = 0            # deterministic probe RNG stream
        self._last_probe: Optional[dict] = None
        # target_recall > 0 arms the probe + per-path knob tuners; the
        # sharded tier serves exact per-shard scans + hierarchical merge
        # (no effort knob), so its probes measure without retuning
        if cfg.target_recall > 0 and not self.sharded:
            from repro.core.tuner import RecallTuner
            self._nprobe_tuner = RecallTuner(
                cfg.target_recall,
                max(1, min(cfg.nprobe, cfg.n_clusters)), 1, cfg.n_clusters)
            ef_lo = max(1, cfg.k)
            ef_hi = max(1024, 8 * max(cfg.hnsw_ef, cfg.k))
            self._ef_tuner = RecallTuner(
                cfg.target_recall,
                min(max(cfg.hnsw_ef, ef_lo), ef_hi), ef_lo, ef_hi)
        else:
            self._nprobe_tuner = None
            self._ef_tuner = None
        if not _alloc_state:
            # device-free init for load_from: the loader installs the
            # restored state (hot) or host/cold residency itself
            self._state = None
        elif self.sharded:
            from repro.core import distributed as dce
            self._state = dce.empty_dist_state(cfg, mesh, spill_capacity)
        else:
            self._state = ivf.empty_state(cfg, spill_capacity)

    @property
    def sharded(self) -> bool:
        return self.cfg.shard_db and self.mesh is not None

    @property
    def n_shards(self) -> int:
        """Mesh size for sharded collections, else 1."""
        return self._n_shards

    @property
    def _spill_floor(self) -> int:
        """Aggregate irreducible spill across shards (see `_spill_floors`)."""
        with self._lock:
            return sum(self._spill_floors)

    # ------------------------------------------------------------------
    # Residency tier (device / host-RAM / disk — see repro.api.residency)
    # ------------------------------------------------------------------
    @property
    def residency(self) -> str:
        """Current tier: "hot" | "warm" | "cold"."""
        with self._lock:
            return self._residency_tier

    def last_used(self) -> float:
        """monotonic() timestamp of the last query/write — the LRU key."""
        with self._lock:
            return self._last_used

    def index_nbytes(self) -> int:
        """Exact byte size of the device state (static shapes — constant
        for the collection's lifetime; equals the audited
        `ivf.footprint(state)["index_bytes"]`)."""
        return self._index_nbytes

    def _host_view_locked(self):
        """Host (numpy) representation of the current state; caller holds
        the writer lock.  Unsharded: one IVFState of numpy arrays.
        Sharded: the per-shard local states (`distributed.split_host`
        layout — the same representation sharded persistence writes)."""
        with self._lock:
            tier = self._residency_tier
            state = self._state
            host = self._host_state
        if tier == "hot":
            if self.sharded:
                from repro.core import distributed as dce
                return dce.split_host(state, self._n_shards)
            return jax.tree.map(
                lambda a: np.asarray(jax.device_get(a)), state)
        if tier == "warm":
            return host
        return self._read_cold_host()

    def _read_cold_host(self):
        """Load the COLD checkpoint back into host numpy arrays (no device
        allocation: `Checkpointer.restore` without shardings stays numpy)."""
        from repro.checkpoint.checkpointer import Checkpointer
        if self._cold_dir is None:
            raise RuntimeError(
                f"collection {self.name!r} is cold but has no checkpoint "
                "directory — demote(tier='cold') requires one")
        template = ivf.empty_host_state(self.cfg,
                                        self.spill_capacity)._asdict()
        if self.sharded:
            shards = []
            for i in range(self._n_shards):
                ck = Checkpointer(
                    os.path.join(self._cold_dir, f"shard_{i:03d}"))
                shards.append(ivf.IVFState(
                    **ck.restore(template, step=self._cold_step)))
            return shards
        ck = Checkpointer(self._cold_dir)
        return ivf.IVFState(**ck.restore(template, step=self._cold_step))

    def _write_host_state(self, directory: str, host, step: int) -> None:
        """Write a host view (from `_host_view_locked`) as checkpoint
        namespaces — one per shard when sharded, matching `save_into`."""
        from repro.checkpoint.checkpointer import Checkpointer
        os.makedirs(directory, exist_ok=True)
        if self.sharded:
            for i, local in enumerate(host):
                Checkpointer(os.path.join(
                    directory, f"shard_{i:03d}")).save(step, local._asdict())
        else:
            Checkpointer(directory).save(step, host._asdict())

    def demote(self, tier: str = "warm", *, directory: Optional[str] = None,
               step: int = 0) -> dict:
        """Release the device state: "warm" keeps a host-RAM copy, "cold"
        writes a disk checkpoint (`directory`, or the collection's existing
        cold namespace) and keeps nothing in memory.

        Serializes through the writer lock, so it can never tear an
        in-flight write; bumps `_epoch` so an in-flight delta-replay
        rebuild aborts (its snapshot no longer exists on device) instead of
        resurrecting the demoted state at its swap.  Queries racing the
        demotion either grabbed the old snapshot (still valid — the arrays
        outlive the swap) or re-promote on their next snapshot read.
        Demoting an already-colder collection is a no-op ("cold" →
        demote("warm") does NOT load anything back).
        """
        if tier not in ("warm", "cold"):
            raise ValueError(f"demote tier must be 'warm' or 'cold', "
                             f"got {tier!r}")
        t0 = time.perf_counter()
        with self._writer_lock:
            with self._lock:
                cur = self._residency_tier
            if cur == tier or cur == "cold":
                return {"tier": cur, "demoted": False}
            host = self._host_view_locked()
            if tier == "cold":
                directory = directory or self._cold_dir
                if directory is None:
                    raise ValueError(
                        f"collection {self.name!r}: demote to cold needs a "
                        "checkpoint directory (configure the service's "
                        "residency_dir)")
                self._write_host_state(directory, host, step)
            with self._lock:
                self._residency_tier = tier
                if tier == "warm":
                    self._host_state = host
                else:
                    self._host_state = None
                    self._cold_dir = directory
                    self._cold_step = step
                self._state = None
                self._version += 1
                self._epoch += 1    # obsoletes in-flight rebuild snapshots
                for s in range(self._n_shards):
                    self._shard_versions[s] += 1
            # the derived graph only serves the HOT tier; free it with the
            # device state (promote + next graph query rebuild it)
            self._graph_invalidate()
        out = {"tier": tier, "demoted": True,
               "demote_s": time.perf_counter() - t0}
        mgr = self._residency_mgr
        if mgr is not None:
            mgr._record_demotion(tier, out["demote_s"])
        return out

    def promote(self) -> dict:
        """Bring a WARM/COLD collection back to the device tier (HOT).

        Asks the residency manager (when attached) to make room FIRST —
        with no collection locks held, so the admission path's victim
        demotions can never deadlock against us — then rebuilds the device
        state under the writer lock.  No-op on a HOT collection.
        """
        with self._lock:
            if self._residency_tier == "hot":
                return {"tier": "hot", "promoted": False}
        mgr = self._residency_mgr
        if mgr is not None:
            mgr.make_room_for(self)
        t0 = time.perf_counter()
        try:
            with self._writer_lock:
                with self._lock:
                    tier = self._residency_tier
                    host = self._host_state
                if tier == "hot":     # raced another promoter — done
                    return {"tier": "hot", "promoted": False}
                if tier == "cold":
                    host = self._read_cold_host()
                if self.sharded:
                    from repro.core import distributed as dce
                    state = dce.assemble_host(host, self.mesh)
                else:
                    state = jax.tree.map(jnp.asarray, host)
                with self._lock:
                    self._state = state
                    self._residency_tier = "hot"
                    self._host_state = None
                    self._last_used = time.monotonic()
                    self._version += 1
                    for s in range(self._n_shards):
                        self._shard_versions[s] += 1
        finally:
            if mgr is not None:
                mgr.finish_admit(self)
        out = {"tier": "hot", "promoted": True,
               "promote_s": time.perf_counter() - t0}
        if mgr is not None:
            mgr._record_promotion(out["promote_s"])
        return out

    def _acquire_writer_hot(self) -> None:
        """Acquire the writer lock with the collection HOT.

        Promote happens BEFORE the lock acquisition (admission takes victim
        writer locks — taking ours first would invert the lock order); if a
        concurrent eviction demoted us between the promote and the acquire,
        release and retry.  Terminates because evictions only happen on
        other tenants' admissions, which are finite between our retries.
        """
        with tracing.span("ame.writer_wait"):
            while True:
                self.promote()
                self._writer_lock.acquire()
                with self._lock:
                    if self._residency_tier == "hot":
                        return
                self._writer_lock.release()

    @contextlib.contextmanager
    def _hot_writer(self):
        self._acquire_writer_hot()
        try:
            yield
        finally:
            self._writer_lock.release()

    def _query_state(self) -> ivf.IVFState:
        """Snapshot for the query path: wait-free on a HOT collection,
        promotes first otherwise (the cold-hit path).  Under adversarial
        eviction thrash, falls back to pinning hotness with the writer
        lock for the pointer read — bounded, and only ever on a collection
        that was demoted multiple times mid-query."""
        for _ in range(4):
            with self._lock:
                if self._residency_tier == "hot":
                    self._last_used = time.monotonic()
                    return self._state
            self.promote()
        with self._hot_writer():
            with self._lock:
                self._last_used = time.monotonic()
                return self._state

    # ------------------------------------------------------------------
    # Versioned state snapshot
    # ------------------------------------------------------------------
    @property
    def state(self) -> ivf.IVFState:
        with self._lock:
            return self._state

    @state.setter
    def state(self, value: ivf.IVFState) -> None:
        with self._lock:
            self._state = value
            self._residency_tier = "hot"
            self._host_state = None
            self._version += 1

    def snapshot(self) -> ivf.IVFState:
        """Wait-free versioned read of the current state pointer.

        This is also the cross-collection fusion layer's read contract
        (`repro.api.batch.execute_group`): unsharded snapshots stack
        host-side; a sharded snapshot stays device-committed in the
        `distributed.state_specs` layout, so the fused sharded dispatch can
        stack each device's shard-local block lane-wise inside `shard_map`
        without ever gathering the state to host.  A concurrent writer or
        rebuild swaps the pointer rather than mutating a published state,
        so whatever snapshot a fused dispatch grabbed stays internally
        consistent for the lifetime of that dispatch.
        """
        with self._lock:
            return self._state

    def version(self) -> int:
        with self._lock:
            return self._version

    def versioned_snapshot(self) -> Tuple[ivf.IVFState, int]:
        """(state, version) read atomically under the pointer lock.

        The fusion layer's stack cache (`repro.api.batch.StackCache`) tags
        a stacked G-state with the exact versions of the snapshots it was
        built from; reading both under one lock acquisition means a cache
        key can never pair a fresh version with a stale state (or vice
        versa), so a version-match is proof the cached stack is current.
        """
        with self._lock:
            return self._state, self._version

    def shard_versions(self) -> List[int]:
        """Per-shard version counters (length `n_shards`).

        A shard-local rebuild bumps only its own shard's entry; writes that
        touch every shard (build / insert / delete) bump all of them.  Lets
        tests and callers assert that maintenance of shard i left siblings'
        state untouched.
        """
        with self._lock:
            return list(self._shard_versions)

    def _swap(self, state: ivf.IVFState, shards: Optional[Tuple[int, ...]] = None,
              **counter_deltas) -> int:
        """Atomically publish a new state; returns the new version.

        `shards` limits which per-shard version counters bump (None = all —
        correct for whole-state writes like build/insert/delete)."""
        with self._lock:
            self._state = state
            self._residency_tier = "hot"
            self._host_state = None
            self._last_used = time.monotonic()
            self._version += 1
            for s in (range(self._n_shards) if shards is None else shards):
                self._shard_versions[s] += 1
            for key, d in counter_deltas.items():
                self.counters[key] += d
                self._probe_ops += d    # recall-probe cadence counter
            return self._version

    # ------------------------------------------------------------------
    def _split(self):
        with self._lock:
            self.key, sub = jax.random.split(self.key)
        return sub

    def _ids_for(self, n: int, ids) -> jax.Array:
        with self._lock:
            if ids is None:
                ids = np.arange(self._next_id, self._next_id + n,
                                dtype=np.int32)
                self._next_id += n
            else:
                ids = np.asarray(ids, np.int32)
                self._next_id = max(self._next_id, int(ids.max()) + 1)
        return jnp.asarray(ids)

    def _bump(self, **deltas) -> None:
        with self._lock:
            self._last_used = time.monotonic()
            for key, d in deltas.items():
                self.counters[key] += d
                self._probe_ops += d    # recall-probe cadence counter

    def _count(self, **deltas) -> None:
        """Bump counters of maintenance events, which the recall probe's
        cadence (ops served) does not count."""
        with self._lock:
            for key, d in deltas.items():
                self.counters[key] += d

    def _log_delta(self, kind: str, rows, ids) -> None:
        """Record a write for every shard with an in-flight rebuild.  Caller
        holds `_writer_lock`, so log order == state application order.

        Inserts are logged as the *shard-local* row slice: `dist_insert`
        routes batch rows block-wise over the mesh (shard s gets rows
        [s*B/S, (s+1)*B/S)), so replay onto a rebuilt shard re-applies
        exactly the rows that landed there.  Deletes are logged whole —
        replay tombstones whatever of the id list lives on the shard.

        The row slicing happens OUTSIDE `_lock`: queries contend on that
        pointer lock, and dispatching device slices under it would tax
        query latency exactly while a rebuild is in flight.  Safe because
        the writer lock (held by our caller) is what installs/retires the
        per-shard logs — the active set cannot change mid-call.
        """
        with self._lock:
            active = [s for s, log in enumerate(self._delta_logs)
                      if log is not None]
        if not active:
            return
        entries = {}
        for s in active:
            if kind == "insert" and self._n_shards > 1:
                b = rows.shape[0] // self._n_shards
                entries[s] = ivf.DeltaOp("insert", rows[s * b:(s + 1) * b],
                                         ids[s * b:(s + 1) * b])
            else:
                entries[s] = ivf.DeltaOp(kind, rows, ids)
        with self._lock:
            for s, op in entries.items():
                log = self._delta_logs[s]
                if log is None:
                    continue
                if len(log) >= self.delta_log_capacity:
                    self._delta_overflow[s] = True
                else:
                    log.append(op)

    # ------------------------------------------------------------------
    # Replication shipping (repro.api.replication)
    # ------------------------------------------------------------------
    def set_ship_hook(self, hook) -> None:
        """Install/remove (`None`) the replication shipping hook.

        `hook(kind, rows, ids)` is called with host numpy arrays from
        inside the writer critical section after each acked write's state
        swap; it must be fast and may only take locks below the writer
        level (the shipping log's `_ship_lock`, 15).  Prefer
        `attach_shipper` when a consistent bootstrap snapshot is needed.
        """
        with self._lock:
            self._ship_hook = hook

    def attach_shipper(self, hook) -> dict:
        """Install `hook` and return a consistent bootstrap snapshot.

        Runs under the writer lock, so no write can land between the
        snapshot read and the hook install: every write is either in the
        returned snapshot or will be reported through the hook — the
        replication tier's no-lost-acked-writes guarantee starts here.
        Returns ``{"built", "rows", "ids", "key", "next_id"}``; rows/ids
        are the flat slot arrays (ids < 0 = dead slots) when built, else
        None.  Sharded collections don't ship (the per-shard delta log
        already replicates across the mesh); ValueError.
        """
        if self.sharded:
            raise ValueError(
                f"collection {self.name!r} is mesh-sharded; replication "
                "shipping supports unsharded collections only")
        with self._hot_writer():
            with self._lock:
                self._ship_hook = hook
                built = self._built
                state = self._state
                key = self.key
                next_id = self._next_id
            rows = ids = None
            if built:
                rows, ids = ivf.flat_rows_host(state)
        return {"built": built, "rows": rows, "ids": ids, "key": key,
                "next_id": next_id}

    def _ship(self, kind: str, rows, ids) -> None:
        """Report one acked write to the shipping hook (no-op when unset).
        Caller holds `_writer_lock`; rows/ids are device-gettable."""
        with self._lock:
            hook = self._ship_hook
        if hook is None:
            return
        rows_np = None if rows is None else np.asarray(
            jax.device_get(rows), np.float32)
        ids_np = np.asarray(jax.device_get(ids), np.int32)
        hook(kind, rows_np, ids_np)

    def apply_delta_batch(self, ops: Sequence[ivf.DeltaOp]) -> dict:
        """Apply a shipped delta batch in order with ONE state swap.

        The replica-side apply path: the first op runs through the shared
        (copying) kernel — concurrent readers may hold the published
        snapshot, so it must not be donated — which yields a sole-owned
        intermediate state; the remaining ops replay onto it with the
        donating `ivf.replay` helpers (no per-op copies), and the result
        publishes atomically.  A crash mid-batch therefore leaves the
        previously published state intact: batches are all-or-nothing,
        which is what lets the replication watermark advance only on
        entry boundaries.  Never calls the shipping hook — applying
        shipped writes on a replica must not re-ship them.

        Returns ``{"applied", "inserted", "spilled", "tombstoned"}``.
        """
        if self.sharded:
            raise ValueError(
                f"collection {self.name!r} is mesh-sharded; apply_delta_batch "
                "supports unsharded replicas only")
        if not ops:
            return {"applied": 0, "inserted": 0, "spilled": 0,
                    "tombstoned": 0}
        assert self._built, \
            f"build() collection {self.name!r} before applying deltas"
        max_id = -1
        for op in ops:
            if op.kind == "insert":
                max_id = max(max_id, int(np.max(np.asarray(op.ids))))
        with self._hot_writer():
            first, rest = ops[0], list(ops[1:])
            spilled = tombstoned = inserted = 0
            if first.kind == "insert":
                state, sp = ivf.insert_shared(
                    self._state, jnp.asarray(first.rows, jnp.float32),
                    jnp.asarray(first.ids, jnp.int32), self.cfg)
                spilled += int(sp)
                inserted += int(np.asarray(first.ids).shape[0])
            else:
                state, n_hit = ivf.delete_shared(
                    self._state, jnp.asarray(first.ids, jnp.int32))
                tombstoned += int(n_hit)
            if rest:
                rest = [ivf.DeltaOp(
                    op.kind,
                    None if op.rows is None else jnp.asarray(op.rows,
                                                             jnp.float32),
                    jnp.asarray(op.ids, jnp.int32)) for op in rest]
                state, sp, tomb = ivf.replay(state, rest, self.cfg)
                spilled += int(sp)
                tombstoned += int(tomb)
                inserted += sum(int(np.asarray(op.ids).shape[0])
                                for op in rest if op.kind == "insert")
            jax.block_until_ready(state.lists)
            with self._lock:
                self._shard_pressure[0]["spilled"] += spilled
                self._shard_pressure[0]["tombstones"] += tombstoned
                self._approx_live = max(
                    0, self._approx_live + inserted - tombstoned)
                self._next_id = max(self._next_id, max_id + 1)
            self._swap(state, inserts=inserted, deletes=tombstoned,
                       spilled=spilled)
            for op in ops:
                rows = None if op.rows is None else jnp.asarray(op.rows)
                ids = jnp.asarray(op.ids, jnp.int32)
                self._log_delta(op.kind, rows, ids)
                self._graph_apply(op.kind, np.asarray(op.rows)
                                  if op.rows is not None else None,
                                  np.asarray(op.ids))
        return {"applied": len(ops), "inserted": inserted,
                "spilled": spilled, "tombstoned": tombstoned}

    # ------------------------------------------------------------------
    # Raw ops (paper templates); the service routes these via the scheduler.
    # ------------------------------------------------------------------
    def _check_shardable(self, kind: str, n: int) -> None:
        """Sharded build/insert route rows block-wise over the mesh, which
        needs the batch to divide evenly; fail with an actionable message
        instead of shard_map's shape error."""
        if self.sharded and n % self._n_shards:
            raise ValueError(
                f"collection {self.name!r}: {kind} batch of {n} rows does "
                f"not divide over the {self._n_shards}-shard mesh; pad the "
                f"batch to a multiple of {self._n_shards}")

    def build(self, vectors, ids=None) -> dict:
        """Bulk build (paper 'index template').  Blocks until the index is
        live (device compute synced before return).

        Runs under the writer lock: a build replaces the whole index, so it
        must not interleave with inserts/deletes (the pre-versioned code
        computed off-lock and swapped unconditionally — the same lost-update
        race rebuild had).  Queries keep reading the old snapshot throughout.
        """
        # sharded rows stay on the host until dist_build places each
        # device's block there (never the whole batch on the first device)
        x = (np.asarray(vectors, np.float32) if self.sharded
             else jnp.asarray(vectors, jnp.float32))
        self._check_shardable("build", int(x.shape[0]))
        ids = self._ids_for(x.shape[0], ids)
        t0 = time.perf_counter()
        # a build replaces the whole state from scratch — no need to promote
        # a demoted one first, but the fresh device state must be admitted
        # against the residency budget (same shapes, same byte charge)
        mgr = self._residency_mgr
        if mgr is not None:
            mgr.make_room_for(self)
        try:
            return self._build_admitted(x, ids, t0)
        finally:
            if mgr is not None:
                mgr.finish_admit(self)

    def _build_admitted(self, x, ids, t0) -> dict:
        with self._writer_lock:
            if self.sharded:
                from repro.core import distributed as dce
                state, spilled_shards = dce.dist_build(
                    self._split(), x, ids, self.cfg, self.mesh,
                    spill_capacity_per_shard=self.spill_capacity)
                jax.block_until_ready(state.lists)
                per_shard = [int(v) for v in
                             np.asarray(jax.device_get(spilled_shards))]
            else:
                state, spilled = ivf.build(self._split(), x, ids, self.cfg,
                                           spill_capacity=self.spill_capacity)
                jax.block_until_ready(state.lists)
                per_shard = [int(spilled)]
            spilled = sum(per_shard)
            with self._lock:
                self._built = True
                self._epoch += 1           # obsoletes in-flight rebuild snapshots
                self._shard_pressure = [{"tombstones": 0, "spilled": sp}
                                        for sp in per_shard]
                self._spill_floors = list(per_shard)
                self._approx_live = int(x.shape[0])
                # a fresh index deserves a prompt recall measurement
                self._probe_ops = self.thresholds.probe_interval_ops
            self._swap(state, rebuilds=1, spilled=spilled)
            self._graph_invalidate()   # derived graph lazily rebuilds
            if not self.sharded:
                self._ship("build", x, ids)
        return {"build_s": time.perf_counter() - t0, "spilled": spilled}

    def insert(self, vectors, ids=None) -> int:
        """Insert rows (paper 'update template').  Returns #spilled.
        Blocks until the rows are queryable (compute synced, then swapped).

        Device compute runs under the writer lock only — concurrent queries
        keep reading the previous snapshot and are never blocked.  Uses the
        copying (`insert_shared`) kernel, never the donating one: queries on
        other threads may still hold the current snapshot, and donation
        would invalidate the buffers under them.  On a sharded collection
        rows route block-wise over the mesh (batch must divide evenly).
        """
        assert self._built, f"build() collection {self.name!r} before inserting"
        x = jnp.asarray(vectors, jnp.float32)
        self._check_shardable("insert", int(x.shape[0]))
        ids = self._ids_for(x.shape[0], ids)
        with self._hot_writer():
            if self.sharded:
                from repro.core import distributed as dce
                with tracing.device(dce.dist_insert):
                    state, spilled_shards = dce.dist_insert(
                        self._state, x, ids, self.cfg, self.mesh)
                    # sync: compute done before publish
                    per_shard = [int(v) for v in
                                 np.asarray(jax.device_get(spilled_shards))]
            else:
                with tracing.device(ivf.insert_shared):
                    state, spilled = ivf.insert_shared(self._state, x, ids,
                                                       self.cfg)
                    per_shard = [int(spilled)]
            with tracing.span("ame.publish"):
                spilled = sum(per_shard)
                with self._lock:
                    for s, sp in enumerate(per_shard):
                        self._shard_pressure[s]["spilled"] += sp
                    self._approx_live += int(x.shape[0])
                self._swap(state, inserts=int(x.shape[0]), spilled=spilled)
                self._log_delta("insert", x, ids)
                # mirror into the derived HNSW graph (no-op until one
                # exists); still under the writer lock, so graph order ==
                # state order
                self._graph_apply("insert", np.asarray(x), np.asarray(ids))
                self._ship("insert", x, ids)
        return spilled

    def delete(self, ids) -> int:
        """Tombstone `ids`; returns the number of slots actually tombstoned
        (ids not present contribute nothing — the maintenance triggers that
        consume the counters see true pressure, not requested counts).
        Blocks until the tombstones are visible to new queries.

        On a sharded collection tombstoning runs shard-locally (each shard
        masks its own slots, no collectives) and the per-shard hit counts
        feed per-shard maintenance pressure."""
        ids = jnp.asarray(np.atleast_1d(np.asarray(ids)), jnp.int32)
        with self._hot_writer():
            if self.sharded:
                from repro.core import distributed as dce
                with tracing.device(dce.dist_delete):
                    state, hits = dce.dist_delete(self._state, ids, self.mesh)
                    # sync: compute done before publish
                    per_shard = [int(v)
                                 for v in np.asarray(jax.device_get(hits))]
            else:
                with tracing.device(ivf.delete_shared):
                    state, n_hit = ivf.delete_shared(self._state, ids)
                    per_shard = [int(n_hit)]
            with tracing.span("ame.publish"):
                n_hit = sum(per_shard)
                with self._lock:
                    for s, n in enumerate(per_shard):
                        self._shard_pressure[s]["tombstones"] += n
                    self._approx_live = max(0, self._approx_live - n_hit)
                self._swap(state, deletes=n_hit)
                self._log_delta("delete", None, ids)
                # graph delete is idempotent per id — absent ids are a
                # no-op, matching the state's "ids not present contribute
                # nothing"
                self._graph_apply("delete", None, np.asarray(ids))
                self._ship("delete", None, ids)
        return n_hit

    def query(self, queries, k: Optional[int] = None,
              nprobe: Optional[int] = None,
              path: Optional[str] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (ids i32[B, k], scores f32[B, k]).  Template-routed;
        `path` ("probed" | "full_scan") overrides the router (benchmarks).

        Wait-free w.r.t. writers on a HOT collection: reads the current
        snapshot under the tiny pointer lock and never takes the writer
        lock — a stalled insert or in-flight rebuild cannot add to query
        latency.  On a WARM/COLD collection this is the cold-hit path: the
        state promotes back to device first (`promote()` — the service
        surfaces that latency separately), then the query runs as usual.
        Blocks only for its own device compute (result is synced to host).
        """
        q = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
        k, nprobe, path = self.resolve_query(q.shape[0], k, nprobe, path)
        state = self._query_state()
        self._bump(queries=int(q.shape[0]))
        if self.sharded:
            from repro.core import distributed as dce
            fn, args = dce.dist_query, (state, q, self.cfg, self.mesh, k)
        elif path == "hnsw":
            # derived-graph path: host-side serial beam search at the
            # tuner-owned ef (the paper's pointer-chasing baseline, live)
            return self._query_graph(np.asarray(q), k)
        elif path == "full_scan":
            fn, args = ivf.query_full_scan, (state, q, self.cfg, k)
        else:
            fn, args = ivf.query_probed, (state, q, self.cfg, k, nprobe)
        with tracing.device(fn):
            ids, scores = fn(*args)
            return np.asarray(ids), np.asarray(scores)

    def rebuild(self, shard: Optional[int] = None, *,
                max_restarts: int = 2) -> dict:
        """Reclaim tombstones + drain spill (paper 'index template') without
        losing concurrent writes.  Blocks until the rebuilt state is live.

        Snapshot -> recompute off-lock (writers log their ops to the bounded
        per-shard delta log) -> reacquire the writer lock -> replay the
        delta onto the rebuilt state -> swap.  On delta-log overflow the
        rebuild restarts from a fresh snapshot; the final attempt holds the
        writer lock for the whole recompute (writers wait, queries don't).
        If a bulk `build()` lands mid-rebuild the snapshot is obsolete and
        the rebuild aborts — the build's state wins.

        On a sharded collection `shard` selects ONE shard to compact
        shard-locally (reassign its live rows against the replicated
        centroids, repack, drain its spill); sibling shards' slices and
        versions are untouched, so hot shards are maintained independently.
        `shard=None` sweeps every shard in turn.  On an unsharded collection
        `shard` must be None or 0 (the index is its own single shard) and
        the rebuild is the full re-cluster (`ivf.rebuild`).
        """
        if not self.sharded:
            if shard not in (None, 0):
                raise ValueError(
                    f"collection {self.name!r} is unsharded; rebuild(shard="
                    f"{shard}) is only meaningful with shard_db=True")
            return self._rebuild_single(max_restarts)
        if shard is None:
            out = {"rebuild_s": 0.0, "spilled": 0, "replayed": 0,
                   "restarts": 0, "aborted": False, "shards": []}
            for s in range(self._n_shards):
                r = self._rebuild_shard(s, max_restarts)
                out["rebuild_s"] += r["rebuild_s"]
                out["spilled"] += r["spilled"]
                out["replayed"] += r["replayed"]
                out["restarts"] += r["restarts"]
                out["aborted"] = out["aborted"] or r["aborted"]
                out["shards"].append(s)
            return out
        if not 0 <= shard < self._n_shards:
            raise ValueError(f"collection {self.name!r} has shards "
                             f"0..{self._n_shards - 1}; got shard={shard}")
        return self._rebuild_shard(shard, max_restarts)

    def _rebuild_single(self, max_restarts: int) -> dict:
        """Unsharded delta-replay rebuild (full re-cluster).  Each phase of
        each attempt is an `ame.rebuild.<phase>` span."""
        t0 = time.perf_counter()
        with self._rebuild_locks[0]:
            restarts = 0
            while True:
                exclusive = restarts >= max_restarts
                attempt = {"attempt": restarts, "exclusive": exclusive}
                with tracing.span("ame.rebuild.snapshot", **attempt):
                    # promote-then-acquire: a demoted collection has no
                    # device state to rebuild (and a demotion mid-rebuild
                    # bumps _epoch, aborting us at the publish step like a
                    # bulk build would)
                    self._acquire_writer_hot()
                    snap = self._state
                    epoch = self._epoch
                    if exclusive:
                        self._count(rebuild_exclusive=1)
                    else:
                        with self._lock:
                            self._delta_logs[0] = []
                            self._delta_overflow[0] = False
                        self._writer_lock.release()
                try:
                    with tracing.span("ame.rebuild.recompute", **attempt):
                        new, spilled = ivf.rebuild(self._split(), snap,
                                                   self.cfg)
                        jax.block_until_ready(new.lists)
                        spilled = int(spilled)
                except BaseException:
                    # stop logging and release cleanly; writes stay applied
                    if not exclusive:
                        self._writer_lock.acquire()
                    try:
                        with self._lock:
                            self._delta_logs[0] = None
                            self._delta_overflow[0] = False
                    finally:
                        self._writer_lock.release()
                    raise
                if not exclusive:
                    with tracing.span("ame.rebuild.lock_wait", **attempt):
                        self._writer_lock.acquire()
                try:
                    with self._lock:
                        log = self._delta_logs[0] or []
                        overflow = self._delta_overflow[0]
                        self._delta_logs[0] = None
                        self._delta_overflow[0] = False
                    if self._epoch != epoch:
                        # a bulk build replaced the index mid-rebuild; our
                        # snapshot (and its tombstones) no longer exist
                        self._count(rebuild_aborts=1)
                        return {"rebuild_s": time.perf_counter() - t0,
                                "spilled": 0, "replayed": 0,
                                "restarts": restarts, "aborted": True}
                    if overflow:
                        self._count(rebuild_restarts=1)
                        restarts += 1
                        continue
                    replayed = sum(int(op.ids.shape[0]) for op in log)
                    tombstoned = 0
                    extra = 0
                    if log:
                        with tracing.span("ame.rebuild.replay", ops=len(log),
                                          rows=replayed, **attempt):
                            new, extra, tombstoned = ivf.replay(new, log,
                                                                self.cfg)
                            jax.block_until_ready(new.lists)
                    with tracing.span("ame.rebuild.publish", **attempt):
                        # replayed deletes leave real tombstones in the
                        # swapped state — pressure must reflect them, not
                        # reset to zero.  Only the recompute's own leftover
                        # spill becomes the floor (this rebuild just proved
                        # it cannot be drained); replay spill was never
                        # tested against a re-cluster, so it stays live
                        # pressure for the next rebuild to try.
                        with self._lock:
                            self._shard_pressure[0] = {
                                "tombstones": tombstoned,
                                "spilled": spilled + extra}
                            self._spill_floors[0] = spilled
                        self._count(replayed_ops=len(log),
                                    replayed_rows=replayed)
                        spilled += extra
                        self._swap(new, rebuilds=1)
                        # the rebuilt store may have repacked/dropped slots
                        # the incrementally-mirrored graph still reflects —
                        # drop the derived graph; the next graph query
                        # rebuilds it from the post-replay live rows
                        self._graph_invalidate()
                    return {"rebuild_s": time.perf_counter() - t0,
                            "spilled": spilled, "replayed": replayed,
                            "restarts": restarts, "aborted": False}
                finally:
                    self._writer_lock.release()

    def _rebuild_shard(self, shard: int, max_restarts: int) -> dict:
        """Shard-local delta-replay rebuild of one mesh shard.

        Same protocol (and spans, with `shard`) as `_rebuild_single` with
        two twists: the recompute is `dist_rebuild` (compaction of shard
        `shard` only — siblings pass through), and the publish step first
        *adopts* the rebuilt shard into the CURRENT live state
        (`dist_adopt_shard`) so sibling-shard writes that landed during the
        off-lock recompute are preserved without replay — only this shard's
        logged ops are replayed onto it.
        """
        from repro.core import distributed as dce
        t0 = time.perf_counter()
        with self._rebuild_locks[shard]:
            restarts = 0
            while True:
                exclusive = restarts >= max_restarts
                attempt = {"attempt": restarts, "exclusive": exclusive,
                           "shard": shard}
                with tracing.span("ame.rebuild.snapshot", **attempt):
                    # promote-then-acquire: a demoted collection has no
                    # device state to rebuild (and a demotion mid-rebuild
                    # bumps _epoch, aborting us at the publish step like a
                    # bulk build would)
                    self._acquire_writer_hot()
                    snap = self._state
                    epoch = self._epoch
                    if exclusive:
                        self._count(rebuild_exclusive=1)
                    else:
                        with self._lock:
                            self._delta_logs[shard] = []
                            self._delta_overflow[shard] = False
                        self._writer_lock.release()
                try:
                    with tracing.span("ame.rebuild.recompute", **attempt):
                        rebuilt, sp = dce.dist_rebuild(snap, self.cfg,
                                                       self.mesh, shard=shard)
                        jax.block_until_ready(rebuilt.lists)
                        spilled = int(np.asarray(jax.device_get(sp))[shard])
                except BaseException:
                    if not exclusive:
                        self._writer_lock.acquire()
                    try:
                        with self._lock:
                            self._delta_logs[shard] = None
                            self._delta_overflow[shard] = False
                    finally:
                        self._writer_lock.release()
                    raise
                if not exclusive:
                    with tracing.span("ame.rebuild.lock_wait", **attempt):
                        self._writer_lock.acquire()
                try:
                    with self._lock:
                        log = self._delta_logs[shard] or []
                        overflow = self._delta_overflow[shard]
                        self._delta_logs[shard] = None
                        self._delta_overflow[shard] = False
                    if self._epoch != epoch:
                        self._count(rebuild_aborts=1)
                        return {"rebuild_s": time.perf_counter() - t0,
                                "spilled": 0, "replayed": 0,
                                "restarts": restarts, "aborted": True,
                                "shard": shard}
                    if overflow:
                        self._count(rebuild_restarts=1)
                        restarts += 1
                        continue
                    replayed = sum(int(op.ids.shape[0]) for op in log)
                    # siblings keep their LIVE slices (concurrent writes
                    # already applied there); only this shard swaps in the
                    # rebuilt slice and replays its log
                    with tracing.span("ame.rebuild.replay", ops=len(log),
                                      rows=replayed, **attempt):
                        merged = dce.dist_adopt_shard(self._state, rebuilt,
                                                      shard, self.mesh)
                        extra = tombstoned = 0
                        if log:
                            merged, extra, tombstoned = dce.dist_replay(
                                merged, log, shard, self.cfg, self.mesh)
                        jax.block_until_ready(merged.lists)
                    with tracing.span("ame.rebuild.publish", **attempt):
                        # Spill rebalance: rows this rebuild could not drain
                        # (the shard's lists are full) move to an underfull
                        # sibling's spill buffer, so effective capacity is
                        # not bounded by the fullest shard.  The sibling's
                        # spill pressure rises accordingly, which is what
                        # wires the warm-up behind maintenance_due_shards():
                        # its next (auto-)rebuild drains the moved rows into
                        # its free list slots.  Runs under the writer lock
                        # we hold.
                        moved, moved_to = 0, None
                        if spilled + extra > 0:
                            merged, moved, moved_to = (
                                self._rebalance_spill_host(merged, shard))
                        with self._lock:
                            self._shard_pressure[shard] = {
                                "tombstones": tombstoned,
                                "spilled": max(spilled + extra - moved, 0)}
                            self._spill_floors[shard] = max(spilled - moved, 0)
                            if moved_to is not None:
                                self._shard_pressure[moved_to]["spilled"] += moved
                        self._count(replayed_ops=len(log),
                                    replayed_rows=replayed)
                        spilled += extra
                        bump = ((shard,) if moved_to is None
                                else (shard, moved_to))
                        self._swap(merged, shards=bump, rebuilds=1)
                    return {"rebuild_s": time.perf_counter() - t0,
                            "spilled": spilled, "replayed": replayed,
                            "restarts": restarts, "aborted": False,
                            "shard": shard, "rebalanced": moved,
                            "rebalance_to": moved_to}
                finally:
                    self._writer_lock.release()

    def _rebalance_spill_host(self, state, src: int):
        """Move shard `src`'s live spill rows to an underfull sibling.

        Host-side (split → move → assemble; this is background maintenance,
        not a hot path).  The destination is the sibling with the most free
        list slots (it can actually absorb the rows at its next rebuild)
        among those with spill room; rows move with their per-row quantized
        sideband, and `src`'s spill buffer is compacted — tombstoned spill
        slots vanish, so `num_deleted` drops by the reclaimed count.

        Caller holds the writer lock.  A sibling whose own rebuild is
        mid-recompute (`_rebuild_locks[j]` held) is skipped: its publish
        step adopts a rebuilt slice computed from a pre-move snapshot,
        which would silently drop rows we moved into it.  A sibling rebuild
        *starting* after this check blocks on the writer lock we hold, so
        its snapshot will include the moved rows.

        Returns (new_state, moved_rows, dst_shard) — (state, 0, None) when
        there is nothing to move or nowhere to put it.
        """
        from repro.core import distributed as dce
        if self._n_shards < 2:
            return state, 0, None
        shards = dce.split_host(state, self._n_shards)
        s = shards[src]
        cap = int(s.spill_ids.shape[0])
        n_src = int(s.spill_size)
        live = np.nonzero(np.asarray(s.spill_ids)[:n_src] >= 0)[0]
        if len(live) == 0:
            return state, 0, None
        dst, dst_key = None, None
        for j, t in enumerate(shards):
            if j == src or self._rebuild_locks[j].locked():
                continue
            free_spill = cap - int(t.spill_size)
            if free_spill <= 0:
                continue
            free_lists = (t.list_ids.shape[0] * t.list_ids.shape[1]
                          - int(np.sum(np.asarray(t.list_sizes))))
            key = (free_lists, free_spill)
            if dst is None or key > dst_key:
                dst, dst_key = j, key
        if dst is None:
            return state, 0, None
        d = shards[dst]
        n_dst = int(d.spill_size)
        m = int(min(len(live), cap - n_dst))
        take, keep = live[:m], live[m:]
        dead = n_src - len(live)     # tombstoned spill slots compacted away

        def pack_src(a, fill=0):
            a = np.asarray(a)
            out = np.full_like(a, fill)
            out[:len(keep)] = a[keep]
            return out

        def grow_dst(a, rows):
            a = np.asarray(a).copy()
            a[n_dst:n_dst + m] = rows
            return a

        s_new = s._replace(
            spill=pack_src(s.spill),
            spill_ids=pack_src(s.spill_ids, fill=-1),
            spill_size=np.asarray(len(keep), np.int32),
            num_deleted=np.asarray(int(s.num_deleted) - dead, np.int32))
        d_new = d._replace(
            spill=grow_dst(d.spill, np.asarray(s.spill)[take]),
            spill_ids=grow_dst(d.spill_ids, np.asarray(s.spill_ids)[take]),
            spill_size=np.asarray(n_dst + m, np.int32))
        if s.q_spill is not None:
            # per-row affine sideband rides along with its rows
            s_new = s_new._replace(
                q_spill=pack_src(s.q_spill),
                q_spill_scales=pack_src(s.q_spill_scales, fill=1.0),
                q_spill_zeros=pack_src(s.q_spill_zeros),
                q_spill_norms=pack_src(s.q_spill_norms))
            d_new = d_new._replace(
                q_spill=grow_dst(d.q_spill, np.asarray(s.q_spill)[take]),
                q_spill_scales=grow_dst(d.q_spill_scales,
                                        np.asarray(s.q_spill_scales)[take]),
                q_spill_zeros=grow_dst(d.q_spill_zeros,
                                       np.asarray(s.q_spill_zeros)[take]),
                q_spill_norms=grow_dst(d.q_spill_norms,
                                       np.asarray(s.q_spill_norms)[take]))
        shards[src], shards[dst] = s_new, d_new
        return dce.assemble_host(shards, self.mesh), m, dst

    # ------------------------------------------------------------------
    # Index policy + derived HNSW graph tier (recall-adaptive routing)
    # ------------------------------------------------------------------
    def index_policy(self) -> str:
        """Resolved index policy for the collection's CURRENT size.

        "auto" follows the host-side live-row estimate across the template
        thresholds: <= `flat_max_rows` -> "flat" (exact full-scan GEMM),
        >= `hnsw_min_rows` -> "hnsw" (derived graph), else "ivf".  Sharded
        collections always resolve to "ivf" — the mesh tier serves exact
        per-shard scans with a hierarchical merge.
        """
        pol = self.cfg.index_policy
        if pol != "auto":
            return pol
        if self.sharded:
            return "ivf"
        with self._lock:
            n = self._approx_live
        if n <= self.thresholds.flat_max_rows:
            return "flat"
        if n >= self.thresholds.hnsw_min_rows:
            return "hnsw"
        return "ivf"

    def tuned_nprobe(self) -> int:
        """The tuner-owned nprobe (cfg default until a tuner exists)."""
        t = self._nprobe_tuner
        return self.cfg.nprobe if t is None else t.knob

    def tuned_ef(self, k: Optional[int] = None) -> int:
        """The tuner-owned HNSW beam width, floored at k."""
        t = self._ef_tuner
        ef = self.cfg.hnsw_ef if t is None else t.knob
        return max(ef, k or self.cfg.k)

    def _graph_invalidate(self) -> None:
        with self._graph_lock:
            self._graph = None

    def _graph_apply(self, kind: str, rows, ids) -> None:
        """Incrementally mirror one write into the derived graph.  Caller
        holds the writer lock, so graph mutation order == state order; a
        no-op until a graph exists (it then rebuilds lazily including this
        write).  `ids` host-convertible; `rows` f32[N, D] for inserts."""
        with self._graph_lock:
            g = self._graph
            if g is None:
                return
            if kind == "insert":
                for r, i in zip(rows, ids):
                    g.add(r, int(i))
            else:
                for i in np.atleast_1d(ids):
                    g.delete(int(i))

    def _build_graph_from(self, state):
        """Fresh HNSW graph over the live rows of `state` (host-side)."""
        from repro.core.hnsw import HNSW
        rows, ids = ivf.flat_rows_host(state)
        live = np.nonzero(ids >= 0)[0]
        g = HNSW(self.cfg.dim, m=self.cfg.hnsw_m,
                 ef_construction=max(self.cfg.hnsw_ef, 2 * self.cfg.hnsw_m),
                 metric=self.cfg.metric)
        g.build(rows[live], ids[live])
        return g

    def _ensure_graph(self):
        """The derived graph, (re)building it from the live rows if absent.

        The build runs under the writer lock (serialized against mutators,
        so no mirror update can be lost between the snapshot read and the
        install) — the O(N log N) cost lands on the first graph query after
        an invalidation, which is exactly the paper's HNSW build story.
        Queries against an existing graph never touch the writer lock.
        """
        with self._graph_lock:
            g = self._graph
        if g is not None:
            return g
        with self._hot_writer():
            with self._graph_lock:
                g = self._graph
            if g is None:
                g = self._build_graph_from(self._state)
                with self._graph_lock:
                    self._graph = g
            return g

    def _query_graph(self, q: np.ndarray, k: int,
                     ef: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Serve a query batch from the HNSW graph (path "hnsw").

        Returns (ids i64[B, k], scores f32[B, k]) in the engine's score
        convention (larger = better; "ip" scores are raw inner products,
        "l2" scores are negated distances so rankings match the IVF paths).
        Searches serialize on the graph lock — the single-threaded
        pointer-chasing baseline the paper measures against.
        """
        g = self._ensure_graph()
        ef = ef or self.tuned_ef(k)
        with self._graph_lock:
            ids, ds = g.search_batch_scored(q, k, ef=ef)
        scores = np.where(np.isfinite(ds), -ds, -np.inf).astype(np.float32)
        return ids, scores

    # ------------------------------------------------------------------
    # Recall probe (background MemoryOp kind "probe")
    # ------------------------------------------------------------------
    def recall_probe_due(self) -> bool:
        """True when the recall tuner wants a fresh measurement: probing
        armed (`cfg.target_recall > 0`), built, HOT, and at least
        `thresholds.probe_interval_ops` ops since the last probe."""
        if self.cfg.target_recall <= 0:
            return False
        with self._lock:
            return (self._built and self._residency_tier == "hot"
                    and self._probe_ops >= self.thresholds.probe_interval_ops)

    def recall_probe(self, sample: Optional[int] = None,
                     k: Optional[int] = None) -> dict:
        """One recall measurement + tuner step (the "probe" op kind).

        Snapshots the state, samples live rows as queries, runs them down
        the collection's LIVE serving path, scores against the exact
        brute-force oracle on the same snapshot, and feeds recall@k to the
        path's knob tuner (`nprobe` on the probed path, `ef` on the graph
        path; the flat and sharded paths are exact — measured, never
        retuned).  Read-only w.r.t. the row store: no writer lock, no state
        swap — retuning has zero query downtime (in-flight queries keep the
        knob they resolved; later ones pick up the new value atomically).
        """
        k = k or self.cfg.k
        sample = sample or self.thresholds.probe_sample
        with self._lock:
            if not self._built or self._residency_tier != "hot":
                return {"skipped": self._residency_tier, "recall": None}
            state = self._state
            self._probe_ops = 0
            seq = self._probe_seq
            self._probe_seq += 1
        # flat host view of the snapshot = the oracle's ground truth
        if self.sharded:
            from repro.core import distributed as dce
            parts = [ivf.flat_rows_host(s)
                     for s in dce.split_host(state, self._n_shards)]
            rows = np.concatenate([p[0] for p in parts])
            ids = np.concatenate([p[1] for p in parts])
        else:
            rows, ids = ivf.flat_rows_host(state)
        live = np.nonzero(ids >= 0)[0]
        # Probe the path the policy serves steady traffic with — NOT the
        # batch router's choice for the probe's own batch size: a
        # probe_sample-row batch would route to the exact full scan and the
        # nprobe tuner would never observe the probed path it owns.
        if self.sharded:
            path, nprobe = "sharded", 0
        else:
            pol = self.index_policy()
            if pol == "flat":
                path, nprobe = "full_scan", 0
            elif pol == "hnsw":
                path, nprobe = "hnsw", 0
            else:
                path = "probed"
                nprobe = max(1, min(self.tuned_nprobe(),
                                    self.cfg.n_clusters))
        out = {"path": path, "k": k, "sample": 0, "recall": 1.0,
               "knob": None, "retuned": False, "seq": seq}
        if len(live) == 0:            # nothing to measure — vacuously met
            with self._lock:
                self._last_probe = out
            return out
        import zlib
        rng = np.random.default_rng(
            (zlib.crc32(self.name.encode()) + seq) & 0x7FFFFFFF)
        sel = rng.choice(live, size=min(sample, len(live)), replace=False)
        qs = rows[sel]
        true = metrics.brute_force_topk(qs, rows, ids, k, self.cfg.metric)
        tuner = None
        if self.sharded:
            from repro.core import distributed as dce
            got, _ = dce.dist_query(state, jnp.asarray(qs), self.cfg,
                                    self.mesh, k)
        elif path == "full_scan":
            got, _ = ivf.query_full_scan(state, jnp.asarray(qs), self.cfg, k)
        elif path == "hnsw":
            tuner = self._ef_tuner
            got, _ = self._query_graph(qs, k)
        else:
            tuner = self._nprobe_tuner
            got, _ = ivf.query_probed(state, jnp.asarray(qs), self.cfg, k,
                                      nprobe)
        rec = metrics.recall_at_k(np.asarray(got), np.asarray(true))
        out.update(recall=rec, sample=int(len(sel)))
        if tuner is not None:
            before = tuner.knob
            after = tuner.observe(rec)
            out.update(knob=after, retuned=after != before)
        with self._lock:
            self._last_probe = out
        return out

    # ------------------------------------------------------------------
    # Maintenance pressure (consumed by the service's MaintenanceController)
    # ------------------------------------------------------------------
    def maintenance_pressure(self) -> dict:
        """Host-side pressure since the last (re)build — poll-cheap.

        Aggregate counters plus a per-shard breakdown under ``"shards"``
        (the controller schedules shard-local rebuilds from the latter).
        """
        with self._lock:
            shards = [dict(p) for p in self._shard_pressure]
            for s, log in enumerate(self._delta_logs):
                shards[s]["delta_backlog"] = len(log) if log is not None else 0
        p = {"tombstones": sum(s["tombstones"] for s in shards),
             "spilled": sum(s["spilled"] for s in shards),
             "delta_backlog": max(s["delta_backlog"] for s in shards),
             "shards": shards}
        return p

    def _maintenance_limits(self) -> Tuple[int, int]:
        """Per-shard (tombstone, spill) rebuild trigger limits.

        Each shard owns `cfg.capacity` list slots and `spill_capacity` spill
        slots (the global sharded arrays are S stacked copies of that), so
        the same fractions apply per shard in both tiers.  The shard-local
        pending floor (`maintenance_shard_min_pending`) only applies when
        the collection is actually sharded — an unsharded collection's
        single shard sees the full traffic and keeps the aggregate floor."""
        return self.thresholds.maintenance_limits(self.cfg.capacity,
                                                  self.spill_capacity,
                                                  per_shard=self.sharded)

    def maintenance_due_shards(self) -> List[int]:
        """Shard ids whose tombstone/spill pressure crosses the collection's
        thresholds — each is worth an independent shard-local rebuild.
        Unsharded collections report `[0]` when due (the single shard)."""
        if not self._built or self.residency != "hot":
            # a demoted collection has no device state to compact; promoting
            # it just to rebuild would fight the eviction policy — pressure
            # keeps accruing and is served once a query promotes it
            return []
        tomb_limit, spill_limit = self._maintenance_limits()
        with self._lock:
            press = [dict(p) for p in self._shard_pressure]
            floors = list(self._spill_floors)
        # only spill above the irreducible floor counts — residual spill the
        # last rebuild failed to place must not re-trigger it forever
        return [s for s in range(self._n_shards)
                if press[s]["tombstones"] >= tomb_limit
                or press[s]["spilled"] - floors[s] >= spill_limit]

    def maintenance_due(self) -> bool:
        """True when any shard's pressure crosses the thresholds and a
        background (shard-local) rebuild would pay for itself."""
        return bool(self.maintenance_due_shards())

    # ------------------------------------------------------------------
    def resolve_query(self, batch: int, k, nprobe, path) -> Tuple[int, int, str]:
        """Resolve query params against collection defaults + the router.

        The resolved triple is part of the batch signature, so sync,
        future, and cross-collection-batched execution of the same request
        all take the identical execution path.

        nprobe is tuner-owned: a caller passing None gets the recall
        tuner's current knob (cfg default until a tuner exists), clamped
        EXACTLY like the kernel clamps it (`ivf.query_probed`: max(1,
        min(nprobe, n_clusters))) — the resolved value IS the executed
        value, so the signature can never disagree with the dispatch, and
        two tenants tuned to different nprobe split fusion groups cleanly.
        Off the probe path nprobe is not an execution parameter at all and
        is pinned to 0, so tuner divergence never splits full-scan or
        graph-path groups.

        The execution path follows the resolved index policy: "flat"
        always full-scans, "hnsw" serves from the derived graph, "ivf"
        (and sharded tenants) keep the profiling-guided template route.
        """
        k = k or self.cfg.k
        if not nprobe:
            nprobe = self.tuned_nprobe()
        # identical clamp to ivf.query_probed — signature == execution
        nprobe = max(1, min(int(nprobe), self.cfg.n_clusters))
        if path is None:
            policy = self.index_policy()
            if policy == "flat":
                path = "full_scan"
            elif policy == "hnsw" and not self.sharded:
                path = "hnsw"
            else:
                path = templates.route("query", batch, self.cfg,
                                       self.thresholds).path
        if path != "probed":
            nprobe = 0        # unused off the probe path; keep groups whole
        return k, nprobe, path

    def batch_signature(self, batch: int, k, nprobe, path):
        """Fusion key: collections whose pending queries share this key can
        stack states and run as one padded GEMM dispatch.

        The third element is the collection's mesh (None when unsharded):
        sharded lanes fuse too (`distributed.dist_fused_query` stacks their
        shard-local blocks per device), but only lanes living on the SAME
        mesh — mesh identity covers both the device set and the axis shape,
        so a 2-shard and a 4-shard tenant can never group.  `cfg` pins the
        state shapes, `spill_capacity` the spill block, and the resolved
        `(k, nprobe, path)` triple the kernel; together the key guarantees
        every lane in a group stacks leaf-for-leaf.

        The store-dtype policy is an explicit element even though `cfg`
        already determines it: fusing an int8 lane with an f32 lane would
        stack mismatched treedefs (the quantized state carries extra
        leaves) and mix scan pipelines — the policy split must hold even if
        the cfg element is ever relaxed to a shape-only key.
        """
        k, nprobe, path = self.resolve_query(batch, k, nprobe, path)
        return (self.cfg, self.cfg.store_dtype, self.spill_capacity,
                self.mesh if self.sharded else None, k, nprobe, path)

    def stats(self) -> dict:
        """Counters + index occupancy snapshot.  Syncs device scalars (live/
        spill/deleted counts) — cheap but not free; poll `maintenance_
        pressure()` instead on hot paths."""
        with self._lock:
            state = self._state
            tier = self._residency_tier
            host = self._host_state
            counters = dict(self.counters)
            version = self._version
            shard_versions = list(self._shard_versions)
            pressure = [dict(p) for p in self._shard_pressure]
        if tier != "hot":
            # no device state to sync; sizes are static, occupancy comes
            # from the host copy when one is in RAM (cold = disk only)
            s = {"n_clusters": self.cfg.n_clusters, "dim": self.cfg.dim,
                 "list_capacity": self.cfg.list_capacity,
                 "index_bytes": self._index_nbytes,
                 "bytes_per_row": self.cfg.dim * (5 if self.cfg.quantized
                                                  else 4),
                 "scan_bytes_per_row": self.cfg.dim * (
                     1 if self.cfg.quantized else 4),
                 "store_dtype": self.cfg.store_dtype}
            if tier == "warm" and host is not None:
                locals_ = host if self.sharded else [host]
                s["live"] = int(sum(
                    np.sum(np.asarray(t.list_ids) >= 0)
                    + np.sum(np.asarray(t.spill_ids) >= 0) for t in locals_))
                s["spill"] = int(sum(int(t.spill_size) for t in locals_))
                s["deleted"] = int(sum(int(t.num_deleted) for t in locals_))
            if self.sharded:
                s["shards"] = self._n_shards
                s["shard_versions"] = shard_versions
        elif self.sharded:
            s = {"n_clusters": state.n_clusters, "dim": state.dim,
                 "list_capacity": state.list_capacity,
                 "live": int(jax.device_get(ivf.live_count(state))),
                 "spill": int(np.sum(jax.device_get(state.spill_size))),
                 "deleted": int(np.sum(jax.device_get(state.num_deleted))),
                 "shards": self._n_shards,
                 "shard_versions": shard_versions,
                 **ivf.footprint(state)}
        else:
            s = ivf.stats(state)
        s.update(counters)
        s["version"] = version
        s["residency"] = tier
        s["pressure"] = {"tombstones": sum(p["tombstones"] for p in pressure),
                         "spilled": sum(p["spilled"] for p in pressure),
                         "shards": pressure}
        s["index_policy"] = self.index_policy()
        if self._nprobe_tuner is not None:
            s["tuner"] = {"nprobe": self._nprobe_tuner.stats(),
                          "ef": self._ef_tuner.stats()}
        with self._lock:
            s["last_probe"] = (None if self._last_probe is None
                               else dict(self._last_probe))
        return s

    # ------------------------------------------------------------------
    # Persistence — one namespace directory per collection.
    # ------------------------------------------------------------------
    def save_into(self, directory: str, step: int = 0) -> None:
        """Write this collection's namespace directory.

        Unsharded: one Checkpointer step dir + `collection.json`.  Sharded:
        one `shard_<i>/` Checkpointer namespace per shard (each holds that
        shard's local `IVFState`) plus the mesh axis names/shape in the
        metadata so `load_from` can verify — or host-reshard — the layout.
        Reads a consistent snapshot; safe to call under live traffic.

        Residency round-trips: the metadata records the tier (and the
        host-side pressure counters, since a demoted collection has no
        device scalars to re-derive them from), and a WARM/COLD collection
        saves from its host copy / cold checkpoint without ever touching
        the device — COLD really is just "checkpointed + not loaded".
        """
        os.makedirs(directory, exist_ok=True)
        with self._writer_lock:
            with self._lock:
                tier = self._residency_tier
                meta = {"name": self.name, "next_id": self._next_id,
                        "counters": dict(self.counters),
                        "built": self._built,
                        "spill_capacity": self.spill_capacity, "step": step,
                        "spill_floors": list(self._spill_floors),
                        "store_dtype": self.cfg.store_dtype,
                        "residency": tier,
                        "pressure": [dict(p) for p in self._shard_pressure],
                        "approx_live": self._approx_live,
                        "probe_seq": self._probe_seq}
            # tuner state round-trips so a restored collection keeps its
            # learned effort knobs instead of re-seeking from the defaults
            if self._nprobe_tuner is not None:
                meta["tuners"] = {"nprobe": self._nprobe_tuner.to_dict(),
                                  "ef": self._ef_tuner.to_dict()}
            if self.sharded:
                meta["sharded"] = True
                meta["mesh_axes"] = list(self.mesh.axis_names)
                meta["mesh_shape"] = [int(self.mesh.shape[a])
                                      for a in self.mesh.axis_names]
            host = self._host_view_locked()
            self._write_host_state(directory, host, step)
        atomic_write_json(os.path.join(directory, META_FILE), meta)

    @classmethod
    def load_from(cls, directory: str, name: str, cfg: EngineConfig, *,
                  step: Optional[int] = None, reshard: bool = False,
                  **kw) -> "Collection":
        """Restore a collection from its namespace directory.

        Sharded snapshots need ``cfg.shard_db=True`` and a ``mesh=`` kwarg.
        If the mesh shape differs from the one the snapshot was saved on,
        the default is to fail fast; pass ``reshard=True`` to re-pack the
        saved rows host-side onto the new mesh (deterministic against the
        saved centroids; see `repro.core.distributed.reshard_host`).
        """
        from repro.checkpoint.checkpointer import Checkpointer
        mpath = os.path.join(directory, META_FILE)
        meta = {}
        if os.path.exists(mpath):
            with open(mpath) as f:
                meta = json.load(f)
        spill_capacity = int(meta.get("spill_capacity", 4096))
        # the snapshot's dtype policy wins: the checkpointed treedef carries
        # (or lacks) the quantized leaves, so restoring under the wrong
        # policy would fail the leaf-count check — pre-policy snapshots
        # default to the caller's cfg
        saved_dtype = meta.get("store_dtype")
        if saved_dtype is not None and saved_dtype != cfg.store_dtype:
            cfg = dataclasses.replace(cfg, store_dtype=saved_dtype)
        residency = meta.get("residency", "hot")
        # never pre-allocate device arrays: a HOT load installs the restored
        # state, a WARM/COLD load must stay device-free entirely
        coll = cls(name, cfg, spill_capacity=spill_capacity,
                   _alloc_state=False, **kw)
        if bool(meta.get("sharded", False)) != coll.sharded:
            saved = "sharded" if meta.get("sharded") else "unsharded"
            raise ValueError(
                f"collection {name!r} was saved {saved} (mesh "
                f"{meta.get('mesh_shape')}); load it with a matching "
                "EngineConfig.shard_db and, when sharded, a mesh= kwarg")
        resharded = False
        template = ivf.empty_host_state(cfg, spill_capacity)._asdict()
        if coll.sharded:
            from repro.core import distributed as dce
            saved_shape = [int(v) for v in meta["mesh_shape"]]
            cur_shape = [int(coll.mesh.shape[a])
                         for a in coll.mesh.axis_names]
            n_saved = int(np.prod(saved_shape))
            if cur_shape != saved_shape and not reshard:
                raise ValueError(
                    f"collection {name!r} was saved on mesh "
                    f"{dict(zip(meta['mesh_axes'], saved_shape))} but is "
                    f"being loaded on mesh shape {cur_shape}; pass "
                    "reshard=True to re-pack the rows host-side onto the "
                    "new mesh")
            if cur_shape != saved_shape:
                # resharding re-packs rows through the device insert kernel;
                # the re-packed state can only materialize HOT
                resharded, residency = True, "hot"
            if residency == "cold":
                # COLD = checkpointed + not loaded: adopt the namespace as
                # the cold checkpoint, touch no array data at all
                with coll._lock:
                    coll._cold_dir = directory
                    coll._cold_step = step
                    coll._residency_tier = "cold"
                floors = meta.get("spill_floors", [0] * n_saved)
            else:
                shards = []
                for i in range(n_saved):
                    ck = Checkpointer(
                        os.path.join(directory, f"shard_{i:03d}"))
                    shards.append(
                        ivf.IVFState(**ck.restore(template, step=step)))
                if resharded:
                    shards = dce.reshard_host(shards, cfg, coll.mesh.size,
                                              spill_capacity)
                    # re-packed layout: old per-shard floors are
                    # meaningless; the next rebuild re-establishes them
                    floors = [0] * coll.mesh.size
                else:
                    floors = meta.get("spill_floors", [0] * n_saved)
                if residency == "warm":
                    with coll._lock:
                        coll._host_state = shards
                        coll._residency_tier = "warm"
                else:
                    coll.state = dce.assemble_host(shards, coll.mesh)
        else:
            if residency == "cold":
                with coll._lock:
                    coll._cold_dir = directory
                    coll._cold_step = step
                    coll._residency_tier = "cold"
            else:
                restored = Checkpointer(directory).restore(template,
                                                           step=step)
                if residency == "warm":
                    with coll._lock:
                        coll._host_state = ivf.IVFState(**restored)
                        coll._residency_tier = "warm"
                else:
                    coll.state = ivf.IVFState(**{
                        k: jnp.asarray(v) if v is not None else None
                        for k, v in restored.items()})
            floors = meta.get("spill_floors")
            if floors is None:   # pre-sharding snapshots: scalar field
                floors = [int(meta.get("spill_floor", 0))]
        # keep the never-built guard across a save/load round-trip (older
        # snapshots without the flag were only saved after a build)
        with coll._lock:
            coll._built = bool(meta.get("built", True))
            coll._next_id = int(meta.get("next_id", 0))
            coll.counters.update(meta.get("counters", {}))
            coll._approx_live = int(meta.get("approx_live", 0))
            coll._probe_seq = int(meta.get("probe_seq", 0))
        # restore learned tuner knobs under the CALLER's target_recall (the
        # cfg wins over the snapshot's target, but the knob/floor survive)
        tuners = meta.get("tuners")
        if tuners is not None and coll._nprobe_tuner is not None:
            from repro.core.tuner import RecallTuner
            for attr, key in (("_nprobe_tuner", "nprobe"),
                              ("_ef_tuner", "ef")):
                d = dict(tuners[key])
                d["target"] = cfg.target_recall
                setattr(coll, attr, RecallTuner.from_dict(d))
        # re-seed maintenance pressure so a reload doesn't silently forget
        # accumulated tombstones/spill: newer snapshots persist the host
        # counters (a demoted collection has no device scalars to read);
        # older ones — always HOT — re-derive them from the device state.
        # The spill floor survives the round-trip so known-irreducible
        # spill doesn't auto-trigger a futile rebuild on every restart.
        press = None if resharded else meta.get("pressure")
        if press is not None:
            press = [{"tombstones": int(p.get("tombstones", 0)),
                      "spilled": int(p.get("spilled", 0))} for p in press]
            press = press[:coll._n_shards]
            press += [{"tombstones": 0, "spilled": 0}
                      for _ in range(coll._n_shards - len(press))]
        else:
            st = coll.state
            deleted = np.atleast_1d(np.asarray(
                jax.device_get(st.num_deleted)))
            spill = np.atleast_1d(np.asarray(jax.device_get(st.spill_size)))
            press = [{"tombstones": int(deleted[s]),
                      "spilled": int(spill[s])}
                     for s in range(coll._n_shards)]
        spill_floors = [int(f) for f in floors][:coll._n_shards]
        spill_floors += [0] * (coll._n_shards - len(spill_floors))
        with coll._lock:
            coll._shard_pressure = press
            coll._spill_floors = spill_floors
        return coll
