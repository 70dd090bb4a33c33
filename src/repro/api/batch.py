"""Cross-collection batched query execution (lane/pad/stack/demux).

Tenant count must scale without per-tenant kernel launches.  Pending queries
against *different* collections that resolved to the same execution
signature — identical `EngineConfig` shapes, mesh (None for unsharded
tenants), `(k, nprobe)`, and routed path — are fused: per-collection query
batches concatenate into **lanes**, lanes **pad** to a common batch Bmax,
collection states **stack** along a new leading G axis, and a single vmapped
(hence one padded-GEMM) dispatch answers all of them.  The results are then
**demuxed** back to the per-op futures by row span.

Two stacking regimes, one invariant:

* Unsharded lanes stack host-held states directly (`stack_states`) and run
  `fused_query` — one jitted vmap over the G-stack.
* Mesh-sharded lanes must NOT gather their device-sharded arrays to host
  just to stack them.  `execute_group(..., mesh=...)` hands the G global
  states to `distributed.dist_fused_query`, which stacks each device's G
  shard-local blocks lane-wise ([G, rows/shard, …] per device) *inside*
  `shard_map` — so G sharded tenants cost one dispatch, same as unsharded.

Correctness invariant (tested, both regimes): the fused path returns exactly
what the per-collection sync path returns — lane `g` only ever scans
collection `g`'s rows, padding lanes are discarded on demux.

Stacking is the one cost fusion adds (a copy of every lane's state per
dispatch), so the service threads a `StackCache` through `execute_group`:
stacked states are tagged with the lanes' atomically-read versions and
reused until any lane writes — steady-state query serving pays the copy
once, not per flush.

Thread-safety: `execute_group` reads each collection's `snapshot()` (wait-
free versioned read; a concurrent writer or in-flight rebuild swaps the
pointer, never mutates a published state) and `demux` only ever *settles*
futures — `OpFuture._set_result` is a plain write + event set, safe from
any scheduler worker while other threads wait.  Neither function takes a
collection or service lock, so a fused dispatch can never deadlock against
writers.
"""
from __future__ import annotations

import functools
import weakref
from collections import OrderedDict
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EngineConfig
from repro.core import index as ivf
from repro.core import locking
from repro.core import tracing


class NotResident(RuntimeError):
    """A fused lane's collection was demoted off the device between flush
    and dispatch — the stacked execution cannot proceed.  The service
    catches this, re-promotes the lane, and retries (or falls back to
    per-lane queries, which promote themselves)."""


@functools.partial(jax.jit, static_argnames=("cfg", "k", "nprobe", "path"))
def fused_query(stacked: ivf.IVFState, q: jax.Array, cfg: EngineConfig,
                k: int, nprobe: int, path: str):
    """One dispatch over G stacked (unsharded) collection states.

    stacked: IVFState whose every leaf has a leading G axis
    q:       f32[G, Bmax, D] padded per-lane query batches
    Returns (ids i32[G, Bmax, k], scores f32[G, Bmax, k]).
    """
    def one(state, qi):
        if path == "full_scan":
            return ivf.query_full_scan(state, qi, cfg, k)
        return ivf.query_probed(state, qi, cfg, k, nprobe)

    return jax.vmap(one)(stacked, q)


def stack_states(states: Sequence[ivf.IVFState]) -> ivf.IVFState:
    """Stack G same-shaped collection states along a new leading axis.

    Host-side stacking for UNSHARDED states only: a mesh-sharded state's
    leaves live distributed over devices, and stacking them here would
    silently gather every shard to one place — sharded lanes instead stack
    per-device inside `distributed.dist_fused_query`'s `shard_map` body.
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def _stack(snaps: Sequence[ivf.IVFState], mesh) -> ivf.IVFState:
    """Stack G snapshots for one fused dispatch: host-side for unsharded
    lanes, per-device inside `shard_map` for mesh-sharded ones."""
    if mesh is not None:
        from repro.core import distributed as dce
        return dce.dist_stack_states(snaps, mesh)
    return stack_states(snaps)


class StackCache:
    """Reuse the stacked G-state across fused dispatches.

    Stacking is the one real cost fusion adds over per-op dispatch: a fresh
    copy of every lane's state per flush.  Query-heavy windows re-dispatch
    the same tenant groups far more often than those tenants write, so the
    cache keys each stacked state by the lanes' *versioned snapshots* —
    `(collection, version)` pairs read atomically
    (`Collection.versioned_snapshot`) — and serves the device-resident
    stack straight back while every lane's version is unchanged.  Any write
    to any lane bumps that collection's version, missing the key; LRU
    eviction (a handful of group entries) bounds the extra device memory.

    Thread-safety: the entry dict is guarded by a lock; the stack build
    itself runs outside it (device work must not serialize flushes).  Two
    racing flushes over the same group may both build — harmless, last one
    cached.  Correctness does not depend on eviction policy: a cache hit is
    proof (via the atomic version tag) that the stack equals re-stacking
    the lanes' current snapshots.
    """

    def __init__(self, maxsize: int = 4):
        self.maxsize = maxsize
        self._lock = locking.make_lock("_lock")
        # key -> (stacked_state, nbytes); nbytes feeds the residency
        # manager's device-budget accounting (the stacks are device copies)
        self._entries: OrderedDict = OrderedDict()
        # collections evicted via evict(): a fused task already in flight
        # when its tenant was dropped must not re-insert that tenant's
        # stack after the eviction (weak refs — the set itself never pins)
        self._dropped: "weakref.WeakSet" = weakref.WeakSet()
        self.hits = 0
        self.misses = 0

    def stacked(self, collections, mesh) -> ivf.IVFState:
        snaps, tag = [], []
        for c in collections:
            state, version = c.versioned_snapshot()
            if state is None:             # demoted off-device mid-window
                raise NotResident(c.name)
            snaps.append(state)
            tag.append((c, version))
        key = (mesh, tuple(tag))
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return hit[0]
        stacked = _stack(snaps, mesh)
        nbytes = sum(int(leaf.nbytes) for leaf in jax.tree.leaves(stacked))
        with self._lock:
            self.misses += 1
            # serve but never cache a stack whose tenant was dropped while
            # we built it — caching would resurrect the entry evict()
            # just removed and pin the dropped state
            if not any(c in self._dropped for c in collections):
                self._entries[key] = (stacked, nbytes)
                self._entries.move_to_end(key)
                while len(self._entries) > self.maxsize:
                    self._entries.popitem(last=False)
        return stacked

    def device_bytes(self) -> int:
        """Device bytes the cached stacks pin — charged against the
        service's residency budget alongside the HOT collections."""
        with self._lock:
            return sum(nb for _, nb in self._entries.values())

    def pop_lru(self) -> bool:
        """Evict the least-recently-used stack; False when empty.  The
        residency manager drains the cache before demoting a live tenant —
        a cached stack is a derived copy, strictly cheaper to lose."""
        with self._lock:
            if not self._entries:
                return False
            self._entries.popitem(last=False)
            return True

    def evict(self, collection) -> None:
        """Drop every entry whose group includes `collection`.

        Called by `MemoryService.drop_collection`: the key holds the
        Collection object and the value a full stacked copy of its state,
        so without eviction a dropped tenant's device memory would stay
        pinned until unrelated LRU churn.  Also marks the collection so a
        fused dispatch racing the drop (stack built off-lock) cannot
        re-insert it afterwards.
        """
        with self._lock:
            self._dropped.add(collection)
            for key in [k for k in self._entries
                        if any(c is collection for c, _ in k[1])]:
                del self._entries[key]

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._entries),
                    "device_bytes": sum(
                        nb for _, nb in self._entries.values())}


def execute_group(collections, queries: List[np.ndarray],
                  cfg: EngineConfig, k: int, nprobe: int, path: str,
                  mesh=None, cache: Optional[StackCache] = None,
                  ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Run one fused dispatch for same-signature lanes.

    collections: G distinct Collection objects (one per lane)
    queries:     G query batches f32[B_g, D] (B_g may differ per lane)
    mesh:        the collections' shared jax Mesh when they are sharded
                 (from the batch signature — same-mesh lanes only), else
                 None for the host-stacked unsharded path
    cache:       optional `StackCache` reusing the stacked state across
                 dispatches while the lanes' versions are unchanged
    Returns per-lane (ids [B_g, k], scores [B_g, k]) with padding removed.
    """
    if path == "hnsw":
        # graph-path lanes never reach the stacked GEMM: the service's
        # fused submit serves them per-lane inside one task (a host-side
        # beam search has nothing to stack) — reaching here is a routing
        # bug, not a shape problem, so fail loudly instead of mis-scanning
        raise ValueError("execute_group cannot stack path='hnsw' lanes; "
                         "the service dispatches graph-path groups per-lane")
    lanes = [jnp.atleast_2d(jnp.asarray(q, jnp.float32)) for q in queries]
    sizes = [int(q.shape[0]) for q in lanes]
    bmax = max(sizes)
    padded = jnp.stack([
        jnp.pad(q, ((0, bmax - q.shape[0]), (0, 0))) for q in lanes])
    if cache is not None:
        stacked = cache.stacked(collections, mesh)
    else:
        snaps = [c.snapshot() for c in collections]
        for c, s in zip(collections, snaps):
            if s is None:                 # demoted off-device mid-window
                raise NotResident(c.name)
        stacked = _stack(snaps, mesh)
    for c, b in zip(collections, sizes):
        c._bump_queries(b, path)
    if mesh is not None:
        from repro.core import distributed as dce
        fn, args = dce.dist_fused_query_stacked, (stacked, padded, cfg, mesh,
                                                  k, nprobe, path)
    else:
        fn, args = fused_query, (stacked, padded, cfg, k, nprobe, path)
    with tracing.device(fn):
        ids, scores = fn(*args)
        ids, scores = np.asarray(ids), np.asarray(scores)
    return [(ids[g, :b], scores[g, :b]) for g, b in enumerate(sizes)]


def demux(entries, results) -> None:
    """Resolve each pending op's future from its lane slice.

    entries: per-lane lists of (future, start, stop) row spans
    results: per-lane (ids, scores) from `execute_group`

    Thread-safe by construction: the numpy results are owned by the calling
    worker, each future is settled exactly once (`_set_result` publishes the
    value before setting the event other threads wait on), and no locks are
    taken — a waiter racing a concurrent rebuild of the queried collection
    sees either this dispatch's snapshot results or nothing yet, never a
    torn value.
    """
    for lane_entries, (ids, scores) in zip(entries, results):
        for fut, start, stop in lane_entries:
            fut._set_result((ids[start:stop], scores[start:stop]))
