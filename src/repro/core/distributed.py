"""Distributed agentic memory — the engine sharded over a TPU mesh.

Beyond-paper (DESIGN.md §2): AME is single-device; we scale the same design
to pods.  Partitioning: every device owns an equal slice of *every* IVF
list's slots (lists sharded along the slot axis), plus its own spill buffer.
Centroids are replicated.  Consequences:

  * query  — each device scans its slice with the fused kernel, takes a
             local top-k, and a tiny all-gather of k candidates per device
             merges globally (the paper's host-side top-k aggregation, made
             hierarchical).
  * fused query — G mesh-sharded collections with same-signature pending
             query lanes answer in ONE dispatch: each device stacks its G
             shard-local blocks lane-wise ([G, rows/shard, …]) inside
             `shard_map` and runs the vmapped scan + batched hierarchical
             merge (`dist_fused_query` — the cross-collection batching
             layer's sharded backend, see `repro.api.batch`).
  * insert — batch rows are routed block-wise to devices (shard s takes the
             contiguous block [s*B/S, (s+1)*B/S) — the per-shard delta-log
             replay relies on exactly this placement); assignment is local
             GEMM (centroids replicated), packing is local.
  * build  — distributed k-means: local assign + local one-hot-GEMM
             partial sums, `psum` over the mesh, identical centroid update
             everywhere.  Collective volume per iteration is O(C*D), not
             O(N*D).
  * delete — tombstoning is embarrassingly shard-local: every shard masks
             the requested ids out of its own slots (no collectives).
  * rebuild / replay — *shard-local maintenance*: a rebuild compacts ONE
             shard's slice (reassign its live rows against the replicated
             centroids, repack, drain its spill) while every other shard's
             arrays pass through untouched, so one hot shard's maintenance
             never stalls its siblings.  Centroids are deliberately kept
             fixed: re-clustering locally would break the replication
             invariant that insert routing and the probed path rely on —
             a full re-cluster is `dist_build` (the bulk-build template).
             Delta replay mirrors the single-shard `ivf.DeltaOp`/`replay`
             protocol, applied to the rebuilt shard only.

Inside `shard_map` every device sees a plain `IVFState`, so the entire
single-device functional core is reused verbatim.  The host-side helpers at
the bottom (`split_host` / `assemble_host` / `reshard_host`) convert between
the global sharded layout and per-shard local states for persistence.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import EngineConfig
from repro.core import index as ivf
from repro.kernels import ops


def _shard_axes(mesh: Mesh) -> Tuple[str, ...]:
    """All mesh axes shard the DB (engine rows want every chip)."""
    return tuple(mesh.axis_names)


def _shard_index(mesh: Mesh) -> jax.Array:
    """Linear shard id of the executing device, row-major over mesh axes.

    Matches the block order `P(axes...)` uses when several axes shard one
    array dimension (first axis is major), so shard `i` here owns slab `i`
    of every sharded leaf in `state_specs`.
    """
    idx = jnp.zeros((), jnp.int32)
    for name in mesh.axis_names:
        idx = idx * mesh.shape[name] + jax.lax.axis_index(name)
    return idx


def state_specs(mesh: Mesh, quantized: bool = False) -> ivf.IVFState:
    """PartitionSpecs for a distributed IVFState.

    The quantized store shards exactly like its f32 counterpart: codes along
    the slot axis, per-list scalars stacked per shard (the `list_sizes`
    pattern), the per-row spill sidebands along the spill axis.  `quantized`
    must match the state's treedef — a None leaf takes no spec.
    """
    ax = _shard_axes(mesh)
    specs = ivf.IVFState(
        centroids=P(),                 # replicated
        lists=P(None, ax, None),       # slot axis sharded
        list_ids=P(None, ax),
        list_sizes=P(ax),              # stacked per-shard rows: [S*C] -> local [C]
        spill=P(ax, None),
        spill_ids=P(ax),
        spill_size=P(ax),
        num_deleted=P(ax),
    )
    if quantized:
        specs = specs._replace(
            q_lists=P(None, ax, None),
            q_scales=P(ax),            # stacked per-shard per-list: [S*C]
            q_zeros=P(ax),
            q_norms=P(None, ax),       # per-slot, alongside list_ids
            q_spill=P(ax, None),
            q_spill_scales=P(ax),
            q_spill_zeros=P(ax),
            q_spill_norms=P(ax),
        )
    return specs


def state_shardings(mesh: Mesh, quantized: bool = False) -> ivf.IVFState:
    """`state_specs` as `NamedSharding`s on `mesh` (placement targets)."""
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                        state_specs(mesh, quantized))


def empty_dist_state(cfg: EngineConfig, mesh: Mesh,
                     spill_capacity_per_shard: int = 4096) -> ivf.IVFState:
    """Global arrays for the sharded state (local view == IVFState), each
    device allocating only its own slab."""
    return jax.jit(functools.partial(_empty_dist_state, cfg, mesh.size,
                                     spill_capacity_per_shard),
                   out_shardings=state_shardings(mesh, cfg.quantized))()


def _empty_dist_state(cfg: EngineConfig, s: int, sc: int) -> ivf.IVFState:
    c, l, d = cfg.n_clusters, cfg.list_capacity, cfg.dim
    st = ivf.IVFState(
        centroids=jnp.zeros((c, d), jnp.float32),
        lists=jnp.zeros((c, l * s, d), jnp.float32),
        list_ids=jnp.full((c, l * s), -1, jnp.int32),
        list_sizes=jnp.zeros((s * c,), jnp.int32),
        spill=jnp.zeros((s * sc, d), jnp.float32),
        spill_ids=jnp.full((s * sc,), -1, jnp.int32),
        spill_size=jnp.zeros((s,), jnp.int32),
        num_deleted=jnp.zeros((s,), jnp.int32),
    )
    if cfg.quantized:
        st = st._replace(
            q_lists=jnp.zeros((c, l * s, d), jnp.int8),
            q_scales=jnp.ones((s * c,), jnp.float32),
            q_zeros=jnp.zeros((s * c,), jnp.float32),
            q_norms=jnp.zeros((c, l * s), jnp.float32),
            q_spill=jnp.zeros((s * sc, d), jnp.int8),
            q_spill_scales=jnp.ones((s * sc,), jnp.float32),
            q_spill_zeros=jnp.zeros((s * sc,), jnp.float32),
            q_spill_norms=jnp.zeros((s * sc,), jnp.float32),
        )
    return st


def _local(state: ivf.IVFState) -> ivf.IVFState:
    """Normalize the shard-local view to a plain IVFState (squeeze scalars)."""
    return state._replace(spill_size=state.spill_size[0],
                          num_deleted=state.num_deleted[0])


def _unlocal(state: ivf.IVFState) -> ivf.IVFState:
    return state._replace(spill_size=state.spill_size[None],
                          num_deleted=state.num_deleted[None])


# ---------------------------------------------------------------------------
# Distributed k-means + build
# ---------------------------------------------------------------------------

def dist_build(key, x, ids, cfg: EngineConfig, mesh: Mesh,
               spill_capacity_per_shard: int = 4096):
    """Build over globally-sharded rows x f32[N, D] (N sharded over the mesh)."""
    ax = _shard_axes(mesh)

    n_shards = mesh.size

    def _build(seed_loc, x_loc, ids_loc):
        valid = ids_loc >= 0
        # ---- distributed k-means (shared centroids via psum) ----
        m = x_loc.shape[0]
        key = jax.random.key(seed_loc[0])
        k0, key = jax.random.split(key)
        # seed: local gumbel-top-k candidates, gathered then truncated
        g = jax.random.gumbel(k0, (m,)) + jnp.where(valid, 0.0, -1e30)
        nseed = max(cfg.n_clusters // n_shards, 1)
        _, si = jax.lax.top_k(g, nseed)
        seeds = jax.lax.all_gather(x_loc[si], ax, tiled=True)
        centroids = seeds[: cfg.n_clusters]
        if centroids.shape[0] < cfg.n_clusters:
            reps = -(-cfg.n_clusters // centroids.shape[0])
            centroids = jnp.tile(centroids, (reps, 1))[: cfg.n_clusters]

        def step(cent, key_i):
            idx, _ = ops.kmeans_assign(
                x_loc, cent, use_kernel=cfg.use_kernel,
                fused_conversion=cfg.fused_conversion)
            idx = jnp.where(valid, idx, -1)
            sums, counts = ops.segsum_gemm(
                x_loc, idx, n_clusters=cfg.n_clusters,
                use_kernel=cfg.use_kernel)
            sums = jax.lax.psum(sums, ax)        # O(C*D) collective
            counts = jax.lax.psum(counts, ax)
            new = sums / jnp.maximum(counts, 1.0)[:, None]
            new = jnp.where((counts > 0)[:, None], new, cent)
            if cfg.metric == "ip":
                new = new / jnp.maximum(
                    jnp.linalg.norm(new, axis=1, keepdims=True), 1e-6)
            return new, None

        centroids, _ = jax.lax.scan(
            step, centroids, jax.random.split(key, cfg.kmeans_iters))

        # ---- local pack into this shard's slots ----
        idx, _ = ops.kmeans_assign(
            x_loc, centroids, use_kernel=cfg.use_kernel,
            fused_conversion=cfg.fused_conversion)
        idx = jnp.where(valid, idx, -1)
        st = ivf.empty_state(cfg, spill_capacity_per_shard)
        st = st._replace(centroids=centroids)
        st, spilled = ivf._pack(st, x_loc, ids_loc, idx, cfg)
        return _unlocal(st), spilled[None]

    specs = state_specs(mesh, cfg.quantized)
    fn = shard_map(
        _build, mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax)),
        out_specs=(specs, P(ax)),
        check_vma=False,
    )
    base = int(jax.random.randint(key, (), 0, 2**31 - 1))
    seeds = (base + jnp.arange(mesh.size, dtype=jnp.int32)) % (2**31 - 1)
    rows = NamedSharding(mesh, P(ax))
    return fn(seeds, jax.device_put(x, rows), jax.device_put(ids, rows))


# ---------------------------------------------------------------------------
# Distributed query
# ---------------------------------------------------------------------------

# The shard_map-wrapped callables below are memoized per (mesh, cfg, ...):
# jax keys its trace/compile cache on the wrapped function object, so
# re-wrapping on every call would re-trace every dispatch — painful on the
# maintenance path, which replays many small ops while the collection holds
# its writer lock.  Meshes and EngineConfigs are hashable and few.

@functools.lru_cache(maxsize=None)
def _query_fn(mesh: Mesh, cfg: EngineConfig, k: int):
    ax = _shard_axes(mesh)

    def _query(state_loc, q_loc):
        st = _local(state_loc)
        ids_l, sc_l = ivf.query_full_scan(st, q_loc, cfg, k)
        ids_g = jax.lax.all_gather(ids_l, ax, axis=1, tiled=True)   # [B, S*k]
        sc_g = jax.lax.all_gather(sc_l, ax, axis=1, tiled=True)
        top, pos = jax.lax.top_k(sc_g, k)
        return jnp.take_along_axis(ids_g, pos, axis=1), top

    return shard_map(
        _query, mesh=mesh,
        in_specs=(state_specs(mesh, cfg.quantized), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )


def dist_query(state: ivf.IVFState, q, cfg: EngineConfig, mesh: Mesh, k: int):
    """Query q f32[B, D] (replicated) -> (ids i32[B,k], scores f32[B,k]).

    Local fused-scan top-k per shard, then one small all-gather of k
    candidates per shard and a final top-k — hierarchical merge.
    """
    return _query_fn(mesh, cfg, k)(state, q)


# ---------------------------------------------------------------------------
# Fused cross-collection query (lanes × shards)
# ---------------------------------------------------------------------------

def _stacked_specs(mesh: Mesh, quantized: bool = False) -> ivf.IVFState:
    """PartitionSpecs for a lane-stacked distributed state: every leaf of
    `state_specs` gains a leading (replicated) G axis — shards keep their
    slot-axis slices, so each device holds a [G, rows/shard, …] stack."""
    return jax.tree.map(lambda sp: P(None, *sp),
                        state_specs(mesh, quantized))


@functools.lru_cache(maxsize=None)
def _stack_fn(mesh: Mesh, g: int, quantized: bool):
    specs = state_specs(mesh, quantized)

    def _stk(*states_loc):
        # Lane-wise stack of the G shard-local states, ON DEVICE: inside
        # shard_map each `states_loc[i]` is collection i's local IVFState,
        # so this stack builds the [G, rows/shard, …] layout per device —
        # no host gather, no cross-device traffic.
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states_loc)

    return shard_map(
        _stk, mesh=mesh,
        in_specs=(specs,) * g,
        out_specs=_stacked_specs(mesh, quantized),
        check_vma=False,
    )


def dist_stack_states(states: Sequence[ivf.IVFState],
                      mesh: Mesh) -> ivf.IVFState:
    """Stack G same-shaped globally-sharded states lane-wise, per device.

    The sharded analogue of `repro.api.batch.stack_states`: the result's
    leaves carry a leading G axis while staying sharded exactly as before
    (`_stacked_specs`), so the stack is G local copies per device and zero
    collectives.  The fusion layer's stack cache reuses the result across
    dispatches while every lane's version is unchanged — query-heavy
    windows then skip the copy entirely.
    """
    return _stack_fn(mesh, len(states), states[0].quantized)(*states)


@functools.lru_cache(maxsize=None)
def _fused_query_fn(mesh: Mesh, cfg: EngineConfig, k: int,
                    nprobe: int, path: str):
    """Memoized like `_query_fn`, keyed per (mesh, cfg, k, nprobe, path);
    the lane count G is carried by the stacked operand's leading axis (a
    new G only re-traces, it does not re-wrap).

    `nprobe`/`path` are part of the key for signature unity with the
    batching layer (`Collection.batch_signature` groups pending lanes by
    the resolved query triple) even though the sharded tier — exactly like
    the per-op `dist_query` it must match bitwise — always serves queries
    via the local full scan + hierarchical merge.
    """
    ax = _shard_axes(mesh)

    def _fq(q_loc, stacked_loc):
        def one(state, qi):
            return ivf.query_full_scan(_local(state), qi, cfg, k)

        ids_l, sc_l = jax.vmap(one)(stacked_loc, q_loc)            # [G, B, k]
        # same hierarchical merge as `dist_query`, batched over lanes:
        # k candidates per shard per lane, one small all-gather, final top-k
        ids_g = jax.lax.all_gather(ids_l, ax, axis=2, tiled=True)  # [G, B, S*k]
        sc_g = jax.lax.all_gather(sc_l, ax, axis=2, tiled=True)
        top, pos = jax.lax.top_k(sc_g, k)
        return jnp.take_along_axis(ids_g, pos, axis=2), top

    return shard_map(
        _fq, mesh=mesh,
        in_specs=(P(), _stacked_specs(mesh, cfg.quantized)),
        out_specs=(P(), P()),
        check_vma=False,
    )


def dist_fused_query_stacked(stacked: ivf.IVFState, q, cfg: EngineConfig,
                             mesh: Mesh, k: int, nprobe: int, path: str):
    """ONE dispatch answering G sharded collections' query lanes at once.

    stacked: a `dist_stack_states` result — every leaf carries a leading G
             axis over same-shaped globally-sharded `IVFState`s (same mesh,
             same `EngineConfig` shapes — the batch signature guarantees
             this; the stack cache may reuse it across dispatches)
    q:       f32[G, Bmax, D] padded per-lane query batches (replicated)
    Returns (ids i32[G, Bmax, k], scores f32[G, Bmax, k]).

    This is the lanes × shards generalization of the fusion invariant: the
    per-device compute is a vmapped full scan over a [G, rows/shard, …]
    stack of the collections' shard-local blocks, so lane `g` only ever
    scans collection `g`'s rows, and the hierarchical candidate merge is
    batched over lanes inside the same `shard_map`.  Bitwise-equivalent to
    G separate `dist_query` calls (asserted by tests/test_batch_fusion.py),
    for one dispatch instead of G.
    """
    return _fused_query_fn(mesh, cfg, k, nprobe, path)(q, stacked)


def dist_fused_query(states: Sequence[ivf.IVFState], q, cfg: EngineConfig,
                     mesh: Mesh, k: int, nprobe: int, path: str):
    """`dist_fused_query_stacked` over freshly-stacked states (uncached)."""
    return dist_fused_query_stacked(dist_stack_states(states, mesh), q,
                                    cfg, mesh, k, nprobe, path)


# ---------------------------------------------------------------------------
# Distributed insert
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _insert_fn(mesh: Mesh, cfg: EngineConfig):
    ax = _shard_axes(mesh)

    def _insert(state_loc, x_loc, ids_loc):
        st = _local(state_loc)
        st, spilled = ivf.insert(st, x_loc, ids_loc, cfg)
        return _unlocal(st), spilled[None]

    specs = state_specs(mesh, cfg.quantized)
    return shard_map(
        _insert, mesh=mesh,
        in_specs=(specs, P(ax), P(ax)),
        out_specs=(specs, P(ax)),
        check_vma=False,
    )


def dist_insert(state: ivf.IVFState, x, ids, cfg: EngineConfig, mesh: Mesh):
    """Insert x f32[B, D]; B must divide by the mesh size — shard s takes
    the contiguous block [s*B/S, (s+1)*B/S) (the per-shard delta-log replay
    in `repro.api.collection` relies on this block placement).  Returns
    (state, spilled i32[S]) with the per-shard spill counts."""
    return _insert_fn(mesh, cfg)(state, x, ids)


# ---------------------------------------------------------------------------
# Distributed delete (shard-local tombstoning)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _delete_fn(mesh: Mesh, quantized: bool):
    ax = _shard_axes(mesh)

    def _del(state_loc, ids_loc):
        st = _local(state_loc)
        st, n = ivf._delete(st, ids_loc)
        return _unlocal(st), n[None]

    specs = state_specs(mesh, quantized)
    return shard_map(
        _del, mesh=mesh,
        in_specs=(specs, P()),
        out_specs=(specs, P(ax)),
        check_vma=False,
    )


def dist_delete(state: ivf.IVFState, ids, mesh: Mesh
                ) -> Tuple[ivf.IVFState, jax.Array]:
    """Tombstone external `ids` i32[B] (replicated) on every shard.

    Purely shard-local — each device masks the ids out of its own list/spill
    slots, no collectives.  Returns (state, n_hit i32[S]): the per-shard
    count of slots actually tombstoned, so callers can account maintenance
    pressure *per shard* (the whole point of shard-local rebuild scheduling).
    """
    return _delete_fn(mesh, state.quantized)(state, ids)


# ---------------------------------------------------------------------------
# Shard-local rebuild (compaction) + delta replay
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _rebuild_fn(mesh: Mesh, cfg: EngineConfig):
    ax = _shard_axes(mesh)

    def _rb(state_loc, shard_t):
        st = _local(state_loc)
        me = _shard_index(mesh)

        def compact(st):
            rows, ids = ivf._flat_rows(st)
            idx, _ = ops.kmeans_assign(
                rows, st.centroids, use_kernel=cfg.use_kernel,
                fused_conversion=cfg.fused_conversion)
            idx = jnp.where(ids >= 0, idx, -1)
            fresh = ivf.empty_state(cfg, st.spill.shape[0])._replace(
                centroids=st.centroids)
            fresh, spilled = ivf._pack(fresh, rows, ids, idx, cfg)
            return fresh, spilled.astype(jnp.int32)

        def keep(st):
            return st, jnp.zeros((), jnp.int32)

        sel = (shard_t[0] < 0) | (me == shard_t[0])
        st, spilled = jax.lax.cond(sel, compact, keep, st)
        return _unlocal(st), spilled[None]

    specs = state_specs(mesh, cfg.quantized)
    return shard_map(
        _rb, mesh=mesh,
        in_specs=(specs, P()),
        out_specs=(specs, P(ax)),
        check_vma=False,
    )


def dist_rebuild(state: ivf.IVFState, cfg: EngineConfig, mesh: Mesh,
                 shard: int = -1) -> Tuple[ivf.IVFState, jax.Array]:
    """Shard-local compaction rebuild.

    Shard `shard` (all shards when `shard < 0`) reassigns its live rows
    against the *existing replicated centroids*, repacks them into fresh
    lists, and drains its spill buffer — reclaiming tombstones without any
    collective and without touching sibling shards, whose arrays pass
    through bit-identical (`lax.cond` skips their compute entirely).

    Centroids are intentionally NOT re-fit here: a shard-local k-means would
    fork the replicated centroids and corrupt global insert routing.  Full
    re-clustering is a bulk `dist_build`.

    Returns (state, spilled i32[S]); `spilled[i]` is rows shard `i` could
    not place (still in its spill buffer) — zeros for untouched shards.
    """
    return _rebuild_fn(mesh, cfg)(state, jnp.asarray([shard], jnp.int32))


@functools.lru_cache(maxsize=None)
def _adopt_fn(mesh: Mesh, quantized: bool):
    def _sel(cur_loc, reb_loc, shard_t):
        take = _shard_index(mesh) == shard_t[0]
        return jax.tree.map(lambda a, b: jnp.where(take, b, a),
                            cur_loc, reb_loc)

    specs = state_specs(mesh, quantized)
    return shard_map(
        _sel, mesh=mesh,
        in_specs=(specs, specs, P()),
        out_specs=specs,
        check_vma=False,
    )


def dist_adopt_shard(current: ivf.IVFState, rebuilt: ivf.IVFState,
                     shard: int, mesh: Mesh) -> ivf.IVFState:
    """Merge a shard-local rebuild into the live state.

    Shard `shard` takes its slice of `rebuilt`; every sibling keeps its
    slice of `current` (which, under the collection's writer lock, already
    contains all writes that landed during the off-lock recompute).  This is
    the sharded analogue of the single-shard rebuild's snapshot swap.
    """
    return _adopt_fn(mesh, current.quantized)(
        current, rebuilt, jnp.asarray([shard], jnp.int32))


@functools.lru_cache(maxsize=None)
def _replay_fns(mesh: Mesh, cfg: EngineConfig):
    ax = _shard_axes(mesh)
    specs = state_specs(mesh, cfg.quantized)

    def _ins(state_loc, shard_t, rows, ids):
        st = _local(state_loc)

        def do(st):
            st2, sp = ivf._insert(st, rows, ids, cfg)
            return st2, sp.astype(jnp.int32)

        def keep(st):
            return st, jnp.zeros((), jnp.int32)

        st, sp = jax.lax.cond(_shard_index(mesh) == shard_t[0], do, keep, st)
        return _unlocal(st), sp[None]

    def _del(state_loc, shard_t, ids):
        st = _local(state_loc)

        def do(st):
            return ivf._delete(st, ids)

        def keep(st):
            return st, jnp.zeros((), jnp.int32)

        st, n = jax.lax.cond(_shard_index(mesh) == shard_t[0], do, keep, st)
        return _unlocal(st), n[None]

    ins_fn = shard_map(_ins, mesh=mesh, in_specs=(specs, P(), P(), P()),
                       out_specs=(specs, P(ax)), check_vma=False)
    del_fn = shard_map(_del, mesh=mesh, in_specs=(specs, P(), P()),
                       out_specs=(specs, P(ax)), check_vma=False)
    return ins_fn, del_fn


def dist_replay(state: ivf.IVFState, log: Sequence[ivf.DeltaOp], shard: int,
                cfg: EngineConfig, mesh: Mesh
                ) -> Tuple[ivf.IVFState, int, int]:
    """Re-apply a per-shard delta log onto shard `shard` only.

    Mirrors the single-shard `ivf.replay` protocol: ops are applied in log
    order before the rebuilt state is published.  Insert ops carry the
    *shard-local* row slice the collection logged for this shard (the same
    rows `dist_insert` routed there); delete ops carry the full id list and
    tombstone whatever of it lives on this shard.  Sibling shards pass
    through untouched.

    Returns (state, n_spilled, n_tombstoned) for the replayed shard — both
    still pending in the replayed state, so per-shard maintenance pressure
    accounting stays truthful.
    """
    ins_fn, del_fn = _replay_fns(mesh, cfg)
    shard_t = jnp.asarray([shard], jnp.int32)
    spilled, tombstoned = [], []
    for op in log:
        if op.kind == "insert":
            state, sp = ins_fn(state, shard_t, op.rows, op.ids)
            spilled.append(sp)
        elif op.kind == "delete":
            state, n = del_fn(state, shard_t, op.ids)
            tombstoned.append(n)
        else:
            raise ValueError(f"unknown delta op kind {op.kind!r}")
    # The per-shard counts are sharded over the mesh; indexing one entry on
    # the device is a gather JAX will not place on an Explicit mesh axis, so
    # read them back in one transfer and pick this shard's entry on the host.
    spilled, tombstoned = jax.device_get((spilled, tombstoned))
    return (state, sum(int(sp[shard]) for sp in spilled),
            sum(int(n[shard]) for n in tombstoned))


# ---------------------------------------------------------------------------
# Host-side shard layout helpers (persistence / elastic reshard)
# ---------------------------------------------------------------------------

def split_host(state: ivf.IVFState, n_shards: int) -> List[ivf.IVFState]:
    """Global sharded state -> per-shard local `IVFState`s on host (numpy).

    Inverts the `state_specs` layout: slab `i` of every sharded leaf is
    shard `i`'s local view.  Used by sharded persistence, which writes one
    checkpoint namespace per shard.
    """
    g = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), state)
    c = g.centroids.shape[0]
    l = g.lists.shape[1] // n_shards
    sc = g.spill.shape[0] // n_shards
    out = []
    for i in range(n_shards):
        st = ivf.IVFState(
            centroids=g.centroids,
            lists=g.lists[:, i * l:(i + 1) * l, :],
            list_ids=g.list_ids[:, i * l:(i + 1) * l],
            list_sizes=g.list_sizes[i * c:(i + 1) * c],
            spill=g.spill[i * sc:(i + 1) * sc],
            spill_ids=g.spill_ids[i * sc:(i + 1) * sc],
            spill_size=g.spill_size[i:i + 1].reshape(()),
            num_deleted=g.num_deleted[i:i + 1].reshape(()),
        )
        if g.q_lists is not None:
            st = st._replace(
                q_lists=g.q_lists[:, i * l:(i + 1) * l, :],
                q_scales=g.q_scales[i * c:(i + 1) * c],
                q_zeros=g.q_zeros[i * c:(i + 1) * c],
                q_norms=g.q_norms[:, i * l:(i + 1) * l],
                q_spill=g.q_spill[i * sc:(i + 1) * sc],
                q_spill_scales=g.q_spill_scales[i * sc:(i + 1) * sc],
                q_spill_zeros=g.q_spill_zeros[i * sc:(i + 1) * sc],
                q_spill_norms=g.q_spill_norms[i * sc:(i + 1) * sc],
            )
        out.append(st)
    return out


def assemble_host(shards: Sequence[ivf.IVFState],
                  mesh: Mesh) -> ivf.IVFState:
    """Per-shard local states -> the global state in `state_specs` layout,
    placed on `mesh`: device `i` receives shard `i`'s slab only (never the
    whole state on the first device)."""
    def cat(name, axis):
        return np.concatenate([np.asarray(getattr(s, name)) for s in shards],
                              axis=axis)

    def stack(name):
        return np.stack([np.asarray(getattr(s, name)).reshape(())
                         for s in shards])

    st = ivf.IVFState(
        centroids=np.asarray(shards[0].centroids),
        lists=cat("lists", 1), list_ids=cat("list_ids", 1),
        list_sizes=cat("list_sizes", 0), spill=cat("spill", 0),
        spill_ids=cat("spill_ids", 0), spill_size=stack("spill_size"),
        num_deleted=stack("num_deleted"),
    )
    quantized = shards[0].q_lists is not None
    if quantized:
        st = st._replace(
            q_lists=cat("q_lists", 1), q_scales=cat("q_scales", 0),
            q_zeros=cat("q_zeros", 0), q_norms=cat("q_norms", 1),
            q_spill=cat("q_spill", 0), q_spill_scales=cat("q_spill_scales", 0),
            q_spill_zeros=cat("q_spill_zeros", 0),
            q_spill_norms=cat("q_spill_norms", 0),
        )
    return jax.device_put(st, state_shardings(mesh, quantized))


def reshard_host(shards: Sequence[ivf.IVFState], cfg: EngineConfig,
                 n_new: int, spill_capacity: int) -> List[ivf.IVFState]:
    """Re-pack saved per-shard states for a different shard count.

    Host-side elastic reshard for load: gathers every live row from the
    saved shards, deals them round-robin into `n_new` groups, and re-packs
    each group against the saved (replicated) centroids with the ordinary
    single-shard insert kernel.  Deterministic given the saved centroids;
    rows that overflow a group's lists land in its spill buffer (rows past
    spill capacity are dropped, same as live-insert semantics).
    """
    rows_all, ids_all = [], []
    for st in shards:
        rows = np.concatenate(
            [np.asarray(st.lists).reshape(-1, st.centroids.shape[1]),
             np.asarray(st.spill)], axis=0)
        ids = np.concatenate([np.asarray(st.list_ids).reshape(-1),
                              np.asarray(st.spill_ids)], axis=0)
        live = ids >= 0
        rows_all.append(rows[live])
        ids_all.append(ids[live])
    rows = np.concatenate(rows_all, axis=0)
    ids = np.concatenate(ids_all, axis=0)
    centroids = jnp.asarray(shards[0].centroids)
    out = []
    for i in range(n_new):
        st = ivf.empty_state(cfg, spill_capacity)._replace(centroids=centroids)
        chunk_rows, chunk_ids = rows[i::n_new], ids[i::n_new]
        if len(chunk_ids):
            st, _ = ivf.insert_shared(st, jnp.asarray(chunk_rows),
                                      jnp.asarray(chunk_ids, jnp.int32), cfg)
        out.append(st)
    return out
