"""Tile-aligned IVF index — AME's hardware-aware vector index on TPU.

Functional core: the index is an `IVFState` pytree of statically-shaped
arrays; every operation is a pure jittable function.  Layout (DESIGN.md §3):

  centroids  : f32[C, D]        C % 128 == 0, D % 128 == 0 (MXU lane tiles)
  lists      : f32[C, L, D]     dense padded lists, L % 8 == 0 (fp32 sublane)
  list_ids   : i32[C, L]        external ids; -1 = empty/tombstoned slot
  list_sizes : i32[C]           high-water marks (tombstones not reclaimed
                                until rebuild, as in the paper's maintenance)
  spill_*    :                  fixed-capacity overflow buffer for rows whose
                                target list is full; drained at rebuild

There is no pointer-chasing anywhere: queries, inserts, and rebuilds are all
GEMM-shaped (the paper's core refactor), and the dense layout means gathers
of probed lists are contiguous DMA streams, not random probes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EngineConfig
from repro.kernels import ops


class IVFState(NamedTuple):
    """IVF index pytree.  The eight required fields are the exact f32 tier.

    The optional ``q_*`` tail is the int8 quantized scan store (present iff
    the collection's ``EngineConfig.store_dtype == "int8"``): affine per-
    list codes for the lists tier, per-row codes for the spill tier, plus
    precomputed dequantized-row norms (so L2 queries never touch the f32
    rows during the coarse scan).  ``None`` fields are empty pytree
    subtrees, so every tree-shaped operation (stacking, vmap, shard_map
    specs, checkpoint flatten) works unchanged for both policies — but the
    two policies have different treedefs, which is exactly what keeps them
    in separate jit caches and separate fusion groups.
    """
    centroids: jax.Array      # f32[C, D]
    lists: jax.Array          # f32[C, L, D]
    list_ids: jax.Array       # i32[C, L]
    list_sizes: jax.Array     # i32[C]
    spill: jax.Array          # f32[S, D]
    spill_ids: jax.Array      # i32[S]
    spill_size: jax.Array     # i32[]
    num_deleted: jax.Array    # i32[]
    # --- optional int8 quantized scan store (store_dtype == "int8") ---
    q_lists: Optional[jax.Array] = None         # i8[C, L, D]
    q_scales: Optional[jax.Array] = None        # f32[C] per-list scale
    q_zeros: Optional[jax.Array] = None         # f32[C] per-list zero-point
    q_norms: Optional[jax.Array] = None         # f32[C, L] dequant row norms
    q_spill: Optional[jax.Array] = None         # i8[S, D]
    q_spill_scales: Optional[jax.Array] = None  # f32[S] per-row scale
    q_spill_zeros: Optional[jax.Array] = None   # f32[S] per-row zero-point
    q_spill_norms: Optional[jax.Array] = None   # f32[S] dequant row norms

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def dim(self) -> int:
        return self.centroids.shape[1]

    @property
    def list_capacity(self) -> int:
        return self.lists.shape[1]

    @property
    def quantized(self) -> bool:
        return self.q_lists is not None


def empty_state(cfg: EngineConfig, spill_capacity: int = 4096) -> IVFState:
    c, l, d = cfg.n_clusters, cfg.list_capacity, cfg.dim
    state = IVFState(
        centroids=jnp.zeros((c, d), jnp.float32),
        lists=jnp.zeros((c, l, d), jnp.float32),
        list_ids=jnp.full((c, l), -1, jnp.int32),
        list_sizes=jnp.zeros((c,), jnp.int32),
        spill=jnp.zeros((spill_capacity, d), jnp.float32),
        spill_ids=jnp.full((spill_capacity,), -1, jnp.int32),
        spill_size=jnp.zeros((), jnp.int32),
        num_deleted=jnp.zeros((), jnp.int32),
    )
    if cfg.quantized:
        state = state._replace(
            q_lists=jnp.zeros((c, l, d), jnp.int8),
            q_scales=jnp.ones((c,), jnp.float32),
            q_zeros=jnp.zeros((c,), jnp.float32),
            q_norms=jnp.zeros((c, l), jnp.float32),
            q_spill=jnp.zeros((spill_capacity, d), jnp.int8),
            q_spill_scales=jnp.ones((spill_capacity,), jnp.float32),
            q_spill_zeros=jnp.zeros((spill_capacity,), jnp.float32),
            q_spill_norms=jnp.zeros((spill_capacity,), jnp.float32),
        )
    return state


def empty_host_state(cfg: EngineConfig, spill_capacity: int = 4096) -> IVFState:
    """Numpy mirror of `empty_state` — no device allocation.

    Used as the restore template for the non-HOT residency tiers (a WARM or
    COLD collection must be loadable without touching the accelerator) and
    for analytic size accounting (`state_nbytes`)."""
    c, l, d = cfg.n_clusters, cfg.list_capacity, cfg.dim
    state = IVFState(
        centroids=np.zeros((c, d), np.float32),
        lists=np.zeros((c, l, d), np.float32),
        list_ids=np.full((c, l), -1, np.int32),
        list_sizes=np.zeros((c,), np.int32),
        spill=np.zeros((spill_capacity, d), np.float32),
        spill_ids=np.full((spill_capacity,), -1, np.int32),
        spill_size=np.zeros((), np.int32),
        num_deleted=np.zeros((), np.int32),
    )
    if cfg.quantized:
        state = state._replace(
            q_lists=np.zeros((c, l, d), np.int8),
            q_scales=np.ones((c,), np.float32),
            q_zeros=np.zeros((c,), np.float32),
            q_norms=np.zeros((c, l), np.float32),
            q_spill=np.zeros((spill_capacity, d), np.int8),
            q_spill_scales=np.ones((spill_capacity,), np.float32),
            q_spill_zeros=np.zeros((spill_capacity,), np.float32),
            q_spill_norms=np.zeros((spill_capacity,), np.float32),
        )
    return state


def state_nbytes(cfg: EngineConfig, spill_capacity: int = 4096,
                 n_shards: int = 1) -> int:
    """Exact resident byte size of a collection state with these shapes.

    Equals `footprint(state)["index_bytes"]` without materializing any
    array — the shapes are static per (cfg, spill_capacity, shard count),
    so the residency budget can charge a collection before it exists on
    device.  A mesh-sharded global state replicates the centroids once and
    stacks every other leaf `n_shards` times (`distributed.empty_dist_state`
    layout: per-shard lists/spill slabs, per-shard scalar counters).
    """
    t = empty_host_state(cfg, spill_capacity)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(t))
    if n_shards == 1:
        return int(total)
    cent = t.centroids.nbytes
    return int(cent + n_shards * (total - cent))


def live_count(state: IVFState) -> jax.Array:
    return (jnp.sum(state.list_ids >= 0) + jnp.sum(state.spill_ids >= 0))


# ---------------------------------------------------------------------------
# Int8 quantized scan store (store_dtype == "int8")
#
# Affine quantization: row ~= scale * code + zero with codes in [-127, 127],
# scale/zero shared per IVF list (lists tier) or per row (spill tier).  The
# granularity matches the layout: a list is the contiguous slab one scan
# tile streams, so its scale/zero ride along as two scalars; spill rows
# have no slab structure, so they carry their own.  Round-trip error is
# bounded by scale/2 = (max-min)/508 per component (tested).  The f32 rows
# remain the source of truth — the quantized store is a derived coarse-scan
# stream, re-derived for exactly the slots each write touches.
# ---------------------------------------------------------------------------

def _affine_encode(x: jax.Array, axes: Tuple[int, ...]):
    """(codes i8, scale, zero) with x ~= scale*codes + zero over `axes`."""
    mn = jnp.min(x, axis=axes)
    mx = jnp.max(x, axis=axes)
    zero = 0.5 * (mn + mx)
    scale = jnp.maximum((mx - mn) / 254.0, 1e-8)
    sb = jnp.expand_dims(scale, axes)
    zb = jnp.expand_dims(zero, axes)
    codes = jnp.clip(jnp.round((x - zb) / sb), -127, 127).astype(jnp.int8)
    return codes, scale, zero


def _quantize_lists(lists: jax.Array, list_ids: jax.Array):
    """Per-list affine quantization of [..., L, D] slabs.

    Tombstoned/empty slots are masked to 0 for the range fit so stale row
    values cannot inflate a list's scale; their codes are garbage-free but
    irrelevant (every scan masks ids < 0).  Returns (codes, scale, zero,
    norms) where norms are the DEQUANTIZED row norms — precomputed here so
    L2 coarse scans order exactly like scanning the dequantized rows.
    """
    masked = jnp.where((list_ids >= 0)[..., None], lists, 0.0)
    codes, scale, zero = _affine_encode(masked, (-2, -1))
    deq = (codes.astype(jnp.float32) * scale[..., None, None]
           + zero[..., None, None])
    norms = jnp.sum(deq * deq, axis=-1)
    return codes, scale, zero, norms


def _quantize_rows(rows: jax.Array, ids: jax.Array):
    """Per-row affine quantization of [..., D] rows (the spill tier)."""
    masked = jnp.where((ids >= 0)[..., None], rows, 0.0)
    codes, scale, zero = _affine_encode(masked, (-1,))
    deq = codes.astype(jnp.float32) * scale[..., None] + zero[..., None]
    norms = jnp.sum(deq * deq, axis=-1)
    return codes, scale, zero, norms


def _quantize_state(state: IVFState) -> IVFState:
    """Full requantization of every tier (build / rebuild / pack time)."""
    ql, qs, qz, qn = _quantize_lists(state.lists, state.list_ids)
    sp, ss, sz, sn = _quantize_rows(state.spill, state.spill_ids)
    return state._replace(q_lists=ql, q_scales=qs, q_zeros=qz, q_norms=qn,
                          q_spill=sp, q_spill_scales=ss, q_spill_zeros=sz,
                          q_spill_norms=sn)


def _requantize_touched(state: IVFState, x: jax.Array, cl_w: jax.Array,
                        spos_w: jax.Array) -> IVFState:
    """Incremental coherence after an insert batch.

    Re-derives the quantized store for exactly what the scatter touched:
    the lists rows landed in (gather slab -> refit scale/zero -> scatter
    back; duplicate cluster hits write identical values, overflow rows'
    writes drop at the same OOB index the f32 scatter dropped at) and the
    spill rows that were appended (per-row encode at the same positions).
    Deletes need no counterpart: tombstoning only flips ids, and every
    scan — quantized or not — masks ids < 0.
    """
    c = state.n_clusters
    touched = jnp.clip(cl_w, 0, c - 1)
    codes, sc, zr, nrm = _quantize_lists(state.lists[touched],
                                         state.list_ids[touched])
    new = state._replace(
        q_lists=state.q_lists.at[cl_w].set(codes, mode="drop"),
        q_scales=state.q_scales.at[cl_w].set(sc, mode="drop"),
        q_zeros=state.q_zeros.at[cl_w].set(zr, mode="drop"),
        q_norms=state.q_norms.at[cl_w].set(nrm, mode="drop"),
    )
    scodes, ssc, szr, snrm = _quantize_rows(x, jnp.zeros(x.shape[0],
                                                         jnp.int32))
    return new._replace(
        q_spill=new.q_spill.at[spos_w].set(scodes, mode="drop"),
        q_spill_scales=new.q_spill_scales.at[spos_w].set(ssc, mode="drop"),
        q_spill_zeros=new.q_spill_zeros.at[spos_w].set(szr, mode="drop"),
        q_spill_norms=new.q_spill_norms.at[spos_w].set(snrm, mode="drop"),
    )


# ---------------------------------------------------------------------------
# Build
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "spill_capacity"))
def build(key: jax.Array, x: jax.Array, ids: jax.Array, cfg: EngineConfig,
          spill_capacity: int = 4096) -> Tuple["IVFState", jax.Array]:
    """Bulk-build an index over rows x f32[N, D] (ids i32[N]; -1 = ignore).

    k-means (GEMM kernels) -> pack rows into padded lists.  Returns
    (state, n_spilled).  Rows that overflow both their list and the spill
    buffer are dropped and counted.
    """
    from repro.core.kmeans import kmeans as _kmeans

    valid = ids >= 0
    # scope names reach the ops' metadata in a profiler trace
    with jax.named_scope("kmeans"):
        centroids, assign = _kmeans(key, x, valid, cfg)
    with jax.named_scope("pack"):
        state = empty_state(cfg, spill_capacity)._replace(centroids=centroids)
        return _pack(state, x, ids, assign, cfg)


def _pack(state: "IVFState", x: jax.Array, ids: jax.Array,
          assign: jax.Array, cfg: EngineConfig) -> Tuple["IVFState", jax.Array]:
    """Scatter assigned rows into padded lists; overflow goes to spill."""
    l_cap = state.list_capacity
    c = state.n_clusters
    cl = jnp.where(ids >= 0, assign, c + 1)        # invalid rows sort last
    rank = _batch_ranks(cl)
    offsets = state.list_sizes[jnp.clip(cl, 0, c - 1)] + rank
    ok = (ids >= 0) & (cl < c) & (offsets < l_cap)

    cl_w = jnp.where(ok, cl, c)
    lists = state.lists.at[cl_w, offsets].set(x, mode="drop")
    list_ids = state.list_ids.at[cl_w, offsets].set(ids, mode="drop")
    list_sizes = state.list_sizes + jnp.bincount(
        jnp.where(ok, cl, c), length=c + 1)[:c].astype(jnp.int32)

    over = (ids >= 0) & ~ok
    s_cap = state.spill.shape[0]
    spos = state.spill_size + jnp.cumsum(over) - 1
    s_ok = over & (spos < s_cap)
    spos_w = jnp.where(s_ok, spos, s_cap)
    spill = state.spill.at[spos_w].set(x, mode="drop")
    spill_ids = state.spill_ids.at[spos_w].set(ids, mode="drop")
    spill_size = jnp.minimum(state.spill_size + jnp.sum(over), s_cap)

    new = state._replace(lists=lists, list_ids=list_ids,
                         list_sizes=list_sizes, spill=spill,
                         spill_ids=spill_ids, spill_size=spill_size)
    if cfg.quantized:
        new = _quantize_state(new)
    return new, jnp.sum(over)


@functools.partial(jax.jit, static_argnames=("cfg",))
def rebuild(key: jax.Array, state: "IVFState",
            cfg: EngineConfig) -> Tuple["IVFState", jax.Array]:
    """Full rebuild: drain lists + spill, re-cluster, re-pack.

    Reclaims tombstoned slots and drains the spill buffer (the paper's
    'index template' operation — large, latency-insensitive, GEMM-heavy).
    """
    rows, ids = _flat_rows(state)
    return build(key, rows, ids, cfg, spill_capacity=state.spill.shape[0])


# ---------------------------------------------------------------------------
# Insert
# ---------------------------------------------------------------------------

def _batch_ranks(cl: jax.Array) -> jax.Array:
    """rank of row i among earlier batch rows assigned to the same cluster.

    Sort-based (O(B log B)): stable-sort by cluster, position within the
    cluster run is arange - run_start.
    """
    b = cl.shape[0]
    order = jnp.argsort(cl, stable=True)
    sorted_cl = cl[order]
    first = jnp.concatenate(
        [jnp.zeros((1,), bool), sorted_cl[1:] != sorted_cl[:-1]])
    run_start = jax.lax.cummax(jnp.where(first, jnp.arange(b), 0))
    pos = jnp.arange(b) - run_start
    return jnp.zeros((b,), jnp.int32).at[order].set(pos.astype(jnp.int32))


def _insert(state: IVFState, x: jax.Array, ids: jax.Array,
            cfg: EngineConfig) -> Tuple[IVFState, jax.Array]:
    """Insert rows x f32[B, D] with external ids i32[B].

    Assignment is the `kmeans_assign` GEMM kernel (the paper: inserts map to
    dense matmuls).  Returns (new_state, n_spilled_or_dropped i32[]).
    """
    b = x.shape[0]
    l_cap = state.list_capacity
    cl, _ = ops.kmeans_assign(
        x, state.centroids, use_kernel=cfg.use_kernel,
        fused_conversion=cfg.fused_conversion)

    rank = _batch_ranks(cl)
    offsets = state.list_sizes[cl] + rank
    fits = offsets < l_cap

    # in-list scatter (mode=drop discards non-fitting rows)
    cl_w = jnp.where(fits, cl, state.n_clusters)      # OOB row index => drop
    lists = state.lists.at[cl_w, offsets].set(x, mode="drop")
    list_ids = state.list_ids.at[cl_w, offsets].set(ids, mode="drop")
    list_sizes = state.list_sizes + jnp.bincount(
        jnp.where(fits, cl, state.n_clusters), length=state.n_clusters + 1
    )[: state.n_clusters].astype(jnp.int32)

    # overflow -> spill buffer
    over = ~fits
    s_cap = state.spill.shape[0]
    srank = jnp.cumsum(over) - 1
    spos = state.spill_size + srank
    s_ok = over & (spos < s_cap)
    spos_w = jnp.where(s_ok, spos, s_cap)
    spill = state.spill.at[spos_w].set(x, mode="drop")
    spill_ids = state.spill_ids.at[spos_w].set(ids, mode="drop")
    spill_size = jnp.minimum(state.spill_size + jnp.sum(over), s_cap)

    n_overflow = jnp.sum(over)
    new = state._replace(lists=lists, list_ids=list_ids,
                         list_sizes=list_sizes, spill=spill,
                         spill_ids=spill_ids, spill_size=spill_size)
    if cfg.quantized:
        new = _requantize_touched(new, x, cl_w, spos_w)
    return new, n_overflow


# `insert` donates the state buffer — updates are in place, the TPU analogue
# of the paper's zero-copy ION shared buffers.  Donation invalidates the old
# arrays, so it is ONLY safe when the caller is the state's sole owner: the
# delta-log replay onto a rebuilt state, a replica's shipped batch, a
# shard's local block.  `insert_shared` is the copying variant for states
# that concurrent readers (scheduler-routed queries) may still hold a
# snapshot of.  A trace names their device programs after the functions
# jitted: `jit_replay_insert` and `jit__insert`.
@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def replay_insert(state: IVFState, x: jax.Array, ids: jax.Array,
                  cfg: EngineConfig) -> Tuple[IVFState, jax.Array]:
    """Insert into a sole-owner state, donating its buffers."""
    return _insert(state, x, ids, cfg)


insert = replay_insert
insert_shared = functools.partial(jax.jit, static_argnames=("cfg",))(_insert)


# ---------------------------------------------------------------------------
# Delete (tombstoning)
# ---------------------------------------------------------------------------

def _delete(state: IVFState, ids: jax.Array) -> Tuple[IVFState, jax.Array]:
    """Tombstone `ids` i32[B]; slots are reclaimed at the next rebuild.

    Returns (new_state, n_hit i32[]) where n_hit counts the slots actually
    tombstoned — ids not present in the index contribute nothing, so callers
    tracking tombstone pressure stay truthful.
    """

    def _mask(haystack):
        hit = jnp.zeros(haystack.shape, bool)
        def body(i, hit):
            return hit | (haystack == ids[i])
        return jax.lax.fori_loop(0, ids.shape[0], body, hit)

    l_hit = _mask(state.list_ids)
    s_hit = _mask(state.spill_ids)
    n = (jnp.sum(l_hit) + jnp.sum(s_hit)).astype(jnp.int32)
    new = state._replace(
        list_ids=jnp.where(l_hit, -1, state.list_ids),
        spill_ids=jnp.where(s_hit, -1, state.spill_ids),
        num_deleted=state.num_deleted + n,
    )
    return new, n


# donating / copying split: same rationale, and trace names
# (`jit_replay_delete`, `jit__delete`), as insert / insert_shared above
@functools.partial(jax.jit, donate_argnums=(0,))
def replay_delete(state: IVFState, ids: jax.Array) -> Tuple[IVFState, jax.Array]:
    """Tombstone in a sole-owner state, donating its buffers."""
    return _delete(state, ids)


delete = replay_delete
delete_shared = jax.jit(_delete)


# ---------------------------------------------------------------------------
# Delta replay (lost-update-safe rebuilds)
# ---------------------------------------------------------------------------

class DeltaOp(NamedTuple):
    """One logged write applied to a collection since a rebuild snapshot.

    kind: "insert" | "delete".  For inserts `rows` is f32[B, D] and `ids`
    i32[B]; for deletes `rows` is None and `ids` the tombstoned ids.

    Ops are appended under the collection's writer lock, so log order is
    exactly state-application order — replaying the log onto a rebuilt
    snapshot reproduces the live state.  On a mesh-sharded collection each
    shard keeps its own log: insert ops there carry only the shard-local
    row slice (the rows `dist_insert` routed to that shard), delete ops
    the full id list (replay tombstones whatever of it the shard holds).
    """
    kind: str
    rows: Optional[jax.Array]
    ids: jax.Array


def replay(state: IVFState, log, cfg: EngineConfig) -> Tuple[IVFState, int, int]:
    """Re-apply a delta log (list of `DeltaOp`) in order to `state`.

    The caller must be the state's sole owner (e.g. the freshly rebuilt
    index before its swap): each step donates the previous state's buffers,
    so replay is in-place on device.  Returns (state, n_spilled,
    n_tombstoned): rows the replayed inserts pushed to the spill buffer,
    and slots the replayed deletes tombstoned — both still pending in the
    replayed state, so maintenance pressure accounting stays truthful.
    """
    # accumulate device scalars and sync once at the end: an int() per op
    # would cost one host round-trip per log entry while the caller holds
    # the writer lock
    spilled = jnp.zeros((), jnp.int32)
    tombstoned = jnp.zeros((), jnp.int32)
    for op in log:
        if op.kind == "insert":
            state, s = replay_insert(state, op.rows, op.ids, cfg)
            spilled = spilled + s
        elif op.kind == "delete":
            state, n = replay_delete(state, op.ids)
            tombstoned = tombstoned + n
        else:
            raise ValueError(f"unknown delta op kind {op.kind!r}")
    return state, int(spilled), int(tombstoned)


# ---------------------------------------------------------------------------
# Query
# ---------------------------------------------------------------------------

def _flat_rows(state: IVFState) -> Tuple[jax.Array, jax.Array]:
    c, l, d = state.lists.shape
    rows = jnp.concatenate(
        [state.lists.reshape(c * l, d), state.spill], axis=0)
    ids = jnp.concatenate(
        [state.list_ids.reshape(c * l), state.spill_ids], axis=0)
    return rows, ids


def flat_rows_host(state: IVFState) -> Tuple[np.ndarray, np.ndarray]:
    """Host (rows f32[N, D], ids[N]) view of every slot — list tier then
    spill.  ids < 0 mark empty/tombstoned slots; callers mask.  Always the
    exact f32 rows, even under the int8 policy (they stay the source of
    truth) — this is the flat view the recall probe's brute-force oracle
    and the derived HNSW graph build read from."""
    rows, ids = _flat_rows(state)
    return (np.asarray(jax.device_get(rows)),
            np.asarray(jax.device_get(ids)))


def _metric_norms(rows: jax.Array, metric: str) -> Optional[jax.Array]:
    if metric == "l2":
        return jnp.sum(rows.astype(jnp.float32) ** 2, axis=1)
    return None


def _order_scores(scores: jax.Array, metric: str) -> jax.Array:
    # top_k maximizes; L2 path returns distances (smaller better) -> negate
    return -scores if metric == "l2" else scores


# --- int8 asymmetric two-stage query (coarse quantized scan -> f32 rescore)

def _flat_codes(state: IVFState):
    """Quantized analogue of `_flat_rows`: the int8 coarse-scan stream with
    per-row-expanded scale/zero/norm sidebands (lists tier repeats its
    per-list scalars over L slots; the spill tier is already per-row)."""
    c, l, d = state.q_lists.shape
    codes = jnp.concatenate(
        [state.q_lists.reshape(c * l, d), state.q_spill], axis=0)
    scales = jnp.concatenate(
        [jnp.repeat(state.q_scales, l), state.q_spill_scales])
    zeros = jnp.concatenate(
        [jnp.repeat(state.q_zeros, l), state.q_spill_zeros])
    norms = jnp.concatenate(
        [state.q_norms.reshape(c * l), state.q_spill_norms])
    return codes, scales, zeros, norms


def _gather_flat_rows(state: IVFState, cand: jax.Array) -> jax.Array:
    """f32 rows for flat candidate indices [..., R] (lists first, then
    spill — `_flat_rows` order) WITHOUT materializing the flat copy: the
    rescore touches rescore_k rows per query, not the whole store."""
    c, l, _ = state.lists.shape
    n_list = c * l
    li = jnp.clip(cand, 0, n_list - 1)
    in_rows = state.lists[li // l, li % l]
    sp_rows = state.spill[jnp.clip(cand - n_list, 0,
                                   state.spill.shape[0] - 1)]
    return jnp.where((cand >= n_list)[..., None], sp_rows, in_rows)


def _rescore_topk(q: jax.Array, rows: jax.Array, ids: jax.Array,
                  metric: str, k: int):
    """Exact f32 rescore of candidates rows f32[B, R, D] -> top-k.

    Pure f32 einsum, deliberately NOT the bf16 fused kernel: the rescore
    exists to erase the coarse tier's quantization error, so it must be
    the highest-precision arithmetic in the pipeline.  O(B*R*D) — noise
    next to the coarse scan.  Returns (ids, scores, rows) at the final k.
    """
    s = jnp.einsum("brd,bd->br", rows, q.astype(jnp.float32))
    if metric == "l2":
        s = jnp.sum(rows * rows, axis=-1) - 2.0 * s
    mask_val = float("inf") if metric == "l2" else float("-inf")
    s = jnp.where(ids >= 0, s, mask_val)
    top, ii = jax.lax.top_k(_order_scores(s, metric), k)
    return (jnp.take_along_axis(ids, ii, axis=1), top,
            jnp.take_along_axis(rows, ii[..., None], axis=1))


def _rescore_r(state: IVFState, cfg: EngineConfig, k: int, n: int) -> int:
    """Static coarse-survivor count: rescore_k clamped to [k, n]."""
    return min(max(cfg.rescore_k, k), n)


def _query_full_scan_q8(state: IVFState, q: jax.Array, cfg: EngineConfig,
                        k: int):
    """Two-stage full scan: int8 coarse scan over every row, exact f32
    rescore of the top `rescore_k` survivors.  The coarse tier streams 1
    byte/component instead of 4; the f32 tier is touched only for
    B*rescore_k gathered rows."""
    codes, scales, zeros, norms = _flat_codes(state)
    ids = jnp.concatenate(
        [state.list_ids.reshape(-1), state.spill_ids], axis=0)
    coarse = ops.scan_scores_q8(
        q, codes, ids, scales, zeros,
        norms if cfg.metric == "l2" else None, metric=cfg.metric,
        use_kernel=cfg.use_kernel)
    r = _rescore_r(state, cfg, k, codes.shape[0])
    _, cand = jax.lax.top_k(_order_scores(coarse, cfg.metric), r)
    rows = _gather_flat_rows(state, cand)
    return _rescore_topk(q, rows, ids[cand], cfg.metric, k)


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def query_full_scan(state: IVFState, q: jax.Array, cfg: EngineConfig,
                    k: int) -> Tuple[jax.Array, jax.Array]:
    """Throughput template: fused GEMM scan of the whole database.

    For large query batches the probed-subset union approaches the full DB,
    so the MXU-friendly move is one dense scan (paper Fig. 4: big GEMMs are
    where the matrix engine wins).  Returns (ids i32[B,k], scores f32[B,k]).

    Under the int8 store policy this is the asymmetric two-stage pipeline:
    quantized coarse scan -> exact f32 rescore of the top `cfg.rescore_k`.
    """
    if cfg.quantized:
        out_ids, top, _ = _query_full_scan_q8(state, q, cfg, k)
        return out_ids, top
    rows, ids = _flat_rows(state)
    scores = ops.scan_scores(
        q, rows, ids, _metric_norms(rows, cfg.metric), metric=cfg.metric,
        use_kernel=cfg.use_kernel, fused_conversion=cfg.fused_conversion)
    top, idx = jax.lax.top_k(_order_scores(scores, cfg.metric), k)
    return ids[idx], top


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def query_full_scan_rows(state: IVFState, q: jax.Array, cfg: EngineConfig,
                         k: int):
    """Like query_full_scan but also returns the vectors f32[B, k, D]
    (used by the fused RAG serving path to splice memories into the prompt)."""
    if cfg.quantized:
        return _query_full_scan_q8(state, q, cfg, k)
    rows, ids = _flat_rows(state)
    scores = ops.scan_scores(
        q, rows, ids, _metric_norms(rows, cfg.metric), metric=cfg.metric,
        use_kernel=cfg.use_kernel, fused_conversion=cfg.fused_conversion)
    top, idx = jax.lax.top_k(_order_scores(scores, cfg.metric), k)
    return ids[idx], top, rows[idx]


@functools.partial(jax.jit, static_argnames=("cfg", "k", "nprobe"))
def query_probed(state: IVFState, q: jax.Array, cfg: EngineConfig,
                 k: int, nprobe: int) -> Tuple[jax.Array, jax.Array]:
    """Latency template: IVF probe path for small query batches.

    Centroid scores are one small GEMM; each query then gathers its nprobe
    lists (contiguous slabs, not random probes) and runs one fused scan over
    [nprobe*L + spill] rows.  Sequential over queries (lax.map) to bound the
    working set — the windowed-submission idea applied inside the op.
    """
    c, l, d = state.lists.shape
    # nprobe is static; clamp so k<=axis holds in the centroid top_k even
    # when a caller asks for more probes than there are clusters
    nprobe = max(1, min(nprobe, c))
    cvalid = jnp.arange(state.n_clusters, dtype=jnp.int32)
    cscores = ops.scan_scores(
        q, state.centroids, cvalid, _metric_norms(state.centroids, cfg.metric),
        metric=cfg.metric, use_kernel=cfg.use_kernel,
        fused_conversion=cfg.fused_conversion)
    _, probes = jax.lax.top_k(_order_scores(cscores, cfg.metric), nprobe)

    spill_rows, spill_ids = state.spill, state.spill_ids

    def one(args):
        qi, pi = args                                   # [D], [nprobe]
        rids = state.list_ids[pi].reshape(nprobe * l)
        rids = jnp.concatenate([rids, spill_ids], axis=0)
        if cfg.quantized:
            # Quantized latency path: the probed slabs stream as int8 codes
            # with their per-list affine scalars; survivors rescore in f32.
            codes = jnp.concatenate(
                [state.q_lists[pi].reshape(nprobe * l, d), state.q_spill],
                axis=0)
            scales = jnp.concatenate(
                [jnp.repeat(state.q_scales[pi], l), state.q_spill_scales])
            zeros = jnp.concatenate(
                [jnp.repeat(state.q_zeros[pi], l), state.q_spill_zeros])
            norms = jnp.concatenate(
                [state.q_norms[pi].reshape(nprobe * l), state.q_spill_norms])
            s = ops.scan_scores_q8(
                qi[None], codes, rids, scales, zeros,
                norms if cfg.metric == "l2" else None, metric=cfg.metric,
                use_kernel=cfg.use_kernel)
            r = _rescore_r(state, cfg, k, codes.shape[0])
            _, cand = jax.lax.top_k(_order_scores(s, cfg.metric), r)
            # survivor f32 rows: probed-slab indices map through pi
            n_probe_rows = nprobe * l
            li = jnp.clip(cand, 0, n_probe_rows - 1)
            in_rows = state.lists[pi[li // l], li % l]
            sp = spill_rows[jnp.clip(cand - n_probe_rows, 0,
                                     spill_rows.shape[0] - 1)]
            rows = jnp.where((cand >= n_probe_rows)[..., None], sp, in_rows)
            out_ids, top, _ = _rescore_topk(qi[None], rows, rids[cand],
                                            cfg.metric, k)
            return out_ids[0], top[0]
        rows = state.lists[pi].reshape(nprobe * l, d)   # contiguous slabs
        rows = jnp.concatenate([rows, spill_rows], axis=0)
        s = ops.scan_scores(
            qi[None], rows, rids, _metric_norms(rows, cfg.metric),
            metric=cfg.metric, use_kernel=cfg.use_kernel,
            fused_conversion=cfg.fused_conversion)
        top, idx = jax.lax.top_k(_order_scores(s, cfg.metric)[0], k)
        return rids[idx], top

    ids_k, scores_k = jax.lax.map(one, (q, probes))
    return ids_k, scores_k


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------

def footprint(state: IVFState) -> dict:
    """Resident-size accounting for the scan store.

    `bytes_per_row` is the full resident footprint per stored vector slot:
    under the int8 policy a row costs its retained exact f32 copy (the
    rescore tier — quantization is a derived scan stream, not a replacement
    store) PLUS its 1-byte/component code, so budgets charged from this
    number are truthful.  `scan_bytes_per_row` is what the coarse scan
    *streams* per vector — 1 byte/component under int8, 4 under f32 — the
    paper's DRAM-traffic argument in numbers.  `index_bytes` sums every
    materialized leaf (both vector tiers, the spill buffer, ids, counters,
    and the per-list quantizer scalars), so it is the number the residency
    budget audits against.
    """
    row_itemsize = 5 if state.quantized else 4
    return {
        "bytes_per_row": state.dim * row_itemsize,
        "scan_bytes_per_row": state.dim * (1 if state.quantized else 4),
        "index_bytes": sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(state)),
        "store_dtype": "int8" if state.quantized else "float32",
    }


def stats(state: IVFState) -> dict:
    sizes = jax.device_get(state.list_sizes)
    return {
        "n_clusters": state.n_clusters,
        "dim": state.dim,
        "list_capacity": state.list_capacity,
        "live": int(jax.device_get(live_count(state))),
        "spill": int(jax.device_get(state.spill_size)),
        "deleted": int(jax.device_get(state.num_deleted)),
        "max_list": int(sizes.max()),
        "mean_list": float(sizes.mean()),
        **footprint(state),
    }
