"""Fused similarity-scan kernel — AME's Data Adaptation Layer on TPU.

Computes ``scores = Q @ DB^T`` (inner-product) or ``-2 Q @ DB^T + ||db||^2``
(L2, query-norm dropped as it is rank-invariant), where

  * Q  : fp32[B, D]   queries (row-major, "CPU-side" layout in the paper)
  * DB : fp32[N, D]   database rows (IVF lists flattened to rows)

The paper's HMX engine consumes FP16 tile-major operands; a naive port
materializes an FP16 transposed copy of the database in DRAM.  Here the
fp32->bf16 conversion happens *inside the kernel*, in VREGs, per VMEM tile —
the TPU analogue of AME's in-HVX ``vcvt``/``vdeal`` path: the bf16 copy never
exists in HBM, and HBM traffic stays at the fp32 stream the pipeline already
pays.  The AB^T pattern needs no explicit transpose on TPU: ``dot_general``
contracts both operands on their last (D) axis, so DB stays row-major
(paper's in-place HVX transpose becomes a dimension-numbers choice).

Execution-transfer overlap: the grid pipeline double-buffers HBM->VMEM DMAs
for the next (i, j, k) tile against the current MXU dot — the structural
equivalent of AME's SMT + DMA double-buffering in TCM (Fig. 3a).

Invocation amortization: a whole batch of queries against all probed lists is
ONE pallas_call inside one jit program (vs. per-tile FastRPC calls at
200-700us each in the naive mobile port).

Masking: ``ids < 0`` marks empty/tombstoned IVF slots; their scores are set
to -inf in the epilogue so downstream top-k never selects them.

``scan_lists`` and ``scan_rows`` are the in-place variants the probed query
uses: they read rows at their true width D (a block whose last dim is the
array's own), so nothing is padded or copied before the kernel.
``scan_lists`` takes the probe table as scalar prefetch and its BlockSpec
index map picks ``lists[probes[b, p]]``: the pipeline DMAs the probed
slabs (for a width-major store, each probed list's tile of 8 lists) from the
index's own buffer.  ``scan_rows`` scores
every query against one shared ``[N, D]`` matrix (the centroids, the spill
buffer).  Both leave masking to the caller, which holds the ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
POS_INF = float("inf")


def _scan_scores_kernel(
    q_ref,        # [bm, bk] fp32
    db_ref,       # [bn, bk] fp32
    ids_ref,      # [1, bn] int32
    norms_ref,    # [1, bn] fp32 (db row norms; zeros for IP metric)
    out_ref,      # [bm, bn] fp32
    acc_ref,      # scratch [bm, bn] fp32
    *,
    k_steps: int,
    metric: str,
    fused_conversion: bool,
    compute_dtype,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]
    db = db_ref[...]
    if fused_conversion:
        # AME Data Adaptation Layer: fp32 -> bf16 in-register, per tile.
        q = q.astype(compute_dtype)
        db = db.astype(compute_dtype)
    # AB^T without a transpose: contract on the last axis of both operands.
    acc_ref[...] += jax.lax.dot_general(
        q, db, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        scores = acc_ref[...]
        if metric == "l2":
            scores = norms_ref[0, :][None, :] - 2.0 * scores
        valid = ids_ref[0, :] >= 0
        # Masked slots must lose under the *metric's* ordering: IP maximizes
        # (mask with -inf), L2 minimizes distances (mask with +inf).
        mask_val = POS_INF if metric == "l2" else NEG_INF
        out_ref[...] = jnp.where(valid[None, :], scores, mask_val)


def _scan_scores_q8_kernel(
    qc_ref,       # [bm, bk] int8 quantized queries
    db_ref,       # [bn, bk] int8 row codes
    ids_ref,      # [1, bn] int32
    scales_ref,   # [1, bn] fp32 per-row affine scale
    zeros_ref,    # [1, bn] fp32 per-row affine zero-point
    norms_ref,    # [1, bn] fp32 dequantized-row norms (zeros for IP)
    qmeta_ref,    # [bm, 128] fp32: col 0 = sq (query scale), col 1 = sq*sum(qc)
    out_ref,      # [bm, bn] fp32
    acc_ref,      # scratch [bm, bn] int32
    *,
    k_steps: int,
    metric: str,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 on the matrix unit: the narrow operands stream
    # straight from the quantized store — no f32 (or dequantized) copy of
    # the tile ever exists, in HBM *or* in registers.  This is the int8
    # analogue of the fused f32->bf16 conversion above, one step further:
    # conversion work is replaced by an integer MAC plus an O(B+N) epilogue.
    acc_ref[...] += jax.lax.dot_general(
        qc_ref[...], db_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        # affine correction (per query row x per db row, rank-1 + scaling):
        #   q_hat . row_hat = sq*scale_n*(qc . c_n) + (sq*sum(qc))*zero_n
        sq = qmeta_ref[:, 0:1]                      # [bm, 1]
        corr = qmeta_ref[:, 1:2]                    # [bm, 1]
        scales = scales_ref[0, :][None, :]
        zeros = zeros_ref[0, :][None, :]
        scores = (acc_ref[...].astype(jnp.float32) * sq * scales
                  + corr * zeros)
        if metric == "l2":
            scores = norms_ref[0, :][None, :] - 2.0 * scores
        valid = ids_ref[0, :] >= 0
        mask_val = POS_INF if metric == "l2" else NEG_INF
        out_ref[...] = jnp.where(valid[None, :], scores, mask_val)


@functools.partial(
    jax.jit,
    static_argnames=("metric", "block_m", "block_n", "block_k", "interpret"),
)
def scan_scores_q8(
    qc: jax.Array,           # int8[B, D] quantized queries (ref.quantize_queries)
    codes: jax.Array,        # int8[N, D] affine row codes
    ids: jax.Array,          # int32[N]
    scales: jax.Array,       # fp32[N] per-row scale
    zeros: jax.Array,        # fp32[N] per-row zero-point
    sq: jax.Array,           # fp32[B] query scales
    corr: jax.Array,         # fp32[B] sq * sum(qc) per query
    db_norms: jax.Array | None = None,   # fp32[N] dequantized norms (L2 only)
    *,
    metric: str = "ip",
    block_m: int = 128,
    block_n: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Quantized coarse scan: fp32[B, N] approximate scores from int8 operands.

    Shapes must be pre-padded to block multiples (``ops.scan_scores_q8``
    pads; code padding is harmless because `corr` is computed over the real
    D before padding).  Per-query scalars ride in a [B, 128] lane-aligned
    sideband so every ref keeps a TPU-friendly 2D block shape.
    """
    b, d = qc.shape
    n, d2 = codes.shape
    assert d == d2, (qc.shape, codes.shape)
    assert b % block_m == 0 and n % block_n == 0 and d % block_k == 0, (
        f"unpadded shapes {qc.shape} x {codes.shape} for blocks "
        f"({block_m},{block_n},{block_k})")
    if db_norms is None:
        db_norms = jnp.zeros((n,), jnp.float32)
    qmeta = jnp.zeros((b, 128), jnp.float32)
    qmeta = qmeta.at[:, 0].set(sq).at[:, 1].set(corr)

    k_steps = d // block_k
    grid = (b // block_m, n // block_n, k_steps)

    kernel = functools.partial(
        _scan_scores_q8_kernel, k_steps=k_steps, metric=metric)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((block_m, 128), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        # int32 accumulator lives in VMEM across the k loop; the f32
        # epilogue converts in-register once per output tile
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
    )(qc, codes, ids[None, :], scales[None, :], zeros[None, :],
      db_norms[None, :], qmeta)


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "block_m", "block_n", "block_k", "fused_conversion",
        "interpret", "compute_dtype",
    ),
)
def scan_scores(
    q: jax.Array,            # fp32[B, D]
    db: jax.Array,           # fp32[N, D]  (or bf16 if pre-converted)
    ids: jax.Array,          # int32[N]
    db_norms: jax.Array | None = None,   # fp32[N] (L2 metric only)
    *,
    metric: str = "ip",
    block_m: int = 128,
    block_n: int = 512,
    block_k: int = 512,
    fused_conversion: bool = True,
    compute_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """Returns fp32[B, N] similarity scores (IP) or negated-rank L2 distances.

    Shapes must be pre-padded to block multiples (``ops.scan_scores`` pads).
    """
    b, d = q.shape
    n, d2 = db.shape
    assert d == d2, (q.shape, db.shape)
    assert b % block_m == 0 and n % block_n == 0 and d % block_k == 0, (
        f"unpadded shapes {q.shape} x {db.shape} for blocks "
        f"({block_m},{block_n},{block_k})")
    if db_norms is None:
        db_norms = jnp.zeros((n,), jnp.float32)

    k_steps = d // block_k
    grid = (b // block_m, n // block_n, k_steps)

    kernel = functools.partial(
        _scan_scores_kernel,
        k_steps=k_steps,
        metric=metric,
        fused_conversion=fused_conversion,
        compute_dtype=compute_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        # fp32 accumulator lives in VMEM across the k loop
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(q, db, ids[None, :], db_norms[None, :])


# ---------------------------------------------------------------------------
# In-place scans at the rows' true width (the probed query)
#
# A TPU lays an array out to waste the least of its (8, 128) tiles, so a
# store whose width is no multiple of 128 may not keep its rows contiguous:
# f32[1024, 2048, 100] is laid out width-major, list slots minor.  Each scan
# reads its operand through the view that is its physical layout (`order`,
# the array's dims from major to minor), so XLA passes the buffer as it is.
# ---------------------------------------------------------------------------

# bytes of one row block in VMEM; the pipeline double-buffers it
_BLOCK_BYTES = 4 * 2**20

ROWS, PLANES = (0, 1, 2), (2, 0, 1)


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _block(n: int, unit_bytes: int):
    """Units of n per block, and the kernel's compiler params: all n when
    they fit `_BLOCK_BYTES`, else the largest multiple of 128 dividing n
    that does, so each block's scores land lane-aligned.  Where no such
    multiple exists (a list of 1,464 slots) the block is all n, and the
    VMEM limit is raised to hold it twice, with its converted and squared
    copies."""
    fits = [b for b in range(128, n, 128)
            if n % b == 0 and b * unit_bytes <= _BLOCK_BYTES]
    if n * unit_bytes <= _BLOCK_BYTES or not fits:
        block = n
    else:
        block = fits[-1]
    if block * unit_bytes <= _BLOCK_BYTES:
        return block, None
    return block, pltpu.CompilerParams(
        vmem_limit_bytes=4 * block * unit_bytes + _BLOCK_BYTES)


def _scores_tile(q, x, *, by_column: bool, metric: str, compute_dtype):
    """Scores f32[m, n] of queries f32[m, D] against n rows held as
    f32[n, D], or f32[D, n] `by_column`: the operands converted in-register,
    f32 accumulation; for L2 each row's squared norm in f32 minus twice the
    product, as `scan_scores` computes with `_metric_norms`."""
    contract = ((1,), (0,)) if by_column else ((1,), (1,))
    s = jax.lax.dot_general(
        q.astype(compute_dtype), x.astype(compute_dtype),
        (contract, ((), ())), preferred_element_type=jnp.float32)
    if metric == "l2":
        if by_column:
            norms = jnp.sum(x * x, axis=0, keepdims=True)
        else:
            # a ones row contracted with the squares lays the norms along
            # lanes, as the scores lie; HIGHEST keeps the sum in f32
            norms = jax.lax.dot_general(
                jnp.ones((1, x.shape[1]), jnp.float32), x * x,
                (contract, ((), ())), precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        s = norms - 2.0 * s
    return s


def _scan_lists_kernel(probes_ref, q_ref, lists_ref, out_ref, *,
                       order, n_query: int, block_l: int, metric: str,
                       compute_dtype):
    g, b, p, j = (pl.program_id(i) for i in range(4))
    if order == PLANES:
        # the block holds the 8 lists of the probed list's sublane tile
        x = lists_ref[:, probes_ref[g * n_query + b, p] % 8, :]
    else:
        x = lists_ref[...]
    s = _scores_tile(q_ref[...], x, by_column=order == PLANES, metric=metric,
                     compute_dtype=compute_dtype)
    if block_l == out_ref.shape[1]:
        out_ref[pl.ds(p, 1), :] = s
    else:
        out_ref[pl.ds(p, 1), pl.ds(pl.multiple_of(j * block_l, block_l),
                                   block_l)] = s


def _scan_rows_kernel(q_ref, rows_ref, out_ref, *, by_column: bool,
                      metric: str, compute_dtype):
    out_ref[...] = _scores_tile(q_ref[...], rows_ref[...],
                                by_column=by_column, metric=metric,
                                compute_dtype=compute_dtype)


@functools.partial(jax.jit, static_argnames=(
    "order", "metric", "compute_dtype", "interpret"))
def scan_lists(
    q: jax.Array,            # fp32[G, B, D]
    lists: jax.Array,        # fp32[G, C, L, D]
    probes: jax.Array,       # int32[G, B, P] list indices per query
    *,
    order=ROWS,              # one lane's lists' dims, major to minor, as
                             # laid out (the lane axis is the major one)
    metric: str = "ip",
    compute_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """Returns fp32[G, B, P, L]: in lane g, query b's scores against list
    probes[g, b, p].  Lanes are the collections of a fused dispatch.

    Grid (lane, query, probe, block of L); the probe table is scalar
    prefetch, and the lists' index map picks each probed slab where it
    lies.  Width-major lists (`PLANES`) are tiled 8 lists to a sublane
    tile, so a step reads the probed list's tile of 8 and scores the one
    it names.  Each query's [P, L] scores stay in VMEM across its probes
    and are written back once.  Unmasked: slots with ids < 0 score like
    any row.
    """
    g, b, d = q.shape
    _, c, l, d2 = lists.shape
    assert d == d2, (q.shape, lists.shape)
    if order != PLANES or c % 8:
        order = ROWS          # any other layout: XLA relays the store out
    n_probe = probes.shape[2]
    if order == ROWS:
        bl, params = _block(l, _lanes(d) * 4)
        spec = pl.BlockSpec(
            (None, None, bl, d),
            lambda h, i, t, j, pr: (h, pr[h * b + i, t], j, 0))
    else:
        bl, params = _block(l, d * 8 * 4)
        spec = pl.BlockSpec(
            (None, d, 8, bl),
            lambda h, i, t, j, pr: (h, 0, pr[h * b + i, t] // 8, j))
    kernel = functools.partial(_scan_lists_kernel, order=order, n_query=b,
                               block_l=bl, metric=metric,
                               compute_dtype=compute_dtype)
    view = jnp.transpose(lists, (0,) + tuple(o + 1 for o in order))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g, b, n_probe, l // bl),
            in_specs=[
                pl.BlockSpec((None, None, 1, d),
                             lambda h, i, t, j, pr: (h, i, 0, 0)),
                spec,
            ],
            out_specs=pl.BlockSpec((None, None, n_probe, l),
                                   lambda h, i, t, j, pr: (h, i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((g, b, n_probe, l), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(probes.reshape(g * b, n_probe).astype(jnp.int32), q[:, :, None, :],
      view)


@functools.partial(jax.jit, static_argnames=(
    "order", "metric", "compute_dtype", "interpret"))
def scan_rows(
    q: jax.Array,            # fp32[B, D]
    rows: jax.Array,         # fp32[N, D]
    *,
    order=(0, 1),            # rows' dims, major to minor, as laid out
    metric: str = "ip",
    compute_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """Returns fp32[B, N]: every query against every row, all queries in
    one block so each row block is read once.  Unmasked, like
    `scan_lists`."""
    b, d = q.shape
    n, d2 = rows.shape
    assert d == d2, (q.shape, rows.shape)
    by_column = tuple(order) == (1, 0)
    if by_column:
        bn, params = _block(n, -(-d // 8) * 8 * 4)
        spec = pl.BlockSpec((d, bn), lambda j: (0, j))
        rows = rows.T
    else:
        bn, params = _block(n, _lanes(d) * 4)
        spec = pl.BlockSpec((bn, d), lambda j: (j, 0))
    kernel = functools.partial(_scan_rows_kernel, by_column=by_column,
                               metric=metric, compute_dtype=compute_dtype)
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((b, d), lambda j: (0, 0)), spec],
        out_specs=pl.BlockSpec((b, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(q, rows)
