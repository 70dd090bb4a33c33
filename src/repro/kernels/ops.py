"""Public jit'd wrappers around the Pallas kernels.

These handle (a) padding arbitrary shapes up to kernel block multiples — the
paper's M-dimension round-up to the tile size, (b) the kernel/ref dispatch
driven by ``EngineConfig`` ablation flags, and (c) the un-fused baseline that
materializes a converted copy (the "naive port" the paper argues against).

Whether a kernel is compiled or interpreted follows from the platform alone
(`interpret_kernels`): compiled on a TPU, Pallas interpret mode elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import scan_scores as _scan
from repro.kernels import kmeans_assign as _assign
from repro.kernels import segsum_gemm as _segsum

NEG_INF = float("-inf")


def interpret_kernels() -> bool:
    """True unless the default backend is a TPU, the one platform the
    kernels compile for; everywhere else Pallas interprets them."""
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0):
    n = x.shape[axis]
    target = ((n + mult - 1) // mult) * mult
    if target == n:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - n)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=(
    "metric", "use_kernel", "fused_conversion", "block_m", "block_n", "block_k"))
def scan_scores(q, db, ids, db_norms=None, *, metric="ip", use_kernel=True,
                fused_conversion=True, block_m=128, block_n=512, block_k=512):
    """Similarity scores fp32[B, N] between queries and database rows.

    Pads B/N/D to block multiples; padded DB rows get id -1 (masked -inf),
    padded query rows are sliced off.
    """
    b, n = q.shape[0], db.shape[0]
    if not fused_conversion:
        # Baseline "C" in the ablation ladder: materialize the converted copy
        # in HBM first (extra full-matrix round trip), then run exact GEMM.
        db = db.astype(jnp.bfloat16)
        q = q.astype(jnp.bfloat16)
    if not use_kernel:
        out = _ref.scan_scores_ref(q, db, ids, db_norms, metric=metric,
                                   fused_conversion=fused_conversion)
        return out
    d_mult = block_k
    qp = _pad_to(_pad_to(q, 0, block_m), 1, d_mult)
    dbp = _pad_to(_pad_to(db, 0, block_n), 1, d_mult)
    idsp = _pad_to(ids, 0, block_n, value=-1)
    if db_norms is not None:
        db_norms = _pad_to(db_norms, 0, block_n)
    out = _scan.scan_scores(
        qp.astype(jnp.float32), dbp.astype(jnp.float32), idsp, db_norms,
        metric=metric, block_m=block_m, block_n=block_n, block_k=block_k,
        fused_conversion=fused_conversion, interpret=interpret_kernels())
    return out[:b, :n]


@functools.partial(jax.jit, static_argnames=(
    "metric", "use_kernel", "block_m", "block_n", "block_k"))
def scan_scores_q8(q, codes, ids, scales, zeros, db_norms=None, *,
                   metric="ip", use_kernel=True, block_m=128, block_n=512, block_k=512):
    """Quantized coarse scan: fp32[B, N] approximate scores.

    q is fp32[B, D]; it is quantized here (symmetric per-query int8, see
    `ref.quantize_queries`) so the kernel and the jnp reference consume
    identical integer operands.  codes/scales/zeros are the affine int8 row
    store (per-row scale/zero-point); `db_norms` must be the DEQUANTIZED
    row norms for L2.  Pads B/N/D to block multiples — code padding is
    exact because the `sum(qc)` correction is taken before padding; padded
    DB rows get id -1 (masked), padded query rows are sliced off.
    """
    b, n = q.shape[0], codes.shape[0]
    qc, sq = _ref.quantize_queries(q)
    if not use_kernel:
        return _ref.scan_scores_q8_ref(q, codes, ids, scales, zeros,
                                       db_norms, metric=metric)
    corr = sq * jnp.sum(qc.astype(jnp.int32), axis=1)
    qp = _pad_to(_pad_to(qc, 0, block_m), 1, block_k)
    cp = _pad_to(_pad_to(codes, 0, block_n), 1, block_k)
    idsp = _pad_to(ids, 0, block_n, value=-1)
    scalesp = _pad_to(scales, 0, block_n)
    zerosp = _pad_to(zeros, 0, block_n)
    sqp = _pad_to(sq, 0, block_m)
    corrp = _pad_to(corr, 0, block_m)
    if db_norms is not None:
        db_norms = _pad_to(db_norms, 0, block_n)
    out = _scan.scan_scores_q8(
        qp, cp, idsp, scalesp, zerosp, sqp, corrp, db_norms,
        metric=metric, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret_kernels())
    return out[:b, :n]


@functools.partial(jax.jit, static_argnames=(
    "use_kernel", "fused_conversion", "block_m", "block_c", "block_k"))
def kmeans_assign(x, centroids, *, use_kernel=True, fused_conversion=True,
                  block_m=256, block_c=256, block_k=512):
    """(idx int32[M], dist fp32[M]) nearest centroid per row (L2, mod ||x||^2)."""
    if not use_kernel:
        return _ref.kmeans_assign_ref(x, centroids,
                                      fused_conversion=fused_conversion)
    m, c = x.shape[0], centroids.shape[0]
    xp = _pad_to(_pad_to(x, 0, block_m), 1, block_k)
    # pad centroids with +inf-norm rows so padded centroids never win
    cp = _pad_to(_pad_to(centroids, 0, block_c, value=3e18), 1, block_k)
    idx, dist = _assign.kmeans_assign(
        xp.astype(jnp.float32), cp.astype(jnp.float32),
        block_m=block_m, block_c=block_c, block_k=block_k,
        fused_conversion=fused_conversion, interpret=interpret_kernels())
    return jnp.minimum(idx[:m], c - 1), dist[:m]


@functools.partial(jax.jit, static_argnames=(
    "n_clusters", "use_kernel", "block_m", "block_c", "block_d"))
def segsum_gemm(x, assign, *, n_clusters, use_kernel=True, block_m=512, block_c=128, block_d=512):
    """(sums fp32[C, D], counts fp32[C]); assign < 0 rows are ignored."""
    if not use_kernel:
        # one_hot(-1) is all-zeros, so negative assignments drop out naturally
        return _ref.segsum_gemm_ref(x, assign, n_clusters=n_clusters)
    c_pad = ((n_clusters + block_c - 1) // block_c) * block_c
    xp = _pad_to(_pad_to(x, 0, block_m), 1, block_d)
    # padded rows get assignment -1 => match no cluster tile
    ap = _pad_to(assign, 0, block_m, value=-1)
    sums, counts = _segsum.segsum_gemm(
        xp.astype(jnp.float32), ap, n_clusters=c_pad,
        block_m=block_m, block_c=block_c, block_d=block_d,
        interpret=interpret_kernels())
    return sums[:n_clusters, : x.shape[1]], counts[:n_clusters]
