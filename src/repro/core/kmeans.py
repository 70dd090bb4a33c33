"""k-means for IVF build/rebuild, GEMM-native end to end.

Assignment = `kmeans_assign` Pallas kernel; centroid update = `segsum_gemm`
one-hot GEMM — both steps are dense matrix work on the MXU, the paper's T2.
Tile alignment of the cluster count (C % 128) is enforced by EngineConfig
when `aligned=True`; the cluster-sweep benchmark measures the misaligned
fragmentation cost (paper Fig. 9).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import EngineConfig
from repro.kernels import ops


@functools.partial(jax.jit, static_argnames=("cfg", "n_clusters", "iters"))
def kmeans(key: jax.Array, x: jax.Array, valid: jax.Array,
           cfg: EngineConfig, n_clusters: int | None = None,
           iters: int | None = None) -> Tuple[jax.Array, jax.Array]:
    """Lloyd's k-means over the valid rows of x f32[M, D].

    Returns (centroids f32[C, D], assignments i32[M]; -1 for invalid rows).
    Empty clusters are re-seeded from random valid rows each iteration.
    """
    c = n_clusters or cfg.n_clusters
    iters = iters or cfg.kmeans_iters
    m, d = x.shape

    # --- init: sample C valid rows (Gumbel top-k over the valid mask) ---
    key, sub = jax.random.split(key)
    g = jax.random.gumbel(sub, (m,)) + jnp.where(valid, 0.0, -1e30)
    _, seed_idx = jax.lax.top_k(g, c)
    centroids = x[seed_idx]

    def step(carry, key_i):
        cent = carry
        idx, _ = ops.kmeans_assign(
            x, cent, use_kernel=cfg.use_kernel,
            fused_conversion=cfg.fused_conversion)
        idx = jnp.where(valid, idx, -1)
        sums, counts = ops.segsum_gemm(
            x, idx, n_clusters=c, use_kernel=cfg.use_kernel)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # re-seed empty clusters from random valid rows
        g = jax.random.gumbel(key_i, (m,)) + jnp.where(valid, 0.0, -1e30)
        _, rs = jax.lax.top_k(g, c)
        new = jnp.where((counts > 0)[:, None], new, x[rs])
        if cfg.metric == "ip":
            # spherical k-means: normalized centroids rank by inner product
            new = new / jnp.maximum(
                jnp.linalg.norm(new, axis=1, keepdims=True), 1e-6)
        return new, None

    keys = jax.random.split(key, iters)
    centroids, _ = jax.lax.scan(step, centroids, keys)

    final_idx, _ = ops.kmeans_assign(
        x, centroids, use_kernel=cfg.use_kernel,
        fused_conversion=cfg.fused_conversion)
    return centroids, jnp.where(valid, final_idx, -1)
