"""Paper Fig. 8 — the NPU-subsystem ablation ladder, re-expressed on TPU.

The paper dissects its NPU pipeline into five configurations E->A.  The TPU
analogue ladder for the fused similarity scan (kernels/scan_scores):

  E  naive port            pure-jnp, fp32 GEMM, no conversion fusion
  D  + accelerator dtype   pure-jnp, fp32->bf16 conversion MATERIALIZED in
                           HBM first (the paper's 'convert the whole matrix'
                           option — doubles peak memory)
  C  + tiling              Pallas kernel, conversion still materialized
                           (paper's TCM-via-memcpy step: on-chip staging
                           pays an extra full-matrix round trip)
  B  + fused conversion    Pallas kernel, fp32->bf16 in-register per tile
                           (the Data Adaptation Layer: bf16 copy never
                           exists in HBM)
  A  + tuned block shapes  B with blocks sized so 2 in-flight tiles +
                           accumulator fill VMEM (execution-transfer overlap
                           via the multi-buffered grid pipeline)

Wall time on this container is XLA:CPU / interpret-mode and NOT the
deliverable; the ladder is scored on modeled v5e HBM traffic + projected
time, which is what the paper's GFLOPS figure measures structurally.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.configs.base import V5E
from repro.kernels import ops

B, N, D = 128, 8192, 1024


VPU_FLOPS = 4e12          # v5e vector unit, fp32 (no MXU) — the 'E' regime


def _traffic(variant: str, block_m=128, block_n=512) -> float:
    """Modeled per-call HBM bytes for scores = Q[B,D] x DB[N,D]^T.

    Tile re-reads: Q is streamed once per j-block, DB once per i-block
    (the BlockSpec index maps in kernels/scan_scores.py).
    """
    n_i, n_j = max(B // block_m, 1), max(N // block_n, 1)
    q, db, out = B * D, N * D, B * N
    if variant in ("D", "C"):       # materialize bf16 copy first:
        # fp32 read + bf16 write, then the GEMM re-streams the bf16 copy
        conv = 4 * (q + db) + 2 * (q + db)
        gemm = 2 * (q * n_j + db * n_i) + 4 * out
        return conv + gemm
    # E/B/A: single fp32 stream through the kernel (E has no tiling: once)
    if variant == "E":
        return 4 * (q + db) + 4 * out
    return 4 * (q * n_j + db * n_i) + 4 * out


def _v5e_seconds(variant: str) -> float:
    flops = 2.0 * B * N * D
    if variant == "E":              # no matrix engine (paper's HVX-only)
        return max(flops / VPU_FLOPS, _traffic("E") / V5E.hbm_bandwidth)
    blocks = dict(E=(128, 512), D=(128, 512), C=(128, 512),
                  B=(128, 512), A=(128, 1024))[variant]
    c = flops / V5E.peak_flops_bf16
    m = _traffic(variant, *blocks) / V5E.hbm_bandwidth
    if variant == "D":              # no execution-transfer overlap: serial
        return c + m
    return max(c, m)                # pipelined: overlap hides the smaller


def run():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, D), jnp.float32)
    db = jax.random.normal(key, (N, D), jnp.float32)
    ids = jnp.arange(N, dtype=jnp.int32)

    variants = {
        "E_naive_fp32": dict(use_kernel=False, fused_conversion=True),
        "D_bf16_materialized": dict(use_kernel=False, fused_conversion=False),
        "C_tiled_materialized": dict(use_kernel=True, fused_conversion=False,
                                     block_m=128, block_n=512, block_k=512),
        "B_fused_conversion": dict(use_kernel=True, fused_conversion=True,
                                   block_m=128, block_n=512, block_k=512),
        "A_tuned_blocks": dict(use_kernel=True, fused_conversion=True,
                               block_m=128, block_n=1024, block_k=1024),
    }
    base = None
    for name, kw in variants.items():
        letter = name[0]
        if kw.get("use_kernel"):
            # the kernel is checked for correctness only; time the REF with
            # the same conversion policy for a consistent CPU wall number
            out_k = ops.scan_scores(q[:8], db[:1024], ids[:1024], None,
                                    metric="ip", **kw)
            out_r = ops.scan_scores(
                q[:8], db[:1024], ids[:1024], None, metric="ip",
                use_kernel=False,
                fused_conversion=kw["fused_conversion"])
            np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                       rtol=3e-2, atol=3e-2)
            wall = common.timeit(lambda kw=kw: jax.block_until_ready(
                ops.scan_scores(q, db, ids, None, metric="ip",
                                use_kernel=False,
                                fused_conversion=kw["fused_conversion"])))
        else:
            wall = common.timeit(lambda kw=kw: jax.block_until_ready(
                ops.scan_scores(q, db, ids, None, metric="ip", **kw)))
        t_proj = _v5e_seconds(letter)
        gf = 2.0 * B * N * D / t_proj / 1e9
        if base is None:
            base = gf
        common.emit("ablation", f"{name}_v5e_us", round(t_proj * 1e6, 2),
                    "us", f"modeled HBM={_traffic(letter)/1e6:.1f}MB")
        common.emit("ablation", f"{name}_v5e_gflops", round(gf, 1),
                    "GFLOP/s", f"{gf / base:.2f}x vs E")
        common.emit("ablation", f"{name}_cpu_wall_us", round(wall * 1e6, 1),
                    "us", "XLA:CPU structural proxy")


if __name__ == "__main__":
    common.header()
    run()
