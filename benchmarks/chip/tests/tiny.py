"""Tiny deployments and mixes for running the harness on the CPU.

The shapes of the real cells at a size a test run can hold: the same
traffic laws, the same comparison, the jnp reference path of the program
(`use_kernel=False`) so nothing runs in Pallas interpret mode.
"""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import spec  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

# B=1 queries take the probed path as at full size: the router's default
# crossover (n_clusters / (8 nprobe)) would send them to the full scan here
ENGINE = {"dim": 128, "n_clusters": 128, "list_capacity": 64, "nprobe": 16, "k": 10,
          "index_policy": "ivf", "store_dtype": "float32", "compute_dtype": "bfloat16",
          "use_kernel": False,
          "kmeans_iters": 4}

TOPICS = {"name": "tiny-topics", "rows": 2048,
          "engine": {**ENGINE, "metric": "ip"},
          "collection": {"spill_capacity": 512}, "thresholds": {"full_scan_batch": 4},
          "data": {"kind": "topics", "rows_per_topic": 16, "spread": 0.5,
                   "query_noise": 0.03},
          "limits": {"score_err": 0.0005, "recall_at_10": 0.85}}

CLUSTERS = {"name": "tiny-clusters", "rows": 4096,
            "engine": {**ENGINE, "metric": "l2"},
            "collection": {"spill_capacity": 1024},
            "thresholds": {"full_scan_batch": 4, "maintenance_tombstone_frac": 0.05},
            "data": {"kind": "clusters", "rows_per_cluster": 32, "spread": 0.5},
            "assumed": {"rebuild_s": 0.3},
            "limits": {"score_err": 0.0005, "recall_at_10": 0.85}}

STREAM = {"loop": "open",
          "streams": [{"op": "query", "arrival": "poisson", "rate_per_s": 40, "rows": 1},
                      {"op": "insert", "arrival": "fixed", "rate_per_s": 16, "rows": 16},
                      {"op": "delete", "arrival": "fixed", "rate_per_s": 16, "rows": 16,
                       "phase": 0.5}],
          "lead_in": {"until_publish": True, "trigger_after_s": 0.3,
                      "after_publish_s": 0.2, "max_s": 120, "pool_extra_s": 60},
          "check_queries": 1024}

AGENT = {"loop": "open",
         "streams": [{"op": "query", "arrival": "poisson", "rate_per_s": 20, "rows": 1,
                      "then": {"op": "insert", "rows": 4}}],
         "lead_in": {"seconds": 0.3, "pool_extra_s": 10},
         "check_queries": 32}

BATCH = {"loop": "closed", "clients": 2, "streams": [{"op": "query", "rows": 32}],
         "lead_in": {"seconds": 0.3}, "query_pool_batches": 8, "check_queries": 64}


def cell(config: dict, mix: dict, name: str = "tiny") -> spec.Cell:
    return spec.Cell(name, 1, copy.deepcopy(config), copy.deepcopy(mix), [], [])


def run(c: spec.Cell, seed: int = 12345678901, seconds: float = 2.0, trace: bool = False,
        controls=()):
    """One run of `c` on the CPU, past the harness's look for a chip."""
    import jax
    from chipbench import cell as run_cell
    return run_cell.run(c, seed, seconds, trace, jax.devices()[:1], time.perf_counter(),
                        peaks=PEAKS, controls=controls, emit=lambda rec: None)
