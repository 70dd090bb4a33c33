"""Benchmark harness: one benchmark per paper table/figure (DESIGN.md §6).

``PYTHONPATH=src python -m benchmarks.run [--only NAME]``

Prints ``bench,name,value,unit,note`` CSV.  Paper-figure mapping:
  gemm_heatmap   -> Fig. 4   cluster_sweep -> Fig. 9
  query_qps      -> Fig. 6L  ablation      -> Fig. 8
  index_build    -> Fig. 6R  paper_claims  -> §6.1 headline ratios
  hybrid         -> Fig. 7
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import common
from repro import compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    compile_cache.enable()

    from benchmarks import (bench_ablation, bench_cluster_sweep,
                            bench_gemm_heatmap, bench_hybrid,
                            bench_index_build, bench_paper_claims,
                            bench_query_qps)
    suites = {
        "gemm_heatmap": bench_gemm_heatmap.run,
        "ablation": bench_ablation.run,
        "cluster_sweep": bench_cluster_sweep.run,
        "query_qps": bench_query_qps.run,
        "index_build": bench_index_build.run,
        "hybrid": bench_hybrid.run,
        "paper_claims": bench_paper_claims.run,
    }
    common.header()
    failed = []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.perf_counter()
        try:
            fn()
            common.emit(name, "_suite_wall_s",
                        round(time.perf_counter() - t0, 1), "s")
        except Exception as e:  # keep the harness going; report at the end
            failed.append((name, e))
            traceback.print_exc()
    if failed:
        raise SystemExit(f"benchmark suites failed: {[n for n, _ in failed]}")


if __name__ == "__main__":
    main()
