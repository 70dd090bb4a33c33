#!/usr/bin/env python3
"""Run one cell of the AME benchmark once, on the chip it is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in `BENCHMARK.json` at the root of the
checkout.  With `--trace 0` the result carries the cell's end-to-end
metrics; with `--trace 1` a profiler trace of the window gives its
per-layer metrics.  Earlier lines of standard output are JSON details of
set-up, the window, maintenance and the reference.  The last lines of
standard error are the numbers compared, each beside its limit; the last
line of standard output is the result:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.  JAX's compilation cache lives in `.jax_cache` at the
root of the checkout, so only a checkout's first run compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import device as dev
    from chipbench.spec import load_cell
    cell = load_cell(args.workload)
    dev.use_compile_cache()
    try:
        devices = dev.require_tpu(cell.chips)
    except dev.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import repro  # noqa: F401  (the system under test must import)
    from chipbench import cell as run_cell
    out = run_cell.run(cell, args.seed, args.seconds, bool(args.trace), devices, T_START)
    for name, c in out["checks"].items():
        ok = c["value"] >= c["limit"] if c["rule"] == ">=" else c["value"] <= c["limit"]
        print(f"check {name} {c['value']!r} {c['rule']} {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # leave at once: nothing may print after the result line
    os._exit(code)
