#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds s1,s2,... \\
        --seconds <s> [--controls fp8,int8]

In one process, runs the cell once per seed as the benchmark does (set-up,
a window of `--seconds` at the cell's own load, the comparison), and for
each seed also puts the reference, rounded to each control precision, in
the program's place on the same sampled queries.  Prints one JSON line per
seed: the program's numbers compared and each control's, with whether
each passes the limits.  The lower reading of a limit is the largest the
program gives over the seeds; the upper is the smallest any control gives.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="fp8,int8")
    args = ap.parse_args()

    from chipbench import cell as run_cell
    from chipbench import device as dev
    from chipbench.spec import load_cell
    cell = load_cell(args.workload)
    dev.use_compile_cache()
    devices = dev.require_tpu(cell.chips)
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell.run(cell, seed, args.seconds, False, devices, time.perf_counter(),
                           controls=controls, emit=lambda rec: None)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "failed": out["failed"], "attempted": out["attempted"],
                          "program": {k: v["value"] for k, v in out["checks"].items()},
                          "controls": {
                              p: {"correct": c["correct"],
                                  **{k: v["value"] for k, v in c["checks"].items()}}
                              for p, c in out.get("controls", {}).items()}}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
