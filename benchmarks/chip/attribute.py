#!/usr/bin/env python3
"""Put a cell's device idle time down to the program's request phases, on
the chip.

    python3 benchmarks/chip/attribute.py --workload <cell> --seed <n> \\
        --seconds <s>

Runs the cell once with a profiler trace of the window, as `run.py --trace
1` does, and reduces the program's `ame.` spans in that trace
(`chipbench/spans.py`).  Prints the run's result line, then one JSON line
under `attribution`: the window's idle and in-flight idle seconds, idle
seconds by the span that names them, the longest idle gaps named, each
span's count, total and self seconds, and the mean `ame.device` span per
request kind (null where the program opens no span).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args()

    from chipbench import cell as run_cell
    from chipbench import device as dev
    from chipbench import spans, trace
    from chipbench.spec import load_cell
    cell = load_cell(args.workload)
    dev.use_compile_cache()
    devices = dev.require_tpu(cell.chips)
    tracers = []

    class Kept(spans.SpanTracer):
        def __init__(self):
            super().__init__()
            tracers.append(self)

    # `cell.run` makes its tracer from `chipbench.trace.Tracer`
    trace.Tracer = Kept
    out = run_cell.run(cell, args.seed, args.seconds, True, devices, T_START,
                       emit=lambda rec: None)
    print(json.dumps(out), flush=True)
    a = tracers[-1].attribution
    print(json.dumps({"attribution": dataclasses.asdict(a) if a else None}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
