#!/usr/bin/env python3
"""Find the highest rate a deployment sustains, by a sweep on the chip.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --stream <i> --rates r1,r2,... [--keep i,j] [--phase-seconds s]

Builds the cell's deployment once and warms it as a run does, then offers
the cell's traffic phase after phase, each `--phase-seconds` long, with
stream `i`'s `rate_per_s` set to each rate in turn (streams of the same
operation kind as a write pair, insert and delete, move together when
`--pair` is given).  `--keep` keeps only the listed streams.  Each phase
prints one JSON line: the rate offered, operations completed per second,
latency quantiles from the due time, and how late the generator ran at the
phase's end; a rate is sustained when every kind was answered at the rate
offered and the generator ended within `--late-ms` of its schedule.  The
benchmark's own runs use fixed rates found this way.
"""
import argparse
import copy
import json
import math
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def phase_report(drv, t0, t1, rate, late_ms) -> dict:
    """A phase is sustained when every kind's operations were answered at
    the rate the schedule offered them (within 3%) and the generator ended
    the phase no more than `late_ms` behind its schedule."""
    import numpy as np
    from chipbench import traffic as tr
    out = {"rate": rate, "rebuilds": sum(t0 <= r.published < t1 for r in drv.log.rebuilds)}
    ok = True
    for kind in ("query", "insert", "delete"):
        offered = tr.rows_per_second(drv.mix, kind) / max(
            [int(s.get("rows", 1)) for s in drv.mix["streams"] if s["op"] == kind]
            + [int(s["then"]["rows"]) for s in drv.mix["streams"]
               if s.get("then", {}).get("op") == kind])
        ops = [op for op in drv.log.ops if op.kind == kind and t0 <= op.due < t1]
        if not ops:
            continue
        lat = np.array([(op.done - op.due) * 1e3 if op.error is None else math.inf
                        for op in ops])
        late = (max(op.submit for op in ops) - (t1 - 1.0 / offered)) * 1e3
        done = sum(op.error is None and op.done < t1 + 1.0 for op in ops) / (t1 - t0)
        out[kind] = {"offered_per_s": offered, "done_per_s": done,
                     "p50_ms": float(np.percentile(lat, 50)),
                     "p99_ms": float(np.percentile(lat, 99)),
                     "end_late_ms": late}
        ok = ok and done >= 0.97 * offered and late <= late_ms
    out["sustained"] = ok
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--stream", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--pair", action="store_true")
    ap.add_argument("--phase-seconds", type=float, default=10.0)
    ap.add_argument("--late-ms", type=float, default=100.0)
    args = ap.parse_args()

    from chipbench import device as dev
    from chipbench import traffic as tr
    from chipbench.cell import prepare
    from chipbench.drive import LoadGen
    from chipbench.spec import load_cell
    cell = load_cell(args.workload)
    dev.use_compile_cache()
    dev.require_tpu(cell.chips)
    rates = [float(r) for r in args.rates.split(",")]
    base = copy.deepcopy(cell.traffic)
    if args.keep is not None:
        keep = [int(i) for i in args.keep.split(",")]
        base["streams"] = [s for i, s in enumerate(base["streams"]) if i in keep]
        args.stream = keep.index(args.stream)
    target = base["streams"][args.stream]
    movers = [s for s in base["streams"]
              if s is target or (args.pair and s["op"] in ("insert", "delete")
                                 and target["op"] in ("insert", "delete"))]

    def mix_at(rate):
        m = copy.deepcopy(base)
        for i, s in enumerate(base["streams"]):
            if any(s is x for x in movers):
                m["streams"][i]["rate_per_s"] = rate
        m["lead_in"] = {"seconds": 1.0}
        return m

    span = args.phase_seconds * len(rates) + 30
    n_ins = int(sum(tr.rows_per_second(mix_at(r), "insert") for r in rates)
                * args.phase_seconds) + 4096
    n_q = int(max(tr.rows_per_second(mix_at(r), "query") for r in rates) * span) + 64
    parts = {}
    svc, drv, corpus = prepare(cell.config, base, args.seed, n_ins, max(n_q, 64), parts)
    print(json.dumps({"setup": parts}), flush=True)
    try:
        prev = drv
        for rate in rates:
            d = LoadGen(svc, "memory", corpus, mix_at(rate), args.seed, after=prev)
            t0 = d.start() + 1.0
            d.stop_at = t0 + args.phase_seconds
            time.sleep(max(0.0, d.stop_at - time.perf_counter()))
            d.finish()
            print(json.dumps(phase_report(d, t0, d.stop_at, rate, args.late_ms)), flush=True)
            prev = d
    finally:
        svc.shutdown()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
