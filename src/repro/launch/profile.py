"""Dry-run profiler: where do the FLOPs / bytes / collectives come from?

The §Perf methodology's "profile" step (EXPERIMENTS.md): given a compiled
cell, attribute collective wire bytes and fusion HBM traffic to the
jax-level op that emitted them (`op_name` metadata), with while-loop trip
multipliers applied — the dry-run analogue of a wall-clock trace viewer.

    PYTHONPATH=src python -m repro.launch.profile --arch deepseek-moe-16b \
        --shape train_4k [--multi-pod] [--what collectives|hbm] [--top 15]
"""
import argparse
import os
import re
from collections import defaultdict

from repro.launch import hlo_analysis as h


def attribute(hlo: str, what: str = "collectives"):
    """[(bytes, kind, op_name)] with trip-count multipliers applied."""
    comps = h.split_computations(hlo)
    costs = h.parse(hlo)
    entry = h.find_entry(hlo, costs)
    agg = defaultdict(float)

    def walk(name, mult, depth=0):
        if depth > 64 or name not in comps:
            return
        lines = comps[name]
        sym = {}
        for ln in lines:
            m = h._INSTR_RE.match(ln)
            if m:
                sym[m.group(1)] = m.group(2).strip()
        for ln in lines:
            m = h._INSTR_RE.match(ln)
            if not m:
                continue
            _, shape, op = m.groups()
            base = op[:-6] if op.endswith("-start") else op
            meta = re.search(r'op_name="([^"]+)"', ln)
            tag = (re.sub(r"jit\([\w.\-]+\)/", "", meta.group(1))[:90]
                   if meta else "?")
            if what == "collectives" and base in h.COLLECTIVES:
                agg[(base, tag)] += mult * h._all_shapes_bytes(shape)
            elif what == "hbm" and op == "fusion":
                out_b = h._all_shapes_bytes(shape)
                ops_m = re.search(r"fusion\(([^)]*)\)", ln)
                b = out_b + (sum(
                    h._all_shapes_bytes(sym.get(o.strip().lstrip("%"), ""))
                    for o in ops_m.group(1).split(",")) if ops_m else 0)
                agg[("fusion", tag)] += mult * b
            if op == "while":
                wm = re.search(
                    r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)", ln)
                tm = h._TRIP_RE.search(ln)
                t = float(tm.group(1)) if tm else 1.0
                if wm:
                    walk(wm.group(2), mult * t, depth + 1)
            elif op in ("call", "fusion"):
                cm = re.search(r"(?:to_apply|calls)=%?([\w.\-]+)", ln)
                if cm:
                    walk(cm.group(1), mult, depth + 1)

    walk(entry, 1.0)
    return sorted(((b, k, t) for (k, t), b in agg.items()), reverse=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--what", default="collectives",
                    choices=("collectives", "hbm"))
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)
    # takes effect only before this process first initializes a backend
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")

    from repro.configs import registry
    from repro.launch import dryrun
    from repro.launch.mesh import make_production_mesh
    cfg = registry.get_arch(args.arch)
    shape = registry.get_shape(args.shape)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    compiled = dryrun.lower_cell(cfg, shape, mesh).compile()
    rows = attribute(compiled.as_text(), args.what)
    unit = "GB (per device, per step)"
    print(f"{args.arch} x {args.shape} — top {args.what} by op_name, {unit}")
    for b, k, t in rows[: args.top]:
        print(f"{b / 1e9:9.2f}  {k:18s} {t}")


if __name__ == "__main__":
    main()
