"""512-device multi-pod dry-run: lower + compile every (arch x shape x mesh)
cell and extract memory / cost / collective evidence for the roofline.

This is the proof of large-scale runnability required by the spec: a cell
that fails to lower (sharding mismatch), fails to compile (unsupported
collective), or does not fit per-device HBM (memory_analysis) is a bug in
the system, not in the methodology.

All recorded HLO-derived numbers are PER DEVICE (the partitioned module's
shapes are shard shapes); roofline terms follow directly (launch/roofline.py).

The 512 host devices are requested in `main`, not at import, so importing
this module (the lowering tests do) never changes a process's device count.
"""
import argparse
import json
import os
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh, describe
from repro.models import api, lm, specs
from repro.models.sharding import use_mesh
from repro.train import optimizer
from repro.train.train_step import make_train_step


# ---------------------------------------------------------------------------
# Shardings for step inputs
# ---------------------------------------------------------------------------

def _axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _batch_sharding(mesh, shape: Tuple[int, ...]) -> NamedSharding:
    """Shard dim 0 over the data axes when divisible, else replicate."""
    axes = _data_axes(mesh)
    sizes = _axis_sizes(mesh)
    n = 1
    for a in axes:
        n *= sizes[a]
    first = axes if (shape and shape[0] % n == 0) else None
    return NamedSharding(mesh, P(first, *([None] * (len(shape) - 1))))


def batch_shardings(batch_specs: Dict[str, Any], mesh):
    return {k: _batch_sharding(mesh, v.shape) for k, v in batch_specs.items()}


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input (spec item 2)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """All step inputs as ShapeDtypeStructs (no allocation).

    train  -> {params, opt_state, batch, key}
    prefill-> {params, batch}
    decode -> {params, token, caches, pos}
    """
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    if shape.kind == "train":
        return {
            "params": params,
            "opt_state": jax.eval_shape(optimizer.init, params),
            "batch": api.train_batch_specs(cfg, shape),
            "key": jax.ShapeDtypeStruct((2,), jnp.uint32),
        }
    if shape.kind == "prefill":
        return {"params": params, "batch": api.prefill_batch_specs(cfg, shape)}
    token, caches, pos = api.decode_inputs_specs(cfg, shape)
    return {"params": params, "token": token, "caches": caches, "pos": pos}


# ---------------------------------------------------------------------------
# Lowerings per shape kind
# ---------------------------------------------------------------------------

def lower_train(cfg: ModelConfig, shape: ShapeConfig, mesh,
                tc: Optional[TrainConfig] = None):
    tc = tc or TrainConfig()
    step = make_train_step(cfg, tc)
    si = input_specs(cfg, shape)
    p_shard = specs.param_shardings(cfg, mesh)
    opt_shard = optimizer.OptState(
        step=replicated(mesh),
        mu=jax.tree.map(lambda s: s, p_shard),
        nu=jax.tree.map(lambda s: s, p_shard))
    b_shard = batch_shardings(si["batch"], mesh)
    jitted = jax.jit(
        step,
        in_shardings=(p_shard, opt_shard, b_shard, replicated(mesh)),
        out_shardings=(p_shard, opt_shard, None),
        donate_argnums=(0, 1),
    )
    return jitted.lower(si["params"], si["opt_state"], si["batch"], si["key"])


def lower_prefill(cfg: ModelConfig, shape: ShapeConfig, mesh):
    s_max = shape.seq_len // 2 if cfg.is_encdec else shape.seq_len

    def prefill_step(params, batch):
        logits, caches, pos = lm.prefill(params, cfg, batch, s_max)
        return logits, caches, pos

    si = input_specs(cfg, shape)
    p_shard = specs.param_shardings(cfg, mesh)
    b_shard = batch_shardings(si["batch"], mesh)
    jitted = jax.jit(prefill_step, in_shardings=(p_shard, b_shard))
    return jitted.lower(si["params"], si["batch"])


def lower_decode(cfg: ModelConfig, shape: ShapeConfig, mesh):
    def decode(params, token, caches, pos):
        logits, caches = lm.decode_step(params, cfg, token, caches, pos)
        nxt = jnp.argmax(
            jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab_size,
                      logits, -jnp.inf), -1).astype(jnp.int32)[:, None]
        return nxt, caches

    si = input_specs(cfg, shape)
    p_shard = specs.param_shardings(cfg, mesh)
    c_shard = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs.cache_specs(cfg, mesh, si["caches"]))
    t_shard = _batch_sharding(mesh, si["token"].shape)
    pos_shard = _batch_sharding(mesh, si["pos"].shape)
    jitted = jax.jit(
        decode,
        in_shardings=(p_shard, t_shard, c_shard, pos_shard),
        out_shardings=(t_shard, c_shard),
        donate_argnums=(2,),
    )
    return jitted.lower(si["params"], si["token"], si["caches"], si["pos"])


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               tc: Optional[TrainConfig] = None):
    with use_mesh(mesh):
        if shape.kind == "train":
            return lower_train(cfg, shape, mesh, tc)
        if shape.kind == "prefill":
            return lower_prefill(cfg, shape, mesh)
        return lower_decode(cfg, shape, mesh)


# ---------------------------------------------------------------------------
# Record extraction
# ---------------------------------------------------------------------------

def _mem_dict(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes", "host_argument_size_in_bytes",
            "host_output_size_in_bytes", "host_temp_size_in_bytes",
            "peak_memory_in_bytes", "serialized_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    return out


def _cost_dict(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items()
            if isinstance(v, (int, float))}


def analyze(lowered, compiled, cfg: ModelConfig, shape: ShapeConfig,
            mesh) -> Dict[str, Any]:
    hlo = compiled.as_text()
    roll = hlo_analysis.rollup(hlo)
    n_dev = mesh.devices.size
    tokens = shape.global_batch * (
        1 if shape.is_decode else
        (shape.seq_len // 2 if cfg.is_encdec else shape.seq_len))
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens
    rec = {
        "arch": cfg.name,
        "shape": shape.name,
        "kind": shape.kind,
        "mesh": describe(mesh),
        "n_devices": n_dev,
        "tokens_per_step": tokens,
        "params": n_params,
        "active_params": n_active,
        "model_flops_total": float(model_flops),
        "memory_analysis": _mem_dict(compiled),
        "cost_analysis_xla": _cost_dict(compiled),
        "hlo_rollup_per_device": {
            "dot_flops": roll["dot_flops"],
            "collective_bytes": roll["collective_bytes"],
            "collective_bytes_total": roll["collective_bytes_total"],
            "hbm_bytes_est": roll["hbm_bytes_est"],
            "hbm_bytes_lower": roll["hbm_bytes_lower"],
            "hbm_by_op": {k: v for k, v in sorted(
                roll["hbm_by_op"].items(), key=lambda kv: -kv[1])[:8]},
        },
        "hlo_bytes": len(hlo),
    }
    return rec


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             out_dir: Optional[str] = None,
             tc: Optional[TrainConfig] = None,
             mesh=None) -> Dict[str, Any]:
    cfg = registry.get_arch(arch)
    shape = registry.get_shape(shape_name)
    ok, why = registry.cell_enabled(cfg, shape)
    mesh_tag = "pod2" if multi_pod else "pod1"
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "skipped", "reason": why}
        _dump(rec, out_dir, arch, shape_name, mesh_tag)
        return rec
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    try:
        lowered = lower_cell(cfg, shape, mesh, tc)
        t_lower = time.perf_counter() - t0
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t1
        rec = analyze(lowered, compiled, cfg, shape, mesh)
        rec.update(status="ok", lower_s=round(t_lower, 2),
                   compile_s=round(t_compile, 2))
    except Exception as e:  # a failing cell is a bug; record it loudly
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "FAILED", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    _dump(rec, out_dir, arch, shape_name, mesh_tag)
    return rec


def _dump(rec, out_dir, arch, shape_name, mesh_tag):
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main(argv=None):
    ap = argparse.ArgumentParser(description="512-device multi-pod dry-run")
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, help="shape id (default: all 4)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (512 chips) instead of 16x16 (256)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--remat", default="block")
    ap.add_argument("--print-memory", action="store_true")
    args = ap.parse_args(argv)
    # takes effect only before this process first initializes a backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    archs = [args.arch] if args.arch else registry.list_archs()
    shapes = [args.shape] if args.shape else list(
        ("train_4k", "prefill_32k", "decode_32k", "long_500k"))
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    tc = TrainConfig(remat_policy=args.remat)
    n_fail = 0
    for mp in meshes:
        mesh = make_production_mesh(multi_pod=mp)
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, multi_pod=mp, out_dir=args.out, tc=tc,
                               mesh=mesh)
                st = rec["status"]
                line = f"[{rec.get('mesh')}] {a} x {s}: {st}"
                if st == "ok":
                    mem = rec["memory_analysis"]
                    peak = mem.get("peak_memory_in_bytes", 0) / 2**30
                    args_gb = mem.get("argument_size_in_bytes", 0) / 2**30
                    line += (f"  lower={rec['lower_s']}s"
                             f" compile={rec['compile_s']}s"
                             f" args={args_gb:.2f}GiB peak={peak:.2f}GiB"
                             f" dotF/dev={rec['hlo_rollup_per_device']['dot_flops']:.3e}"
                             f" collB/dev={rec['hlo_rollup_per_device']['collective_bytes_total']:.3e}")
                elif st == "FAILED":
                    n_fail += 1
                    line += "  " + rec["error"]
                else:
                    line += f"  ({rec['reason']})"
                print(line, flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} cells FAILED")


if __name__ == "__main__":
    main()
