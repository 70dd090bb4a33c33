"""Mixture-of-Experts layer (olmoe / deepseek-moe).

Dispatch is gather-based and per-sequence (no [T, E, C] one-hot): for each
(batch row, expert) we take the top-C tokens that routed to that expert
(C = capacity_factor * S * top_k / E), gather them into a dense [B, E, C, D]
buffer, run the expert FFNs as one grouped einsum with the expert axis
sharded over 'model' (EP), and scatter-add the weighted results back.
Tokens beyond capacity are dropped (standard capacity semantics).

EP collective schedule (§Perf iteration 1 for deepseek-moe/train_4k): under
plain GSPMD the combine scatter-add has an E-sharded update and a
model-replicated target, so the partitioner REPLICATES the whole [B,E,C,D]
dispatch buffer over the model axis — a 10.7 GB/layer all-reduce (measured:
481 GB/step fwd + 240 GB bwd for the gather transpose).  `_expert_ffn_sharded`
instead runs gather->FFN->local scatter-add inside a `shard_map` over the
mesh, reducing the combine to ONE [B_local,S,D] psum per layer (536 MB) and
making the gather's transpose a local scatter + the same psum.  FSDP gathers
of the expert weights happen explicitly inside the body (all_gather over
'data'), whose transpose is the proper ZeRO-3 reduce-scatter of grads.

deepseek-moe: `num_shared_experts` always-on experts run as a plain dense
gated MLP of width shared*d_ff_expert in parallel with the routed experts.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.sharding import current_mesh, shard


def moe_init(key, cfg: ModelConfig):
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(ks[0], (d, e)),
        "wi": layers.dense_init(ks[1], (e, d, f)),
        "wu": layers.dense_init(ks[2], (e, d, f)),
        "wo": layers.dense_init(ks[3], (e, f, d)),
    }
    if cfg.num_shared_experts:
        p["shared"] = layers.mlp_init(
            ks[4], d, cfg.num_shared_experts * cfg.d_ff_expert)
    return p


def _capacity(cfg: ModelConfig, seq: int) -> int:
    c = int(cfg.capacity_factor * seq * cfg.moe_top_k / cfg.num_experts)
    return min(seq, max(8, -(-c // 8) * 8))


def moe_apply(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x [B,S,D] -> (y [B,S,D], aux_loss scalar)."""
    dt = x.dtype
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.moe_top_k
    cap = _capacity(cfg, s)

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(dt)).astype(jnp.float32)
    # Constrain logits replicated-over-model so the ROUTER BACKWARD reduces
    # grad_logits [B,S,E] (16 MB) instead of grad_x [B,S,D] (536 MB) — a 32x
    # smaller all-reduce (§Perf deepseek iteration 2a: 60 GB -> 2 GB/step).
    logits = shard(logits, "batch", None, None)
    probs = jax.nn.softmax(logits, axis=-1)
    # replicated over model: the token-level top-k is tiny ([B,S,E]) and
    # GSPMD otherwise all-gathers it per layer (§Perf deepseek iteration 2c)
    probs = shard(probs, "batch", None, None)

    # top-k mask per token
    topv, _ = jax.lax.top_k(probs, k)                       # [B,S,k]
    thresh = topv[..., -1:]
    sel = probs >= thresh                                   # [B,S,E] ~k True
    gate = jnp.where(sel, probs, 0.0)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    frac_tokens = jnp.mean(sel.astype(jnp.float32), axis=(0, 1))   # [E]
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs)

    # per-(row, expert) top-C token selection; E-sharded so the top-C runs
    # shard-local (§Perf deepseek iteration 2b: kills 2x16 GB of all-gather
    # that GSPMD inserted when it replicated esc for top_k)
    esc = jnp.where(sel, probs, -1.0).transpose(0, 2, 1)    # [B,E,S]
    esc = shard(esc, "batch", "expert", None)
    cval, cidx = jax.lax.top_k(esc, cap)                    # [B,E,C]
    valid = cval > 0.0
    cgate = jnp.take_along_axis(gate.transpose(0, 2, 1), cidx, axis=-1)
    cgate = jnp.where(valid, cgate, 0.0)                    # [B,E,C]

    # gather -> grouped FFN (expert axis sharded over 'model') -> scatter-add
    y = _expert_ffn(p, x, cidx, cgate, cfg)

    if cfg.num_shared_experts:
        y = y + layers.mlp_apply(p["shared"], x, cfg.act)
    return y, aux


def _ffn_body(x_l, cidx_l, cgate_l, wi, wu, wo, *, act: str,
              gather_axis: str = ""):
    """Dispatch + grouped FFN + combine on (possibly shard-local) arrays."""
    dt = x_l.dtype
    b = x_l.shape[0]
    if gather_axis:                       # explicit ZeRO-3 gather of weights
        wi = jax.lax.all_gather(wi, gather_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, gather_axis, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, gather_axis, axis=2, tiled=True)
    xe = jnp.take_along_axis(x_l[:, None, :, :],
                             cidx_l[..., None], axis=2)     # [B,E_l,C,D]
    h = jnp.einsum("becd,edf->becf", xe, wi.astype(dt))
    u = jnp.einsum("becd,edf->becf", xe, wu.astype(dt))
    actf = jax.nn.gelu if act == "gelu" else jax.nn.silu
    ye = jnp.einsum("becf,efd->becd", actf(h) * u, wo.astype(dt))
    ye = ye * cgate_l[..., None].astype(dt)
    y = jnp.zeros_like(x_l)
    return y.at[jnp.arange(b)[:, None, None], cidx_l].add(ye)


def _expert_ffn(p, x, cidx, cgate, cfg: ModelConfig):
    """EP execution of the routed experts; shard_map when a mesh is active."""
    mesh = current_mesh()
    b = x.shape[0]
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        m = sizes.get("model", 1)
        bax = tuple(a for a in ("pod", "data") if a in sizes)
        dp = 1
        for a in bax:
            dp *= sizes[a]
        fsdp = "data" if (sizes.get("data", 1) > 1
                          and p["wi"].shape[1] % sizes["data"] == 0) else ""
        if m > 1 and b % dp == 0 and cfg.num_experts % m == 0:
            body = functools.partial(_ffn_body, act=cfg.act,
                                     gather_axis=fsdp)

            def mapped(x_, cidx_, cgate_, wi_, wu_, wo_):
                y_p = body(x_, cidx_, cgate_, wi_, wu_, wo_)
                return jax.lax.psum(y_p, "model")   # ONE [B_l,S,D] combine

            bspec = bax if len(bax) > 1 else (bax[0] if bax else None)
            wspec = ("data" if fsdp else None)
            fn = shard_map(
                mapped, mesh=mesh,
                in_specs=(P(bspec, None, None),
                          P(bspec, "model", None),
                          P(bspec, "model", None),
                          P("model", wspec, None),
                          P("model", wspec, None),
                          P("model", None, wspec)),
                out_specs=P(bspec, None, None),
                check_vma=False,
            )
            return fn(x, cidx, cgate, p["wi"], p["wu"], p["wo"])
    # no mesh / non-divisible: plain GSPMD path (smoke tests, tiny meshes)
    return _ffn_body(x, cidx, cgate, p["wi"], p["wu"], p["wo"], act=cfg.act)


def moe_specs(cfg: ModelConfig):
    sp = {"router": (None, None),
          "wi": ("expert", "fsdp", None),
          "wu": ("expert", "fsdp", None),
          "wo": ("expert", None, "fsdp")}
    if cfg.num_shared_experts:
        sp["shared"] = layers.mlp_specs()
    return sp
