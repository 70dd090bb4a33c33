"""Serving driver: batched RAG generation with the agentic memory engine.

``python -m repro.launch.serve --arch granite-3-2b --requests 8``

This is the paper's full loop on TPU-shaped substrate: build an IVF memory
over a synthetic corpus, accept a batch of token "requests", embed each,
retrieve top-k memories (fused GEMM scan), splice them into the prompt as
soft-prefix embeddings, prefill, then decode N tokens — with concurrent
inserts running through the windowed scheduler (the paper's query-update
hybrid template).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.api import MemoryOp, MemoryService
from repro.configs import registry
from repro.configs.base import EngineConfig
from repro.core.scheduler import WindowedScheduler
from repro.launch.mesh import make_production_mesh
from repro.models import api, lm
from repro.models.sharding import use_mesh
from repro.serving import rag, serve_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    choices=registry.list_archs())
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--corpus", type=int, default=4096)
    ap.add_argument("--mem-k", type=int, default=4)
    ap.add_argument("--concurrent-inserts", type=int, default=256)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = registry.reduced_arch(args.arch)
    if cfg.family == "encdec":
        raise SystemExit("serve driver targets decoder LMs; use examples/"
                         "quickstart.py for the enc-dec path")
    ecfg = EngineConfig(dim=cfg.d_model, n_clusters=128, list_capacity=64,
                        nprobe=16, k=args.mem_k)
    mesh = make_production_mesh() if args.production_mesh else None

    key = jax.random.PRNGKey(args.seed)
    with use_mesh(mesh):
        params = lm.init_params(key, cfg)

    # ---- agentic memory: build + concurrent inserts via the scheduler ----
    sched = WindowedScheduler(window=ecfg.window)
    svc = MemoryService(scheduler=sched)
    memory = svc.create_collection("serve", ecfg)
    corpus = np.random.default_rng(args.seed).standard_normal(
        (args.corpus, ecfg.dim), dtype=np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    stats = svc.build("serve", corpus)
    print(f"memory built: {args.corpus} vectors in {stats['build_s']:.2f}s")

    ins = np.random.default_rng(args.seed + 1).standard_normal(
        (args.concurrent_inserts, ecfg.dim), dtype=np.float32)
    futs = [svc.submit(MemoryOp("insert", "serve", ins[i: i + 32],
                                concurrent=True))
            for i in range(0, len(ins), 32)]

    # ---- batched requests through the RAG prefill + decode loop ----
    batch = api.synth_batch(jax.random.PRNGKey(args.seed + 2), cfg,
                            "prefill", args.requests, args.prompt_len)
    s_max = args.prompt_len + args.decode_steps + 1
    prefill = jax.jit(rag.make_rag_prefill(cfg, ecfg, s_max, k=args.mem_k))
    decode = serve_step.make_decode(cfg)

    with use_mesh(mesh):
        t1 = time.perf_counter()
        logits, caches, pos, mem_ids = prefill(params, memory.snapshot(),
                                               batch)
        tok = jnp.argmax(
            jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab_size, logits,
                      -jnp.inf), -1).astype(jnp.int32)[:, None]
        out = [tok]
        for _ in range(args.decode_steps - 1):
            pos = pos + 1
            tok, caches = decode(params, tok, caches, pos)
            out.append(tok)
        seq = jnp.concatenate(out, axis=1)
        jax.block_until_ready(seq)
        t2 = time.perf_counter()

    for f in futs:
        f.result()
    sched.shutdown()
    n_tok = args.requests * args.decode_steps
    print(f"retrieved memory ids (req 0): {np.asarray(mem_ids)[0].tolist()}")
    print(f"generated {n_tok} tokens in {t2 - t1:.2f}s "
          f"({n_tok / (t2 - t1):.1f} tok/s on "
          f"{jax.devices()[0].device_kind})")
    print(f"memory stats: {memory.stats()}")
    print(f"scheduler: {sched.stats()}")


if __name__ == "__main__":
    main()
