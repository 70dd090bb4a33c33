"""Distributed engine tests: shard_map paths on 8 fake CPU devices, plus
device-free fault-tolerance units (`repro.distributed.fault`) that run
everywhere — the replication tier (repro.api.replication) leans on
PreemptionGuard/StragglerMonitor, so they get direct coverage here."""
import os

# must run before jax initializes; tests/conftest.py keeps other files at 1 dev
os.environ.setdefault("_REPRO_DIST_TEST", "1")

import signal
import threading
import time

import numpy as np
import pytest

import jax

from repro.distributed.fault import PreemptionGuard, StragglerMonitor

# shard_map tests need the 8-device mesh (run tests/dist/ via
# run_dist_tests.sh); the fault units below run on any device count
needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 fake devices (run tests/dist/ via run_dist_tests.sh)")

if jax.device_count() >= 8:
    import jax.numpy as jnp
    from repro.configs.base import EngineConfig
    from repro.core import distributed as dist
    from repro.core import metrics

    CFG = EngineConfig(dim=128, n_clusters=128, list_capacity=32, nprobe=8,
                       k=10, kmeans_iters=3)


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((4, 2), ("data", "model"))


# ---------------------------------------------------------------------------
# Fault-tolerance units (device-free; tier-1 everywhere)
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_preemption_guard_installs_on_main_thread():
    prev = signal.getsignal(signal.SIGTERM)
    guard = PreemptionGuard()
    try:
        assert guard.installed
        assert not guard.should_checkpoint
        # deliver the signal to ourselves: the handler must only set the
        # event, never raise into the serving loop
        signal.raise_signal(signal.SIGTERM)
        assert guard.should_checkpoint
        guard.reset()
        assert not guard.should_checkpoint
    finally:
        guard.uninstall()
    assert not guard.installed
    assert signal.getsignal(signal.SIGTERM) == prev


@pytest.mark.tier1
def test_preemption_guard_degrades_off_main_thread():
    """Off the main thread the guard must not touch signal handlers (the
    old code attempted the install and relied on ValueError) but stays
    functional through the programmatic request path."""
    prev = signal.getsignal(signal.SIGTERM)
    out = {}

    def make():
        g = PreemptionGuard()
        out["installed"] = g.installed
        g.request()
        out["requested"] = g.should_checkpoint
        g.uninstall()                      # no-op off-main: must not raise

    t = threading.Thread(target=make)
    t.start()
    t.join()
    assert out == {"installed": False, "requested": True}
    assert signal.getsignal(signal.SIGTERM) == prev


@pytest.mark.tier1
def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(window=16, threshold=2.0)
    for _ in range(8):                     # build the baseline median
        mon.start()
        out = mon.stop()
        assert not out["straggler"]
    mon.start()
    time.sleep(0.05)                       # >> the ~0s baseline median
    out = mon.stop()
    assert out["straggler"] and out["step_s"] >= 0.05
    assert mon.flagged == 1
    stats = mon.stats()
    assert stats["n"] == 9 and stats["flagged"] == 1


@pytest.mark.tier1
def test_straggler_monitor_stop_without_start_raises():
    mon = StragglerMonitor()
    assert not mon.running
    with pytest.raises(RuntimeError, match="without start"):
        mon.stop()
    mon.start()
    assert mon.running
    mon.stop()
    assert not mon.running
    with pytest.raises(RuntimeError):      # start() is consumed by stop()
        mon.stop()


# ---------------------------------------------------------------------------
# shard_map engine tests (8-device mesh)
# ---------------------------------------------------------------------------


def corpus(n=4096, d=128, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(32, d)).astype(np.float32) * 3
    x = centers[rng.integers(0, 32, n)] + rng.normal(size=(n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


@needs8
def test_dist_build_query_recall(mesh):
    x = corpus()
    ids = np.arange(4096, dtype=np.int32)
    with mesh:
        state, spilled = dist.dist_build(
            jax.random.PRNGKey(0), jnp.asarray(x), jnp.asarray(ids), CFG, mesh)
        got, _ = dist.dist_query(state, jnp.asarray(x[:16]), CFG, mesh, 10)
    true = metrics.brute_force_topk(x[:16], x, ids, 10)
    assert metrics.recall_at_k(np.asarray(got), true) > 0.9


@needs8
def test_dist_no_rows_lost(mesh):
    x = corpus(2048)
    ids = np.arange(2048, dtype=np.int32)
    with mesh:
        state, _ = dist.dist_build(
            jax.random.PRNGKey(1), jnp.asarray(x), jnp.asarray(ids), CFG, mesh)
    live = np.concatenate([np.asarray(state.list_ids).ravel(),
                           np.asarray(state.spill_ids).ravel()])
    live = live[live >= 0]
    assert len(np.unique(live)) == 2048


@needs8
def test_dist_insert_visible_globally(mesh):
    x = corpus(2048)
    ids = np.arange(2048, dtype=np.int32)
    with mesh:
        state, _ = dist.dist_build(
            jax.random.PRNGKey(2), jnp.asarray(x), jnp.asarray(ids), CFG, mesh)
        newx = jnp.asarray(corpus(64, seed=7))
        newids = jnp.asarray(np.arange(90000, 90064, dtype=np.int32))
        state, _ = dist.dist_insert(state, newx, newids, CFG, mesh)
        got, _ = dist.dist_query(state, newx[:8], CFG, mesh, 1)
    assert np.isin(np.asarray(got)[:, 0], np.arange(90000, 90064)).mean() > 0.8


@needs8
def test_elastic_reshard_roundtrip(tmp_path_factory):
    """Checkpoint on a 4x2 mesh, elastic-restart into a 2x4 mesh.

    Checkpoints store full arrays, so any live-device factorization can
    restore — the 1000-node failure story (DESIGN.md §7): lose hosts, call
    remesh(), reshard_restore(), resume.
    """
    import jax.numpy as jnp
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.configs import registry
    from repro.distributed import elastic
    from repro.models import lm, specs
    from repro.models.sharding import use_mesh

    cfg = registry.reduced_arch("granite-3-2b")
    mesh_a = jax.make_mesh((4, 2), ("data", "model"))
    with use_mesh(mesh_a):
        sh_a = specs.param_shardings(cfg, mesh_a)
        params = jax.jit(lambda k: lm.init_params(k, cfg),
                         out_shardings=sh_a)(jax.random.PRNGKey(0))

    ckpt = Checkpointer(str(tmp_path_factory.mktemp("elastic")))
    ckpt.save(7, params)

    # "failure": restart on a different factorization of the same devices
    mesh_b = elastic.remesh(model_pref=4)
    assert mesh_b.devices.shape == (2, 4)
    restored = elastic.reshard_restore(ckpt, params, mesh_b, cfg, step=7)

    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restored leaves actually live on the new mesh
    leaf = jax.tree.leaves(restored)[0]
    assert leaf.sharding.mesh.devices.shape == (2, 4)
