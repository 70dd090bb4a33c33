"""Engine behaviour tests: build/insert/delete/query/rebuild + recall."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import EngineConfig
from repro.core import index as ivf
from repro.core import metrics
from repro.core.engine import AgenticMemoryEngine

CFG = EngineConfig(dim=128, n_clusters=128, list_capacity=64, nprobe=16,
                   k=10, kmeans_iters=4)


def corpus(n=2000, d=128, n_centers=32, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_centers, d)).astype(np.float32) * 3
    x = centers[rng.integers(0, n_centers, n)] + rng.normal(size=(n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


@pytest.fixture(scope="module")
def built_engine():
    eng = AgenticMemoryEngine(CFG)
    eng.build(corpus())
    return eng


def test_build_keeps_all_rows(built_engine):
    s = built_engine.stats()
    assert s["live"] == 2000
    assert s["max_list"] <= CFG.list_capacity


def test_full_scan_recall(built_engine):
    x = corpus()
    ids, _ = built_engine.query(x[:64], k=10)   # full-scan route
    true = metrics.brute_force_topk(x[:64], x, np.arange(2000), 10)
    assert metrics.recall_at_k(ids, true) > 0.95


def test_probed_recall(built_engine):
    x = corpus()
    ids, _ = built_engine.query(x[:4], k=10, nprobe=32)   # probe route
    true = metrics.brute_force_topk(x[:4], x, np.arange(2000), 10)
    assert metrics.recall_at_k(ids, true) > 0.9


def test_probed_recall_increases_with_nprobe():
    eng = AgenticMemoryEngine(CFG)
    x = corpus()
    eng.build(x)
    true = metrics.brute_force_topk(x[:8], x, np.arange(2000), 10)
    recalls = []
    for nprobe in (1, 4, 16, 64):
        ids, _ = eng.query(x[:8], k=10, nprobe=nprobe)
        recalls.append(metrics.recall_at_k(ids, true))
    assert recalls == sorted(recalls), recalls
    assert recalls[-1] > 0.95


def test_insert_then_query_finds_new_rows():
    eng = AgenticMemoryEngine(CFG)
    x = corpus()
    eng.build(x)
    novel = corpus(seed=9)[:50]
    eng.insert(novel, ids=np.arange(50000, 50050))
    ids, _ = eng.query(novel[:10], k=1)
    assert np.isin(ids[:, 0], np.arange(50000, 50050)).mean() > 0.8


def test_delete_tombstones_then_rebuild_reclaims():
    eng = AgenticMemoryEngine(CFG)
    x = corpus()
    eng.build(x)
    eng.delete(np.arange(100))
    ids, _ = eng.query(x[:20], k=1)
    assert not np.isin(ids[:, 0], np.arange(100)).any()
    before = eng.stats()
    assert before["deleted"] == 100
    eng.rebuild()
    after = eng.stats()
    assert after["live"] == 1900
    ids2, _ = eng.query(x[150:160], k=1)
    assert (ids2[:, 0] == np.arange(150, 160)).mean() > 0.8


def test_spill_overflow_and_rebuild_drain():
    # tiny lists force spill
    cfg = EngineConfig(dim=128, n_clusters=128, list_capacity=8, nprobe=16,
                       k=5, kmeans_iters=2)
    eng = AgenticMemoryEngine(cfg, spill_capacity=8192)
    x = corpus(3000)
    eng.build(x)
    s = eng.stats()
    assert s["live"] == 3000          # nothing lost: overflow sits in spill
    assert s["spill"] > 0
    ids, _ = eng.query(x[:16], k=5)   # full scan covers spill rows
    true = metrics.brute_force_topk(x[:16], x, np.arange(3000), 5)
    assert metrics.recall_at_k(ids, true) > 0.9


def test_l2_metric_route():
    cfg = EngineConfig(dim=128, n_clusters=128, list_capacity=64, nprobe=16,
                       k=5, metric="l2", kmeans_iters=3)
    eng = AgenticMemoryEngine(cfg)
    x = corpus()
    eng.build(x)
    ids, _ = eng.query(x[:8], k=5)
    true = metrics.brute_force_topk(x[:8], x, np.arange(2000), 5, metric="l2")
    assert metrics.recall_at_k(ids, true) > 0.9


def test_property_live_count_conserved():
    """Property: build keeps every valid row somewhere (lists or spill)."""
    pytest.importorskip("hypothesis")     # dev-only dep (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(200, 1200), seed=st.integers(0, 1000))
    def check(n, seed):
        cfg = EngineConfig(dim=128, n_clusters=128, list_capacity=32,
                           kmeans_iters=1)
        x = jnp.asarray(corpus(n, seed=seed))
        ids = jnp.arange(n, dtype=jnp.int32)
        state, spilled = ivf.build(jax.random.PRNGKey(seed), x, ids, cfg,
                                   spill_capacity=4096)
        assert int(ivf.live_count(state)) == n
        # ids are unique across lists+spill
        all_ids = np.concatenate([np.asarray(state.list_ids).ravel(),
                                  np.asarray(state.spill_ids).ravel()])
        live = all_ids[all_ids >= 0]
        assert len(np.unique(live)) == n

    check()
