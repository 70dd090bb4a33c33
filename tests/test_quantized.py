"""Int8 quantized scan pipeline tests (executable spec).

Covers the asymmetric two-stage design end to end:

* affine int8 round-trip error is bounded by scale/2 per component;
* the Pallas q8 kernel matches the jnp reference over identical integer
  operands (both metrics);
* recall@10 of the quantized pipeline stays within 5% of the f32 pipeline
  on a synthetic workload (the "matched recall" acceptance bar);
* quantized store stays coherent through insert/delete/rebuild;
* the batching layer splits windows on dtype policy (int8 lanes never fuse
  with f32 lanes) while same-policy sharded int8 lanes still fuse into ONE
  dispatch, bitwise-equal to the per-op path;
* save/load round-trips the quantized store (sharded and unsharded), and
  the snapshot's dtype policy wins over the caller's cfg;
* stats report the policy's bytes-per-row and resident index bytes.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import MemoryOp, MemoryService
from repro.configs.base import EngineConfig
from repro.core import index as ivf
from repro.core import metrics
from repro.kernels import ops, ref

DIM = 128
QCFG = EngineConfig(dim=DIM, n_clusters=128, list_capacity=16, nprobe=8,
                    k=4, use_kernel=False, kmeans_iters=2,
                    store_dtype="int8", rescore_k=32)
FCFG = dataclasses.replace(QCFG, store_dtype="float32")


def _corpus(n, seed=0, dim=DIM):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, dim), dtype=np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _built(cfg, n=256, seed=0):
    x = jnp.asarray(_corpus(n, seed=seed))
    ids = jnp.arange(n, dtype=jnp.int32)
    state, _ = ivf.build(jax.random.PRNGKey(seed), x, ids, cfg)
    return state, x, ids


# ---------------------------------------------------------------------------
# Quantizer + kernel contracts
# ---------------------------------------------------------------------------

def test_config_rejects_unknown_store_dtype():
    with pytest.raises(ValueError, match="store_dtype"):
        EngineConfig(store_dtype="fp8")
    with pytest.raises(ValueError, match="rescore_k"):
        EngineConfig(rescore_k=0)


def test_affine_roundtrip_error_bound():
    """Dequantized rows differ from the originals by at most scale/2 per
    component (round-to-nearest onto a 254-step affine grid)."""
    state, x, ids = _built(QCFG, n=300, seed=1)
    lists = np.asarray(state.lists)
    live = np.asarray(state.list_ids) >= 0
    deq = (np.asarray(state.q_lists, dtype=np.float32)
           * np.asarray(state.q_scales)[:, None, None]
           + np.asarray(state.q_zeros)[:, None, None])
    err = np.abs(deq - lists)[live]
    bound = np.broadcast_to(
        np.asarray(state.q_scales)[:, None, None] / 2 + 1e-6,
        lists.shape)[live]
    assert (err <= bound).all()
    # stored norms are the dequantized-row norms (the L2 scan contract)
    norms = np.sum(deq * deq, axis=-1)
    np.testing.assert_allclose(np.asarray(state.q_norms)[live],
                               norms[live], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_q8_scan_kernel_matches_ref(metric):
    """Pallas kernel vs jnp oracle over identical integer operands: the
    epilogues share op order, so scores agree to float rounding."""
    rng = np.random.default_rng(2)
    n, b = 300, 5                               # deliberately unaligned
    rows = rng.standard_normal((n, DIM)).astype(np.float32)
    q = rng.standard_normal((b, DIM)).astype(np.float32)
    ids = np.arange(n, dtype=np.int32)
    ids[::7] = -1                               # tombstones mask
    codes, scales, zeros = [np.asarray(a) for a in
                            ivf._quantize_rows(jnp.asarray(rows),
                                               jnp.asarray(ids))[:3]]
    deq = codes.astype(np.float32) * scales[:, None] + zeros[:, None]
    norms = jnp.asarray(np.sum(deq * deq, axis=1)) if metric == "l2" else None
    got = ops.scan_scores_q8(
        jnp.asarray(q), jnp.asarray(codes), jnp.asarray(ids),
        jnp.asarray(scales), jnp.asarray(zeros), norms, metric=metric,
        use_kernel=True, block_m=8, block_n=128, block_k=128)
    want = ref.scan_scores_q8_ref(
        jnp.asarray(q), jnp.asarray(codes), jnp.asarray(ids),
        jnp.asarray(scales), jnp.asarray(zeros), norms, metric=metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Matched recall (the acceptance bar)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_recall_at_10_matches_f32(metric):
    n, k = 2048, 10
    qcfg = dataclasses.replace(QCFG, metric=metric, k=k, rescore_k=64)
    fcfg = dataclasses.replace(qcfg, store_dtype="float32")
    x = jnp.asarray(_corpus(n, seed=3))
    ids = jnp.arange(n, dtype=jnp.int32)
    qs, fs = (ivf.build(jax.random.PRNGKey(3), x, ids, c)[0]
              for c in (qcfg, fcfg))
    q = jnp.asarray(_corpus(64, seed=4))
    true_ids = metrics.brute_force_topk(np.asarray(q), np.asarray(x),
                                        np.asarray(ids), k, metric=metric)
    got_q, _ = ivf.query_full_scan(qs, q, qcfg, k)
    got_f, _ = ivf.query_full_scan(fs, q, fcfg, k)
    r_q = metrics.recall_at_k(np.asarray(got_q), true_ids)
    r_f = metrics.recall_at_k(np.asarray(got_f), true_ids)
    assert r_q >= 0.95 * r_f, (r_q, r_f)
    assert r_f >= 0.99                           # sanity: f32 scan is exact


def test_rescored_rows_are_exact_f32():
    """query_full_scan_rows under int8 policy returns the ORIGINAL f32
    vectors of the winners (rescore gathers from the exact tier), never
    dequantized approximations."""
    state, x, ids = _built(QCFG, n=256, seed=5)
    got_ids, _, rows = ivf.query_full_scan_rows(state, x[:8], QCFG, 1)
    np.testing.assert_array_equal(np.asarray(got_ids[:, 0]),
                                  np.asarray(ids[:8]))
    np.testing.assert_allclose(np.asarray(rows[:, 0]), np.asarray(x[:8]),
                               rtol=0, atol=1e-7)


# ---------------------------------------------------------------------------
# Write-path coherence
# ---------------------------------------------------------------------------

def test_quantized_store_coherent_through_insert_delete_rebuild():
    state, x, ids = _built(QCFG, n=256, seed=6)
    x2 = jnp.asarray(_corpus(16, seed=7))
    ids2 = jnp.arange(1000, 1016, dtype=jnp.int32)
    state, _ = ivf.insert(state, x2, ids2, QCFG)
    got, _ = ivf.query_full_scan(state, x2, QCFG, 1)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(ids2))
    state, n_del = ivf.delete(state, ids2[:8])
    assert int(n_del) == 8
    got, _ = ivf.query_full_scan(state, x2[:8], QCFG, 1)
    assert not np.isin(np.asarray(got[:, 0]), np.asarray(ids2[:8])).any()
    state, _ = ivf.rebuild(jax.random.PRNGKey(8), state, QCFG)
    assert state.quantized
    got, _ = ivf.query_full_scan(state, x2[8:], QCFG, 1)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(ids2[8:]))


def test_probed_path_matches_full_scan_top1():
    state, x, ids = _built(QCFG, n=256, seed=9)
    got, _ = ivf.query_probed(state, x[:16], QCFG, 1, QCFG.nprobe)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(ids[:16]))


# ---------------------------------------------------------------------------
# Policy: fusion-window splitting + stats + persistence
# ---------------------------------------------------------------------------

def test_mixed_dtype_window_splits():
    """An int8 lane and an f32 lane in one batched window -> 2 dispatch
    groups (store_dtype is an explicit batch-signature element)."""
    svc = MemoryService(maintenance=False)
    try:
        for name, cfg, seed in (("q0", QCFG, 10), ("q1", QCFG, 11),
                                ("f0", FCFG, 12)):
            svc.create_collection(name, cfg)
            svc.build(name, _corpus(256, seed=seed))
        qs = {n: _corpus(4, seed=20 + i)
              for i, n in enumerate(("q0", "q1", "f0"))}
        sync = {n: svc.query(n, q, k=4) for n, q in qs.items()}
        futs = {n: svc.submit(MemoryOp("query", n, q, k=4, batch=True))
                for n, q in qs.items()}
        assert svc.flush() == 2      # {q0,q1} fuse; f0 is its own group
        for n in qs:
            ids_, scores_ = futs[n].result(timeout=60)
            np.testing.assert_array_equal(ids_, sync[n][0])
            np.testing.assert_array_equal(scores_, sync[n][1])
        st = svc.stats()["collections"]
        # int8 storage keeps BOTH the quantized codes (1 B/component, the
        # scan operand stream) and the retained f32 rows (4 B/component,
        # the exact-rescore source) resident
        assert st["q0"]["bytes_per_row"] == 5 * DIM
        assert st["q0"]["scan_bytes_per_row"] == DIM     # 1 byte/component
        assert st["f0"]["bytes_per_row"] == 4 * DIM
        assert st["f0"]["scan_bytes_per_row"] == 4 * DIM
        assert st["q0"]["store_dtype"] == "int8"
        assert st["q0"]["index_bytes"] > 0
    finally:
        svc.shutdown()


def test_quantized_save_load_roundtrip(tmp_path):
    from repro.api import Collection
    coll = Collection("qc", QCFG)
    coll.build(jnp.asarray(_corpus(256, seed=13)),
               ids=jnp.arange(256, dtype=jnp.int32))
    q = jnp.asarray(_corpus(8, seed=14))
    want = coll.query(q, k=4)
    d = str(tmp_path / "qc")
    coll.save_into(d)
    # load with an f32 cfg: the snapshot's int8 policy must win
    back = Collection.load_from(d, "qc", FCFG)
    assert back.cfg.store_dtype == "int8"
    assert back.snapshot().quantized
    got = back.query(q, k=4)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# ---------------------------------------------------------------------------
# Sharded: fusion + persistence (needs the 2 fake CPU devices)
# ---------------------------------------------------------------------------

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >= 2 devices (tests/conftest.py forces 2 fake CPU devices)")


@needs_mesh
def test_sharded_quantized_lanes_fuse_bitwise_equal():
    from repro.core import distributed as dce
    mesh = jax.make_mesh((2,), ("shard",))
    scfg = dataclasses.replace(QCFG, shard_db=True)
    svc = MemoryService(maintenance=False)
    try:
        for i, name in enumerate(("sq0", "sq1")):
            svc.create_collection(name, scfg, mesh=mesh)
            svc.build(name, _corpus(256, seed=30 + i),
                      ids=np.arange(i * 10_000, i * 10_000 + 256))
        qs = {n: _corpus(3 + i, seed=40 + i)
              for i, n in enumerate(("sq0", "sq1"))}
        coll = svc.collection("sq0")
        ref_ids, ref_scores = dce.dist_query(coll.snapshot(), qs["sq0"],
                                             scfg, mesh, 4)
        futs = {n: svc.submit(MemoryOp("query", n, q, k=4, batch=True))
                for n, q in qs.items()}
        assert svc.flush() == 1      # ONE dispatch for both int8 tenants
        ids0, scores0 = futs["sq0"].result(timeout=60)
        np.testing.assert_array_equal(ids0, np.asarray(ref_ids))
        np.testing.assert_array_equal(scores0, np.asarray(ref_scores))
        assert (futs["sq1"].result(timeout=60)[0] // 10_000 == 1).all()
    finally:
        svc.shutdown()


@needs_mesh
def test_sharded_quantized_save_load_roundtrip(tmp_path):
    from repro.api import Collection
    mesh = jax.make_mesh((2,), ("shard",))
    scfg = dataclasses.replace(QCFG, shard_db=True)
    coll = Collection("sq", scfg, mesh=mesh)
    coll.build(jnp.asarray(_corpus(256, seed=15)),
               ids=jnp.arange(256, dtype=jnp.int32))
    q = jnp.asarray(_corpus(8, seed=16))
    want = coll.query(q, k=4)
    d = str(tmp_path / "sq")
    coll.save_into(d)
    back = Collection.load_from(d, "sq", scfg, mesh=mesh)
    assert back.snapshot().quantized
    got = back.query(q, k=4)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
