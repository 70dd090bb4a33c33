"""Multi-tenant MemoryService API tests.

Covers the redesign's contract: collection isolation, async future
semantics (submit -> result, error propagation), cross-collection batched
execution equal to per-collection execution, service-level persistence,
and counter thread-safety under concurrent scheduler workers.
"""
import numpy as np
import pytest

from repro.api import Collection, MemoryOp, MemoryService, OpFuture
from repro.configs.base import EngineConfig
from repro.core import metrics

CFG = EngineConfig(dim=128, n_clusters=128, list_capacity=64, nprobe=16,
                   k=5, use_kernel=False, kmeans_iters=3)


def _corpus(n=1500, dim=128, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((32, dim), dtype=np.float32)
    x = centers[rng.integers(0, 32, n)] + 0.15 * rng.standard_normal(
        (n, dim), dtype=np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


@pytest.fixture(scope="module")
def service():
    svc = MemoryService()
    xa, xb = _corpus(seed=1), _corpus(seed=2)
    svc.create_collection("alpha", CFG)
    svc.create_collection("beta", CFG)
    svc.build("alpha", xa)                                # ids 0..n-1
    svc.build("beta", xb, ids=np.arange(50_000, 51_500))  # disjoint id space
    yield svc, xa, xb
    svc.shutdown()


# ---------------------------------------------------------------------------
# Collection registry + isolation
# ---------------------------------------------------------------------------

def test_registry_semantics(service):
    svc, *_ = service
    assert "alpha" in svc and "missing" not in svc
    assert svc.list_collections()[:2] == ["alpha", "beta"]
    with pytest.raises(ValueError):
        svc.create_collection("alpha", CFG)       # duplicate
    with pytest.raises(ValueError):
        svc.create_collection("bad/name", CFG)    # unsafe for namespacing
    with pytest.raises(KeyError):
        svc.collection("missing")


def test_collections_are_isolated(service):
    """Queries never cross collections; id spaces are independent."""
    svc, xa, xb = service
    ids_a, _ = svc.query("alpha", xa[:16], k=5)
    ids_b, _ = svc.query("beta", xb[:16], k=5)
    assert (ids_a < 50_000).all()                 # only alpha's ids
    assert (ids_b >= 50_000).all()                # only beta's ids
    # recall stays high per tenant (no cross-tenant pollution)
    true_a = metrics.brute_force_topk(xa[:16], xa, np.arange(len(xa)), 5)
    assert metrics.recall_at_k(ids_a, true_a) >= 0.85


def test_same_external_ids_do_not_collide(service):
    """Two tenants can reuse the same external ids without interference."""
    svc, *_ = service
    x1, x2 = _corpus(300, seed=5), _corpus(300, seed=6)
    svc.create_collection("t1", CFG)
    svc.create_collection("t2", CFG)
    svc.build("t1", x1, ids=np.arange(300))
    svc.build("t2", x2, ids=np.arange(300))
    ids1, _ = svc.query("t1", x1[:8], k=1)
    ids2, _ = svc.query("t2", x2[:8], k=1)
    # same id values, different vectors behind them
    r1 = svc.collection("t1").stats()
    r2 = svc.collection("t2").stats()
    assert r1["live"] == r2["live"] == 300
    assert (ids1[:, 0] == np.arange(8)).mean() > 0.8
    assert (ids2[:, 0] == np.arange(8)).mean() > 0.8


# ---------------------------------------------------------------------------
# Futures
# ---------------------------------------------------------------------------

def test_future_semantics(service):
    svc, xa, _ = service
    fut = svc.submit(MemoryOp("query", "alpha", xa[:4], k=5))
    assert isinstance(fut, OpFuture)
    ids, scores = fut.result(timeout=60)
    assert fut.done() and fut.exception() is None
    assert ids.shape == (4, 5) and scores.shape == (4, 5)
    # result() is idempotent
    ids2, _ = fut.result()
    np.testing.assert_array_equal(ids, ids2)


def test_future_error_propagation(service):
    svc, xa, _ = service
    svc.create_collection("unbuilt", CFG)
    fut = svc.submit(MemoryOp("insert", "unbuilt", xa[:4]))
    with pytest.raises(AssertionError, match="build"):
        fut.result(timeout=60)
    assert isinstance(fut.exception(), AssertionError)
    # unknown collection fails fast at submit, not at result
    with pytest.raises(KeyError):
        svc.submit(MemoryOp("query", "nope", xa[:4]))
    # malformed ops rejected at construction
    with pytest.raises(ValueError):
        MemoryOp("compact", "alpha")
    with pytest.raises(ValueError):
        MemoryOp("insert", "alpha", xa[:4], batch=True)


def test_async_insert_then_query(service):
    svc, xa, _ = service
    fresh = _corpus(64, seed=9)
    fut = svc.submit(MemoryOp("insert", "alpha", fresh,
                              ids=np.arange(90_000, 90_064),
                              concurrent=True))
    assert fut.result(timeout=60) == 0            # nothing spilled
    ids, _ = svc.query("alpha", fresh[:8], k=1)
    assert (ids[:, 0] >= 90_000).mean() > 0.8


# ---------------------------------------------------------------------------
# Cross-collection batched execution
# ---------------------------------------------------------------------------

def test_batched_equals_sync_equals_futures(service):
    """The acceptance invariant: identical results via all three paths."""
    svc, xa, xb = service
    qa, qb = xa[:6], xb[:9]                       # unequal batches -> padding
    sync_a = svc.query("alpha", qa, k=5)
    sync_b = svc.query("beta", qb, k=5)
    fut_a = svc.submit(MemoryOp("query", "alpha", qa, k=5)).result()
    fut_b = svc.submit(MemoryOp("query", "beta", qb, k=5)).result()
    (bat_a, bat_b) = svc.query_many([("alpha", qa), ("beta", qb)], k=5)
    for (ids, scores) in (fut_a, bat_a):
        np.testing.assert_array_equal(ids, sync_a[0])
        np.testing.assert_allclose(scores, sync_a[1], rtol=1e-5, atol=1e-5)
    for (ids, scores) in (fut_b, bat_b):
        np.testing.assert_array_equal(ids, sync_b[0])
        np.testing.assert_allclose(scores, sync_b[1], rtol=1e-5, atol=1e-5)


def test_batched_mixed_signatures_and_lane_merge(service):
    """Same-collection ops merge into one lane; signature mismatches split."""
    svc, xa, xb = service
    reqs = [("alpha", xa[:3]), ("beta", xb[:3]), ("alpha", xa[3:7])]
    out = svc.query_many(reqs, k=5, path="full_scan")
    np.testing.assert_array_equal(
        out[0][0], svc.query("alpha", xa[:3], k=5, path="full_scan")[0])
    np.testing.assert_array_equal(
        out[2][0], svc.query("alpha", xa[3:7], k=5, path="full_scan")[0])
    # different k -> different signature -> still correct, just unfused
    o1 = svc.query_many([("alpha", xa[:3])], k=3)
    assert o1[0][0].shape == (3, 3)


def test_batch_window_autoflush(service):
    svc, xa, xb = service
    futs = [svc.submit(MemoryOp("query", "alpha" if i % 2 else "beta",
                                (xa if i % 2 else xb)[:2], k=5, batch=True))
            for i in range(svc.batch_window)]     # hits the window -> flush
    for f in futs:
        ids, _ = f.result(timeout=60)
        assert ids.shape == (2, 5)


# ---------------------------------------------------------------------------
# Persistence
# ---------------------------------------------------------------------------

def test_service_save_load_roundtrip(tmp_path, service):
    svc, xa, xb = service
    svc.save(str(tmp_path))
    svc2 = MemoryService.load(str(tmp_path))
    try:
        assert set(svc2.list_collections()) >= {"alpha", "beta"}
        for name, x in (("alpha", xa), ("beta", xb)):
            ids1, _ = svc.query(name, x[:8], k=5)
            ids2, _ = svc2.query(name, x[:8], k=5)
            np.testing.assert_array_equal(ids1, ids2)
            # id allocator restored: post-reload inserts don't collide
            assert (svc2.collection(name)._next_id
                    == svc.collection(name)._next_id)
        spilled = svc2.insert("alpha", xa[:5])
        assert spilled == 0
    finally:
        svc2.shutdown()


def test_load_registry_with_retired_interpret_field(tmp_path):
    """A registry saved while `EngineConfig` still had `interpret` loads,
    and the field carries nothing into the restored config."""
    import json
    svc = MemoryService(maintenance=False)
    svc.create_collection("old", CFG)
    x = _corpus(400, seed=7)
    svc.build("old", x)
    svc.save(str(tmp_path))
    svc.shutdown()
    path = tmp_path / "service.json"
    registry = json.loads(path.read_text())
    registry["collections"]["old"]["cfg"]["interpret"] = True
    path.write_text(json.dumps(registry))
    back = MemoryService.load(str(tmp_path), maintenance=False)
    try:
        assert back.collection("old").cfg == CFG
        assert back.collection("old").stats()["live"] == 400
    finally:
        back.shutdown()


def test_atomic_metadata_write(tmp_path):
    """collection.json lands via os.replace: no partial file ever visible."""
    coll = Collection("solo", CFG)
    coll.build(_corpus(400, seed=3))
    d = str(tmp_path / "ns")
    coll.save_into(d)
    files = set(__import__("os").listdir(d))
    assert "collection.json" in files
    assert not any(f.startswith("collection.json.tmp") for f in files)
    back = Collection.load_from(d, "solo", CFG)
    assert back._next_id == coll._next_id
    assert back.counters["rebuilds"] == coll.counters["rebuilds"]


# ---------------------------------------------------------------------------
# Thread safety
# ---------------------------------------------------------------------------

def test_counters_consistent_under_concurrency():
    """Op counters are mutated under the collection lock: concurrent
    scheduler workers must never lose an increment (seed engine bug)."""
    svc = MemoryService()
    svc.create_collection("c", CFG)
    x = _corpus(1000, seed=4)
    svc.build("c", x)
    futs = []
    for i in range(20):
        futs.append(svc.submit(MemoryOp("insert", "c", _corpus(32, seed=i),
                                        concurrent=True)))
        futs.append(svc.submit(MemoryOp("query", "c", x[:4], k=5)))
    for f in futs:
        f.result(timeout=120)
    c = svc.collection("c").counters
    assert c["inserts"] == 20 * 32
    assert c["queries"] == 20 * 4
    svc.shutdown()


def test_stats_count_probed_rows_by_list_scan():
    """`svc.stats()` counts probed query rows by how their lists were
    scanned: in place on an f32 store with the kernels (`probed_inplace`),
    gathered on an int8 store or the jnp reference (`probed_gather`).  Full
    scans count under neither; fused lanes count like per-op queries."""
    small = dict(dim=128, n_clusters=128, list_capacity=16, nprobe=4, k=5,
                 kmeans_iters=2)
    cfgs = {"f32": EngineConfig(**small),
            "int8": EngineConfig(**small, store_dtype="int8"),
            "ref": EngineConfig(**small, use_kernel=False)}
    x = _corpus(600, seed=5)
    with MemoryService(maintenance=False) as svc:
        for name, cfg in cfgs.items():
            svc.create_collection(name, cfg)
            svc.build(name, x)
            svc.query(name, x[:3], path="probed")
            svc.query(name, x[:2], path="full_scan")
        svc.query_many([("f32", x[:4])], path="probed")
        got = {name: {key: s[key] for key in
                      ("queries", "probed_inplace", "probed_gather")}
               for name, s in svc.stats()["collections"].items()}
    assert got == {"f32": {"queries": 9, "probed_inplace": 7,
                           "probed_gather": 0},
                   "int8": {"queries": 5, "probed_inplace": 0,
                            "probed_gather": 3},
                   "ref": {"queries": 5, "probed_inplace": 0,
                           "probed_gather": 3}}
