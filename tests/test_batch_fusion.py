"""Cross-collection fused batched queries over mesh-sharded collections.

Executable spec for the sharded arm of the batching/fusion layer
(docs/ARCHITECTURE.md "Batched execution & cross-collection fusion"):

* a same-signature batched window over G sharded tenants executes as ONE
  fused `shard_map` dispatch (`flush()` reports 1) and returns results
  bitwise-equal to the per-op `dist_query` path;
* a mixed sharded + unsharded window splits into the correct groups (mesh
  is part of the batch signature);
* the degenerate G=1 sharded lane (several ops, one collection) still
  fuses into a single dispatch;
* demux stays correct while a lane's collection is concurrently rebuilding
  (snapshot reads — fusion never touches writer locks or delta logs).

Runs on the 2 fake CPU devices tests/conftest.py forces.
"""
import dataclasses
import threading

import numpy as np
import pytest

import jax

if jax.device_count() < 2:
    pytest.skip("needs >= 2 devices (tests/conftest.py forces 2 fake CPU "
                "devices unless XLA_FLAGS was pre-set)",
                allow_module_level=True)

from repro.api import MemoryOp, MemoryService
from repro.configs.base import EngineConfig
from repro.core import distributed as dce
from repro.core import templates

N_SHARDS = 2
SCFG = EngineConfig(dim=128, n_clusters=128, list_capacity=16, nprobe=8,
                    k=4, use_kernel=False, kmeans_iters=2, shard_db=True)
UCFG = EngineConfig(dim=128, n_clusters=128, list_capacity=16, nprobe=8,
                    k=4, use_kernel=False, kmeans_iters=2)
N0 = 256


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((N_SHARDS,), ("shard",))


def _corpus(n, seed=0, dim=128):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, dim), dtype=np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


@pytest.fixture()
def svc(mesh):
    svc = MemoryService(maintenance=False)
    for i, name in enumerate(("s0", "s1", "s2")):
        svc.create_collection(name, SCFG, mesh=mesh)
        svc.build(name, _corpus(N0, seed=i), ids=np.arange(i * 10_000,
                                                           i * 10_000 + N0))
    yield svc
    svc.shutdown()


# ---------------------------------------------------------------------------
# Fused-sharded == per-op dist_query (the acceptance invariant)
# ---------------------------------------------------------------------------

def test_sharded_window_is_one_dispatch_bitwise_equal(svc, mesh):
    """3 sharded tenants, one batched window -> 1 fused shard_map dispatch,
    bitwise-equal to the per-op `dist_query` path."""
    qs = {n: _corpus(3 + i, seed=20 + i)        # unequal batches -> padding
          for i, n in enumerate(("s0", "s1", "s2"))}
    # per-op reference: Collection.query on a sharded collection IS
    # dist_query (assert that explicitly for s0)
    coll = svc.collection("s0")
    ref_ids, ref_scores = dce.dist_query(coll.snapshot(), qs["s0"], SCFG,
                                         mesh, 4)
    sync = {n: svc.query(n, q, k=4) for n, q in qs.items()}
    np.testing.assert_array_equal(sync["s0"][0], np.asarray(ref_ids))
    np.testing.assert_array_equal(sync["s0"][1], np.asarray(ref_scores))

    futs = {n: svc.submit(MemoryOp("query", n, q, k=4, batch=True))
            for n, q in qs.items()}
    assert svc.flush() == 1                     # ONE dispatch for 3 tenants
    for n in qs:
        ids, scores = futs[n].result(timeout=60)
        np.testing.assert_array_equal(ids, sync[n][0])       # bitwise
        np.testing.assert_array_equal(scores, sync[n][1])    # bitwise
    # tenant isolation survives fusion: lane g only scanned collection g
    assert (futs["s1"].result()[0] // 10_000 == 1).all()
    assert (futs["s2"].result()[0] // 10_000 == 2).all()


def test_query_many_sharded(svc):
    """The one-call entry point covers sharded tenants too."""
    qs = [("s0", _corpus(4, seed=30)), ("s2", _corpus(6, seed=31))]
    out = svc.query_many(qs, k=4)
    for (name, q), (ids, scores) in zip(qs, out):
        want_ids, want_scores = svc.query(name, q, k=4)
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(scores, want_scores)


def test_degenerate_single_lane_still_fuses(svc):
    """Several batched ops against ONE sharded collection: G=1 lane, still
    a single fused dispatch, per-op row spans demuxed correctly."""
    q1, q2 = _corpus(3, seed=40), _corpus(5, seed=41)
    f1 = svc.submit(MemoryOp("query", "s1", q1, k=4, batch=True))
    f2 = svc.submit(MemoryOp("query", "s1", q2, k=4, batch=True))
    assert svc.flush() == 1
    np.testing.assert_array_equal(f1.result(timeout=60)[0],
                                  svc.query("s1", q1, k=4)[0])
    np.testing.assert_array_equal(f2.result(timeout=60)[0],
                                  svc.query("s1", q2, k=4)[0])


# ---------------------------------------------------------------------------
# Window splitting
# ---------------------------------------------------------------------------

def test_mixed_window_splits_sharded_and_unsharded(svc, mesh):
    """Sharded and unsharded tenants in one window -> two fused groups (the
    mesh is part of the signature), each correct."""
    for name, seed in (("u0", 7), ("u1", 8)):
        svc.create_collection(name, UCFG)
        svc.build(name, _corpus(N0, seed=seed))
    qs = {n: _corpus(4, seed=50 + i)
          for i, n in enumerate(("s0", "s1", "u0", "u1"))}
    sync = {n: svc.query(n, q, k=4) for n, q in qs.items()}
    futs = {n: svc.submit(MemoryOp("query", n, q, k=4, batch=True))
            for n, q in qs.items()}
    # 2 sharded lanes fuse into one dispatch, 2 unsharded into another
    assert svc.flush() == 2
    for n in qs:
        ids, scores = futs[n].result(timeout=60)
        np.testing.assert_array_equal(ids, sync[n][0])
        np.testing.assert_array_equal(scores, sync[n][1])
    for name in ("u0", "u1"):
        svc.drop_collection(name)


def test_singleton_sharded_group_takes_per_op_path(svc):
    """A lone sharded batched op has nothing to fuse with: per-op dispatch,
    same count (1), same results."""
    q = _corpus(4, seed=60)
    fut = svc.submit(MemoryOp("query", "s2", q, k=4, batch=True))
    assert svc.flush() == 1
    np.testing.assert_array_equal(fut.result(timeout=60)[0],
                                  svc.query("s2", q, k=4)[0])


def test_fused_route_is_throughput_class():
    """A fused dispatch never steals a latency worker, however small the
    per-lane batches are."""
    th = templates.TemplateThresholds(full_scan_batch=32)
    plan = templates.route("query", 4, UCFG, th)
    assert plan.backend == "latency"            # tiny single-op batch
    plan = templates.route("query", 4, UCFG, th, fused_lanes=3)
    assert plan.backend == "throughput"         # same rows, fused -> bulk
    assert plan.path == "probed"                # path still signature-driven


# ---------------------------------------------------------------------------
# Unsharded probed lanes on the in-place list scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim", [128, 100], ids=["d128", "d100"])
def test_fused_probed_lanes_equal_per_op_in_place_scan(monkeypatch, dim):
    """The vmapped `fused_query` over stacked states, each lane with its
    own batch and probes, answers as per-op `query_probed` does when both
    scan the probed lists in place (kernels on, f32 store).  The unaligned
    width is read through the width-major view a TPU lays it out in."""
    import jax.numpy as jnp

    from repro.api import batch
    from repro.core import index as ivf
    from repro.kernels import ops

    if dim % 128:
        monkeypatch.setattr(ops, "layout_order",
                            lambda shape, dtype=None: (0, 3, 1, 2)
                            if len(shape) == 4 else
                            (len(shape) - 1,) + tuple(range(len(shape) - 1)))
    cfg = EngineConfig(dim=dim, n_clusters=16, list_capacity=16, nprobe=4,
                       k=4, metric="l2" if dim % 128 else "ip",
                       aligned=False, kmeans_iters=2)
    build_cfg = dataclasses.replace(cfg, use_kernel=False)
    assert ivf.scans_in_place(cfg)
    states, queries = [], []
    for g, b in enumerate((1, 3, 2)):
        x = _corpus(200, seed=90 + g, dim=dim)
        state, _ = ivf.build(jax.random.PRNGKey(g), jnp.asarray(x),
                             jnp.arange(g * 1000, g * 1000 + 200,
                                        dtype=jnp.int32), build_cfg,
                             spill_capacity=32)
        states.append(state)
        queries.append(jnp.asarray(x[10 * g: 10 * g + b]))
    padded = jnp.stack([jnp.pad(q, ((0, 3 - q.shape[0]), (0, 0)))
                        for q in queries])
    ids, scores = batch.fused_query(batch.stack_states(states), padded, cfg,
                                    4, 4, "probed")
    for g, (state, q) in enumerate(zip(states, queries)):
        want_ids, want_scores = ivf.query_probed(state, q, cfg, 4, 4)
        np.testing.assert_array_equal(np.asarray(ids[g, :q.shape[0]]),
                                      np.asarray(want_ids))
        np.testing.assert_allclose(np.asarray(scores[g, :q.shape[0]]),
                                   np.asarray(want_scores), rtol=1e-6,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# Stack cache: reuse across dispatches, invalidation on any lane write
# ---------------------------------------------------------------------------

def test_stack_cache_reuses_and_invalidates(svc):
    qs = {n: _corpus(4, seed=80 + i)
          for i, n in enumerate(("s0", "s1", "s2"))}

    def window():
        futs = {n: svc.submit(MemoryOp("query", n, q, k=4, batch=True))
                for n, q in qs.items()}
        assert svc.flush() == 1
        return {n: f.result(timeout=60) for n, f in futs.items()}

    first = window()
    base = svc.stats()["stack_cache"]
    second = window()                           # same versions -> cache hit
    after = svc.stats()["stack_cache"]
    assert after["hits"] == base["hits"] + 1
    assert after["misses"] == base["misses"]
    for n in qs:
        np.testing.assert_array_equal(second[n][0], first[n][0])
        np.testing.assert_array_equal(second[n][1], first[n][1])

    # a write to ANY lane bumps its version: next window must restack and
    # see the new rows (cached stale state would miss id 77777)
    probe = _corpus(N_SHARDS, seed=99)
    svc.insert("s1", probe, ids=np.asarray([77_777, 77_778]))
    third = window()
    assert svc.stats()["stack_cache"]["misses"] == after["misses"] + 1
    ids, _ = svc.query("s1", probe[:1], k=4)
    assert 77_777 in ids[0] or 77_778 in ids[0]     # sanity: row landed
    fused_ids, _ = third["s1"]
    np.testing.assert_array_equal(fused_ids, svc.query("s1", qs["s1"], k=4)[0])

    # dropping a tenant releases every cached stack that includes it —
    # a cached group holds a full copy of the tenant's state
    assert svc.stats()["stack_cache"]["entries"] >= 1
    svc.drop_collection("s1")
    assert svc.stats()["stack_cache"]["entries"] == 0


# ---------------------------------------------------------------------------
# Fusion vs concurrent maintenance
# ---------------------------------------------------------------------------

def test_demux_correct_under_concurrent_rebuild(svc):
    """Fused dispatches read snapshots; a lane whose collection is mid-
    delta-replay-rebuild must neither block nor corrupt the demux."""
    svc.delete("s0", np.arange(32))             # give the rebuild real work
    stop = threading.Event()
    errors = []

    def churn():
        try:
            while not stop.is_set():
                for s in range(N_SHARDS):
                    out = svc.collection("s0").rebuild(shard=s)
                    assert not out["aborted"]
        except BaseException as e:   # noqa: BLE001
            errors.append(e)

    t = threading.Thread(target=churn)
    t.start()
    try:
        qs = {n: _corpus(4, seed=70 + i)
              for i, n in enumerate(("s0", "s1", "s2"))}
        want_s1 = svc.query("s1", qs["s1"], k=4)
        want_s2 = svc.query("s2", qs["s2"], k=4)
        for _ in range(10):
            futs = {n: svc.submit(MemoryOp("query", n, q, k=4, batch=True))
                    for n, q in qs.items()}
            assert svc.flush() == 1
            for n, fut in futs.items():
                ids, scores = fut.result(timeout=60)
                assert ids.shape == (4, 4) and scores.shape == (4, 4)
                # live rows only — deleted ids 0..31 never resurface
                if n == "s0":
                    assert not np.isin(ids, np.arange(32)).any()
            # untouched siblings stay bitwise-stable under s0's rebuilds
            np.testing.assert_array_equal(futs["s1"].result()[0], want_s1[0])
            np.testing.assert_array_equal(futs["s2"].result()[0], want_s2[0])
    finally:
        stop.set()
        t.join(timeout=60)
    assert not errors, errors
