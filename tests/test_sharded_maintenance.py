"""Shard-local maintenance on mesh-sharded collections.

The full write/maintenance lifecycle — delete, delta-replay rebuild,
automatic maintenance, persistence — on a 2-shard mesh (tests/conftest.py
forces 2 fake CPU devices).  The invariants mirror tests/test_concurrency.py
plus the shard-locality ones:

* tombstoning and rebuilds are shard-local: a rebuild of shard i reclaims
  shard i's tombstones and leaves sibling shards' arrays AND versions
  bitwise untouched;
* concurrent insert/delete/shard-rebuild loses zero rows (per-shard delta
  logs replay onto the rebuilt shard only);
* maintenance pressure is accounted per shard and the service's
  MaintenanceController auto-schedules shard-local rebuilds from it;
* sharded save/load round-trips through per-shard namespaces, checks the
  mesh shape, and can host-reshard onto a different mesh.
"""
import tempfile
import threading
import time

import numpy as np
import pytest

import jax

if jax.device_count() < 2:
    pytest.skip("needs >= 2 devices (tests/conftest.py forces 2 fake CPU "
                "devices unless XLA_FLAGS was pre-set)",
                allow_module_level=True)

from conftest import live_ids as _live_ids

from repro.api import Collection, MemoryService
from repro.configs.base import EngineConfig
from repro.core import distributed as dce
from repro.core import templates

N_SHARDS = 2
CFG = EngineConfig(dim=128, n_clusters=128, list_capacity=16, nprobe=8,
                   k=4, use_kernel=False, kmeans_iters=2, shard_db=True)
N0 = 512
INS_BATCH = 16           # divisible by N_SHARDS
DEL_BATCH = 8


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((N_SHARDS,), ("shard",))


def _corpus(n, seed=0, dim=128):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, dim), dtype=np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _built(mesh, seed=0, spill_capacity=1024, thresholds=None):
    coll = Collection("c", CFG, mesh=mesh, spill_capacity=spill_capacity,
                      thresholds=thresholds)
    coll.build(_corpus(N0, seed=seed))            # ids 0 .. N0-1
    return coll


# ---------------------------------------------------------------------------
# Delete + rebuild lifecycle
# ---------------------------------------------------------------------------

def test_sharded_delete_then_rebuild_reclaims(mesh):
    coll = _built(mesh)
    n = coll.delete(np.arange(64))
    assert n == 64                                # every id existed once
    assert _live_ids(coll.snapshot()) == set(range(64, N0))
    press = coll.maintenance_pressure()
    assert press["tombstones"] == 64
    assert sum(p["tombstones"] for p in press["shards"]) == 64
    out = coll.rebuild()                          # sweeps both shards
    assert not out["aborted"] and out["shards"] == [0, 1]
    st = coll.stats()
    assert st["deleted"] == 0                     # tombstones reclaimed
    assert st["pressure"]["tombstones"] == 0
    assert _live_ids(coll.snapshot()) == set(range(64, N0))
    # deleting a missing id reports 0 hits
    assert coll.delete(np.asarray([999_999])) == 0


def test_shard_local_rebuild_leaves_siblings_untouched(mesh):
    coll = _built(mesh, seed=1)
    coll.delete(np.arange(96))
    pre = dce.split_host(coll.snapshot(), N_SHARDS)
    pre_press = coll.maintenance_pressure()["shards"]
    v0 = coll.shard_versions()
    # pick the shard that actually holds tombstones; rebuild only it
    deleted = [int(np.asarray(s.num_deleted)) for s in pre]
    target = int(np.argmax(deleted))
    sibling = 1 - target
    out = coll.rebuild(shard=target)
    assert not out["aborted"] and out["shard"] == target
    v1 = coll.shard_versions()
    assert v1[target] == v0[target] + 1           # rebuilt shard bumped
    assert v1[sibling] == v0[sibling]             # sibling version untouched
    post = dce.split_host(coll.snapshot(), N_SHARDS)
    # sibling arrays bitwise identical
    for a, b in zip(pre[sibling], post[sibling]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # rebuilt shard reclaimed its tombstones; sibling kept its own
    assert int(np.asarray(post[target].num_deleted)) == 0
    assert int(np.asarray(post[sibling].num_deleted)) == deleted[sibling]
    after_press = coll.maintenance_pressure()["shards"]
    assert after_press[target]["tombstones"] == 0
    assert after_press[sibling]["tombstones"] == pre_press[sibling]["tombstones"]
    assert _live_ids(coll.snapshot()) == set(range(96, N0))


def test_sharded_state_stays_spread_over_the_mesh(mesh):
    """Every path that publishes a sharded state (create, build, a rebuild
    whose residual spill is rebalanced on the host, promotion from host RAM)
    leaves each leaf on every mesh device, never all of it on the first."""
    def assert_spread(coll):
        for leaf in jax.tree.leaves(coll.snapshot()):
            assert leaf.sharding.device_set == set(mesh.devices.flat)

    rng = np.random.default_rng(3)
    # near-identical rows all land in one list per shard and overflow it
    x = _corpus(1, seed=3) + 1e-3 * rng.standard_normal((N0, 128),
                                                         dtype=np.float32)
    coll = Collection("c", CFG, mesh=mesh, spill_capacity=1024)
    assert_spread(coll)
    coll.build(x)
    assert_spread(coll)
    out = coll.rebuild(shard=0)
    assert out["rebalanced"] > 0              # spill moved via the host
    assert_spread(coll)
    coll.demote("warm")
    coll.promote()
    assert_spread(coll)
    assert _live_ids(coll.snapshot()) == set(range(N0))


def test_sharded_concurrent_writes_rebuild_zero_lost_rows(mesh):
    coll = _built(mesh, seed=2)
    n_ins_batches, n_del_batches = 10, 6
    inserted, deleted, errors = set(), set(), []

    def inserter():
        try:
            for i in range(n_ins_batches):
                ids = np.arange(10_000 + i * INS_BATCH,
                                10_000 + (i + 1) * INS_BATCH)
                coll.insert(_corpus(INS_BATCH, seed=100 + i), ids=ids)
                inserted.update(ids.tolist())
        except BaseException as e:   # noqa: BLE001
            errors.append(e)

    def deleter():
        try:
            for i in range(n_del_batches):
                ids = np.arange(i * DEL_BATCH, (i + 1) * DEL_BATCH)
                assert coll.delete(ids) == DEL_BATCH
                deleted.update(ids.tolist())
        except BaseException as e:   # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=inserter),
               threading.Thread(target=deleter)]
    for t in threads:
        t.start()
    # alternate shard-local rebuilds while the writers churn: the per-shard
    # delta log must replay every concurrent write onto the rebuilt shard
    rebuilds = 0
    while any(t.is_alive() for t in threads):
        out = coll.rebuild(shard=rebuilds % N_SHARDS)
        assert not out["aborted"]
        rebuilds += 1
    for t in threads:
        t.join()
    assert not errors, errors
    assert rebuilds >= 1

    want = (set(range(N0)) - deleted) | inserted
    assert _live_ids(coll.snapshot()) == want     # zero lost rows
    assert coll.counters["inserts"] == n_ins_batches * INS_BATCH
    assert coll.counters["deletes"] == n_del_batches * DEL_BATCH
    # a quiet full sweep reclaims all remaining tombstones
    coll.rebuild()
    assert coll.stats()["deleted"] == 0
    assert _live_ids(coll.snapshot()) == want


def test_sharded_insert_batch_must_divide(mesh):
    coll = _built(mesh, seed=3)
    with pytest.raises(ValueError, match="divide over the 2-shard mesh"):
        coll.insert(_corpus(3, seed=9), ids=np.arange(70_000, 70_003))
    with pytest.raises(ValueError, match="shards 0..1"):
        coll.rebuild(shard=5)


def test_unsharded_rebuild_rejects_shard_arg():
    cfg = EngineConfig(dim=128, n_clusters=128, list_capacity=16, nprobe=8,
                       k=4, use_kernel=False, kmeans_iters=2)
    coll = Collection("solo", cfg)
    coll.build(_corpus(128, seed=4))
    with pytest.raises(ValueError, match="unsharded"):
        coll.rebuild(shard=1)
    coll.rebuild(shard=0)                         # the single shard is fine


# ---------------------------------------------------------------------------
# Per-shard pressure -> shard-local auto-maintenance
# ---------------------------------------------------------------------------

def test_service_auto_schedules_shard_local_rebuild(mesh):
    th = templates.TemplateThresholds(maintenance_tombstone_frac=0.001,
                                      maintenance_min_pending=16,
                                      maintenance_shard_min_pending=16)
    svc = MemoryService(maintenance_poll_interval_s=0.02)
    try:
        svc.create_collection("c", CFG, mesh=mesh, thresholds=th)
        svc.build("c", _corpus(N0, seed=5))
        coll = svc.collection("c")
        # cross the per-shard tombstone threshold (max(16, .1% of 2048)=16)
        # on at least one shard and do NOT call rebuild(): the controller
        # must schedule shard-local rebuilds on its own
        assert svc.delete("c", np.arange(64)) == 64
        due = coll.maintenance_due_shards()
        assert due, coll.maintenance_pressure()
        deadline = time.time() + 60
        while time.time() < deadline:
            st = coll.stats()
            if st["deleted"] == 0 and not coll.maintenance_due_shards():
                break
            time.sleep(0.05)
        st = coll.stats()
        assert st["deleted"] == 0, st             # tombstones reclaimed
        assert st["rebuilds"] >= 2                # build + auto rebuild(s)
        assert svc.stats()["maintenance"]["triggered"] >= 1
        assert st["live"] == N0 - 64
        # the controller rebuilt shard-locally: only due shards' versions
        # moved past the build+delete baseline, but every tombstone is gone
        assert st["pressure"]["tombstones"] == 0
    finally:
        svc.shutdown()


def test_shard_pressure_is_per_shard(mesh):
    coll = _built(mesh, seed=6)
    _, hits = dce.dist_delete(coll.snapshot(), np.arange(48, dtype=np.int32),
                              mesh)
    per_shard_truth = [int(v) for v in np.asarray(hits)]
    coll.delete(np.arange(48))
    shards = coll.maintenance_pressure()["shards"]
    assert [s["tombstones"] for s in shards] == per_shard_truth
    assert sum(per_shard_truth) == 48


# ---------------------------------------------------------------------------
# Sharded persistence
# ---------------------------------------------------------------------------

def test_sharded_save_load_roundtrip(mesh):
    coll = _built(mesh, seed=7)
    coll.insert(_corpus(INS_BATCH, seed=70),
                ids=np.arange(40_000, 40_000 + INS_BATCH))
    coll.delete(np.arange(32))
    q = _corpus(4, seed=71)
    want_ids, want_scores = coll.query(q, k=4)
    want_live = _live_ids(coll.snapshot())
    with tempfile.TemporaryDirectory() as d:
        coll.save_into(d)
        back = Collection.load_from(d, "c", CFG, mesh=mesh)
        assert _live_ids(back.snapshot()) == want_live
        got_ids, got_scores = back.query(q, k=4)
        np.testing.assert_array_equal(got_ids, want_ids)
        np.testing.assert_allclose(got_scores, want_scores, rtol=1e-5)
        # pressure re-seeded from the restored per-shard state
        press = back.maintenance_pressure()
        assert press["tombstones"] == coll.maintenance_pressure()["tombstones"]
        # inserts keep going after a restore (id allocator survived)
        back.insert(_corpus(INS_BATCH, seed=72))
        assert back._next_id > 40_000


def test_sharded_load_mesh_mismatch_and_reshard(mesh):
    coll = _built(mesh, seed=8)
    coll.delete(np.arange(16))
    want_live = _live_ids(coll.snapshot())
    mesh_b = jax.make_mesh((1, N_SHARDS), ("replica", "shard"))
    with tempfile.TemporaryDirectory() as d:
        coll.save_into(d)
        # same device count, different mesh shape: fail fast by default...
        with pytest.raises(ValueError, match="reshard=True"):
            Collection.load_from(d, "c", CFG, mesh=mesh_b)
        # ...and host-reshard on request, preserving every live row
        back = Collection.load_from(d, "c", CFG, mesh=mesh_b, reshard=True)
        assert _live_ids(back.snapshot()) == want_live
        ids, _ = back.query(_corpus(4, seed=80), k=4)
        assert ids.shape == (4, 4)
        # resharded tombstones were dropped with their slots: pressure clean
        assert back.stats()["deleted"] == 0
    # loading a sharded snapshot with an unsharded config is an error that
    # names the fix, not a NotImplementedError
    unsharded = EngineConfig(dim=128, n_clusters=128, list_capacity=16,
                             nprobe=8, k=4, use_kernel=False, kmeans_iters=2)
    with tempfile.TemporaryDirectory() as d:
        coll.save_into(d)
        with pytest.raises(ValueError, match="shard_db"):
            Collection.load_from(d, "c", unsharded)


def test_service_save_load_sharded_collection(mesh):
    svc = MemoryService(maintenance=False)
    try:
        svc.create_collection("planet", CFG, mesh=mesh)
        svc.build("planet", _corpus(N0, seed=9))
        svc.delete("planet", np.arange(8))
        want = _live_ids(svc.collection("planet").snapshot())
        with tempfile.TemporaryDirectory() as d:
            svc.save(d)
            with pytest.raises(ValueError, match="mesh="):
                MemoryService.load(d, maintenance=False)
            back = MemoryService.load(d, maintenance=False, mesh=mesh)
            try:
                assert _live_ids(back.collection("planet").snapshot()) == want
                ids, _ = back.query("planet", _corpus(2, seed=90), k=3)
                assert ids.shape == (2, 3)
            finally:
                back.shutdown()
    finally:
        svc.shutdown()
