"""The service's spans and maintenance counters, read back from a profiler
trace of a real run on the CPU.

One traced session drives insert, query, delete and two rebuilds through
`MemoryService`: the first with a write landing during its recompute (the
write is replayed), the second with a delta log of one op and two writes
landing, so it restarts once before it replays.  Writes land from inside
`ivf.rebuild`, which the test wraps, so their timing is deterministic.
"""
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import MemoryService
from repro.api import collection as collection_mod
from repro.configs.base import EngineConfig

CFG = EngineConfig(dim=128, n_clusters=128, list_capacity=16, nprobe=8, k=4,
                   use_kernel=False, kmeans_iters=2)


def _rows(n, seed):
    x = np.random.default_rng(seed).standard_normal((n, CFG.dim)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _events(trace_dir):
    """(thread, name, start_ns, dur_ns, args) of every event on the host."""
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    with warnings.catch_warnings():
        # the reader's stats type warns as it is built, for every event
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host"):
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    out.append((f"{line.name}#{i}", e.name, e.start_ns,
                                e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    svc = MemoryService(maintenance=False)
    coll = svc.create_collection("c", CFG, spill_capacity=256)
    svc.build("c", _rows(512, 0))
    landing = []            # writes to land during each coming recompute
    real_rebuild = collection_mod.ivf.rebuild

    def rebuild_with_writes(key, snap, cfg):
        out = real_rebuild(key, snap, cfg)
        for seed in range(landing.pop(0) if landing else 0):
            # the throughput worker takes the insert while the background
            # worker sits here, inside the rebuild's recompute
            svc.insert("c", _rows(4, 100 + seed))
        return out

    trace_dir = tmp_path_factory.mktemp("trace")
    results = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(collection_mod.ivf, "rebuild", rebuild_with_writes)
        jax.profiler.start_trace(str(trace_dir))
        try:
            svc.insert("c", _rows(8, 1))
            svc.query("c", _rows(1, 2))
            svc.delete("c", np.arange(8, dtype=np.int32))
            landing.append(1)
            results["replayed"] = svc.rebuild("c")
            coll.delta_log_capacity = 1
            landing.extend([2, 1])
            results["restarted"] = svc.rebuild("c")
        finally:
            jax.profiler.stop_trace()
    results["stats"] = svc.stats()["collections"]["c"]
    svc.shutdown()
    return results, _events(trace_dir)


def _spans(events, name):
    return [e for e in events if e[1] == name]


def test_every_task_has_a_submit_with_its_request_id(traced):
    _, events = traced
    tasks = _spans(events, "ame.task")
    submits = {e[4]["req"]: e for e in _spans(events, "ame.submit")}
    assert len(tasks) == 9          # insert, query, delete, 2 rebuilds, 4 landing writes
    for t in tasks:
        assert t[4]["req"] in submits
        assert submits[t[4]["req"]][4]["kind"] == t[4]["kind"]
        assert t[4]["queued_us"] >= 0
    assert {t[4]["kind"] for t in tasks} == {"insert", "query", "delete", "rebuild"}


def test_spans_inside_a_task_carry_its_request_id(traced):
    _, events = traced
    kind = {e[4]["req"]: e[4]["kind"] for e in _spans(events, "ame.task")}
    devices = _spans(events, "ame.device")
    assert {kind[e[4]["req"]] for e in devices} >= {"insert", "query", "delete"}
    assert {e[4]["module"] for e in devices} >= {
        "jit__insert", "jit__delete", "jit_query_probed"}
    for name in ("ame.writer_wait", "ame.publish"):
        assert all(e[4]["req"] in kind for e in _spans(events, name))


def test_rebuild_phases_in_order_and_restart_counted(traced):
    results, events = traced
    rebuild_reqs = [e[4]["req"] for e in sorted(_spans(events, "ame.task"),
                                                key=lambda e: e[2])
                    if e[4]["kind"] == "rebuild"]
    assert len(rebuild_reqs) == 2

    def phases(req):
        return [(e[1].rsplit(".", 1)[1], e[4]["attempt"])
                for e in sorted(events, key=lambda e: e[2])
                if e[1].startswith("ame.rebuild.") and e[4].get("req") == req]

    assert phases(rebuild_reqs[0]) == [
        ("snapshot", 0), ("recompute", 0), ("lock_wait", 0), ("replay", 0),
        ("publish", 0)]
    assert phases(rebuild_reqs[1]) == [
        ("snapshot", 0), ("recompute", 0), ("lock_wait", 0),
        ("snapshot", 1), ("recompute", 1), ("lock_wait", 1), ("replay", 1),
        ("publish", 1)]
    assert results["replayed"]["restarts"] == 0
    assert results["restarted"]["restarts"] == 1

    stats = results["stats"]
    replays = _spans(events, "ame.rebuild.replay")
    restarts = sum(e[4]["attempt"] > 0 for e in _spans(events, "ame.rebuild.snapshot"))
    assert stats["rebuild_restarts"] == restarts == 1
    assert stats["replayed_ops"] == sum(e[4]["ops"] for e in replays) == 2
    assert stats["replayed_rows"] == sum(e[4]["rows"] for e in replays) == 8
    assert stats["rebuild_exclusive"] == stats["rebuild_aborts"] == 0


def test_copying_and_replayed_writes_run_different_modules(traced):
    _, events = traced
    modules = {e[4].get("hlo_module") for e in events}
    assert {"jit__insert", "jit_replay_insert", "jit_rebuild"} <= modules


def test_exclusive_attempt_and_abort_are_counted():
    """An attempt past `max_restarts` holds the writer lock through the
    recompute; a bulk build during a rebuild aborts it."""
    svc = MemoryService(maintenance=False)
    try:
        coll = svc.create_collection("c", CFG, spill_capacity=256)
        svc.build("c", _rows(256, 0))
        assert coll.rebuild(max_restarts=0)["restarts"] == 0
        assert coll.counters["rebuild_exclusive"] == 1

        real_rebuild = collection_mod.ivf.rebuild

        def rebuild_then_build(key, snap, cfg):
            out = real_rebuild(key, snap, cfg)
            coll.build(_rows(256, 1))        # the writer lock is free here
            return out

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(collection_mod.ivf, "rebuild", rebuild_then_build)
            assert coll.rebuild()["aborted"]
        assert coll.counters["rebuild_aborts"] == 1
        assert coll.counters["rebuild_restarts"] == 0
    finally:
        svc.shutdown()
