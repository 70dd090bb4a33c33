"""Admission-control tests: overload must degrade to a typed `Overloaded`
with bounded submit latency — never an unbounded queue or a hang — with
background maintenance shed strictly before latency-class queries, and
full recovery once the queues drain."""
import threading
import time

import numpy as np
import pytest

from repro.api import AdmissionControl, MemoryService, Overloaded
from repro.api.ops import MemoryOp
from repro.api.service import MaintenanceController
from repro.configs.base import EngineConfig
from repro.core.scheduler import Task, WindowedScheduler


def _wedge(sched, backend):
    """Block `backend`'s worker on a gate; returns the gate after the
    wedge task is actually running (so queue depths start at zero)."""
    gate = threading.Event()
    started = threading.Event()

    def fn():
        started.set()
        gate.wait()

    sched.submit(Task(fn=fn, kind="rebuild", backend=backend))
    assert started.wait(timeout=10), "wedge task never started"
    return gate


@pytest.mark.tier1
def test_overload_raises_typed_overloaded_not_hang():
    adm = AdmissionControl(max_queue_depth=2)
    sched = WindowedScheduler(backends={"latency": 1}, admission=adm)
    gate = _wedge(sched, "latency")
    try:
        for _ in range(adm.max_queue_depth):
            sched.submit(Task(fn=lambda: None, kind="query",
                              backend="latency"))
        t0 = time.perf_counter()
        with pytest.raises(Overloaded) as exc:
            sched.submit(Task(fn=lambda: None, kind="query",
                              backend="latency"))
        # bounded-latency rejection: the typed error is raised pre-queue,
        # not after a window/queue wait
        assert time.perf_counter() - t0 < 1.0
        assert exc.value.backend == "latency"
        assert exc.value.depth == 2 and exc.value.limit == 2
        assert exc.value.reason == "queue-depth"
        adm_stats = sched.stats()["admission"]
        assert adm_stats["enabled"]
        assert adm_stats["shed"]["latency"] == 1
        assert adm_stats["depth_peak"]["latency"] == 2
        assert adm_stats["limits"]["latency"] == 2
    finally:
        gate.set()
    # recovery: once the queue drains, the same submit is admitted (the
    # drain is asynchronous — poll the depth down before resubmitting)
    deadline = time.perf_counter() + 10
    while (sched.stats()["admission"]["queue_depth"].get("latency", 0) > 0
           and time.perf_counter() < deadline):
        time.sleep(0.01)
    task = sched.submit(Task(fn=lambda: 7, kind="query", backend="latency"))
    assert task.done.wait(timeout=10) and task.result == 7
    assert sched.stats()["admission"]["queue_depth"]["latency"] == 0
    sched.shutdown()


@pytest.mark.tier1
def test_background_shed_before_latency():
    # background gets only background_frac of the depth budget: under the
    # same overload, maintenance is rejected while queries still queue
    adm = AdmissionControl(max_queue_depth=4, background_frac=0.5)
    sched = WindowedScheduler(window=16, backends={"background": 1},
                              admission=adm)
    gate = _wedge(sched, "background")
    try:
        for _ in range(2):                 # frac * 4 = 2 admitted
            sched.submit(Task(fn=lambda: None, kind="rebuild",
                              backend="background"))
        with pytest.raises(Overloaded) as exc:
            sched.submit(Task(fn=lambda: None, kind="rebuild",
                              backend="background"))
        assert exc.value.limit == 2
        for _ in range(4):                 # full budget for latency
            sched.submit(Task(fn=lambda: None, kind="query",
                              backend="latency"))
        with pytest.raises(Overloaded):
            sched.submit(Task(fn=lambda: None, kind="query",
                              backend="latency"))
        shed = sched.stats()["admission"]["shed"]
        assert shed == {"background": 1, "latency": 1}
    finally:
        gate.set()
    sched.shutdown()


@pytest.mark.tier1
def test_estimated_queue_wait_rejection():
    adm = AdmissionControl(max_queue_depth=100, max_queue_wait_s=0.05)
    sched = WindowedScheduler(backends={"latency": 1}, admission=adm)
    # teach the estimator this backend's mean task time (~0.2s)
    seed = sched.submit(Task(fn=lambda: time.sleep(0.2), kind="query",
                             backend="latency"))
    assert seed.done.wait(timeout=10)
    gate = _wedge(sched, "latency")
    try:
        # depth 0: estimated wait 0 — admitted even with a slow backend
        sched.submit(Task(fn=lambda: None, kind="query", backend="latency"))
        # depth 1: est ~= 1 x 0.2s / 1 worker >> 0.05s — typed rejection
        with pytest.raises(Overloaded) as exc:
            sched.submit(Task(fn=lambda: None, kind="query",
                              backend="latency"))
        assert exc.value.reason.startswith("est queue-wait")
    finally:
        gate.set()
    sched.shutdown()


@pytest.mark.tier1
def test_full_submission_window_rejects_not_hangs():
    adm = AdmissionControl(max_queue_depth=100, max_queue_wait_s=0.2)
    sched = WindowedScheduler(window=2, backends={"latency": 1},
                              admission=adm)
    gate = _wedge(sched, "latency")        # 1 of 2 window slots in flight
    try:
        sched.submit(Task(fn=lambda: None, kind="query", backend="latency"))
        t0 = time.perf_counter()
        with pytest.raises(Overloaded) as exc:   # window full: bounded wait
            sched.submit(Task(fn=lambda: None, kind="query",
                              backend="latency"))
        assert 0.2 <= time.perf_counter() - t0 < 5.0
        assert exc.value.reason == "submission window full"
    finally:
        gate.set()
    sched.shutdown()


@pytest.mark.tier1
def test_service_exposes_admission_watermarks():
    adm = AdmissionControl(max_queue_depth=8)
    with MemoryService(maintenance=False, admission=adm) as svc:
        cfg = EngineConfig(dim=128, n_clusters=128, list_capacity=64,
                           nprobe=64, k=10, use_kernel=False, kmeans_iters=3)
        svc.create_collection("mem", cfg)
        rng = np.random.default_rng(0)
        svc.build("mem", rng.standard_normal((256, 128)).astype(np.float32))
        ids, _ = svc.query("mem", rng.standard_normal(
            (4, 128)).astype(np.float32))
        assert ids.shape == (4, 10)
        stats = svc.stats()["scheduler"]["admission"]
        assert stats["enabled"]
        assert stats["limits"]["latency"] == 8
        assert stats["limits"]["background"] == 4     # frac of the budget
        assert all(d == 0 for d in stats["queue_depth"].values())
        assert stats["depth_peak"].get("latency", 0) <= 8


@pytest.mark.tier1
def test_maintenance_controller_counts_shed_not_failed(monkeypatch):
    svc = MemoryService(maintenance=False)
    ctrl = MaintenanceController(svc, poll_interval_s=0.01)
    try:
        def overloaded_submit(op):
            raise Overloaded("background", 2, 2)

        monkeypatch.setattr(svc, "submit", overloaded_submit)
        key = ("mem", None)
        op = MemoryOp("rebuild", "mem")
        # a shed background op is NOT a failure: it backs off one poll
        # interval and re-offers, without tripping the failure backoff
        assert not ctrl._try_submit(key, op)
        assert ctrl.stats()["shed"] == 1
        assert ctrl.stats()["failed"] == 0
        assert not ctrl._try_submit(key, op)      # still inside the backoff
        assert ctrl.stats()["shed"] == 1

        class _Fut:
            req = 1                               # an OpFuture's request id

            def done(self):
                return False

        monkeypatch.setattr(svc, "submit", lambda op: _Fut())
        time.sleep(0.05)                          # one poll interval later
        assert ctrl._try_submit(key, op)          # re-offered and accepted
        assert ctrl.stats()["failed"] == 0
    finally:
        ctrl.stop()
        svc.shutdown()
