"""One run of one cell: set up the deployment, warm every shape the traffic
uses, measure `seconds`, then compare what the timed path returned with the
reference and read the metrics.

Set-up is everything from the process's start to the window's start:
imports and the chip, the corpus made on the device, the index build, one
operation of each shape the traffic sends (and, where the traffic deletes,
one rebuild with writes replayed into it, so maintenance compiles nothing
later), and the lead-in.  A mix with `lead_in.until_publish` runs its
traffic until the maintenance controller publishes a rebuild, and opens the
window `after_publish_s` later, so every run's window holds the same
whole maintenance cycles.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from chipbench import device as dev
from chipbench import reference as ref
from chipbench import traffic as tr
from chipbench.data import Corpus, seed_words
from chipbench.drive import LoadGen, Op, Rebuild
from chipbench.spec import Cell

COLLECTION = "memory"
SETTLE_S = 60.0            # how long maintenance may still run after the window


@dataclass
class Run:
    """What metric readers see of one run."""
    cell: Cell
    seconds: float
    w0: float
    w1: float
    setup_s: float
    ops: List[Op]
    rebuilds: List[Rebuild]
    sched0: dict
    sched1: dict
    peaks: dict
    live_rows: int
    trace: Optional[object] = None            # trace.Summary of a traced run

    def due_in_window(self, kind: str) -> List[Op]:
        return [op for op in self.ops if op.kind == kind and self.w0 <= op.due < self.w1]

    def latencies_ms(self, kind: str) -> np.ndarray:
        """Due-to-done latency of each `kind` operation due in the window;
        one that failed counts as never done."""
        return np.array([(op.done - op.due) * 1e3 if op.error is None else math.inf
                         for op in self.due_in_window(kind)])

    def rebuilds_in_window(self) -> List[Rebuild]:
        return [r for r in self.rebuilds if self.w0 <= r.published < self.w1]


class CompileWatch:
    """Times of backend compiles and persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.compiles: List[float] = []
        self.hits = self.misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles.append(time.perf_counter())

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def between(self, a: float, b: float) -> int:
        with self._lock:
            return sum(a <= t < b for t in self.compiles)


def _engine(config: dict):
    from repro.configs.base import EngineConfig
    from repro.core.templates import TemplateThresholds
    cfg = EngineConfig(**config["engine"])
    thr = dataclasses.replace(TemplateThresholds.from_profile(cfg),
                              **config.get("thresholds", {}))
    return cfg, thr


def _shapes(mix: dict) -> List[tuple]:
    """Each (operation, rows) the mix sends, follow-ups included."""
    out = []
    for s in mix["streams"]:
        for op in (s, s.get("then")):
            if op and (op["op"], int(op.get("rows", 1))) not in out:
                out.append((op["op"], int(op.get("rows", 1))))
    return out


def _warm(svc, drv: LoadGen, mix: dict) -> None:
    from repro.api import MemoryOp
    shapes = _shapes(mix)
    for kind, rows in shapes:
        drv.run_sync(kind, rows)
    writes = [(k, r) for k, r in shapes if k in ("insert", "delete")]
    if not any(k == "delete" for k, _ in writes):
        return
    # a rebuild with writes landing during it compiles the rebuild and the
    # replay of each write shape, as the controller's rebuilds will run them
    for pause in (0.2, 0.05, 0.0):
        fut = svc.submit(MemoryOp("rebuild", COLLECTION))
        time.sleep(pause)
        for kind, rows in writes:
            drv.run_sync(kind, rows)
        if fut.result(timeout=900)["replayed"] > 0:
            return
    raise RuntimeError("no write landed during the warm-up rebuild")


def _lead_in(drv: LoadGen, mix: dict, config: dict, emit) -> float:
    """Start the traffic; returns the window's start time."""
    lead = mix.get("lead_in", {})
    if lead.get("until_publish"):
        # bring tombstones to `trigger_after_s` seconds of deletes short of
        # the rebuild threshold, so the lead-in's rebuild comes soon
        rows = next(int(s["rows"]) for s in mix["streams"] if s["op"] == "delete")
        short = tr.rows_per_second(mix, "delete") * float(lead.get("trigger_after_s", 1.0))
        have = drv.coll.maintenance_pressure()["tombstones"]
        n_pre = max(0, int((tr.tombstone_limit(config) - have - short) // rows))
        pre = [drv.make("delete", rows, time.perf_counter()) for _ in range(n_pre)]
        for op, fut in [(op, drv.submit(op)) for op in pre]:
            drv.settle(op, fut)
        t0 = drv.start()
        if not drv.published.wait(float(lead.get("max_s", 120.0))):
            raise RuntimeError("no rebuild published during the lead-in")
        w0 = drv.log.rebuilds[-1].published + float(lead.get("after_publish_s", 0.0))
        emit({"lead_in": {"prefill_deletes": n_pre, "publish_after_s": w0 - t0}})
        return w0
    return drv.start() + float(lead.get("seconds", 1.0))


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(min(d, 0.5))


def _live_ids(coll) -> np.ndarray:
    s = coll.snapshot()
    ids = np.concatenate([np.asarray(s.list_ids).ravel(), np.asarray(s.spill_ids).ravel()])
    return ids[ids >= 0]


def _settle_maintenance(svc) -> None:
    deadline = time.perf_counter() + SETTLE_S
    while time.perf_counter() < deadline:
        m = svc.maintenance
        if m is None or not m.stats()["inflight"]:
            return
        time.sleep(0.05)
    raise RuntimeError("maintenance still in flight a minute after the window")


def prepare(config: dict, mix: dict, seed: int, n_ins: int, n_q: int, parts: dict):
    """The deployment built and every shape of `mix` warmed: returns the
    service, a load generator on it and the corpus; set-up seconds go to `parts`."""
    from repro.api import MemoryService
    t = time.perf_counter()
    corpus = Corpus(config, seed, n_ins, n_q)
    corpus.build.block_until_ready()
    parts["data_s"] = time.perf_counter() - t
    cfg, thr = _engine(config)
    svc = MemoryService()
    try:
        t = time.perf_counter()
        svc.create_collection(COLLECTION, cfg, seed=seed_words(seed)[1] % 2**31,
                              spill_capacity=int(config["collection"]["spill_capacity"]),
                              thresholds=thr)
        built = svc.build(COLLECTION, corpus.build,
                          ids=np.arange(corpus.n_build, dtype=np.int32))
        corpus.build = None
        parts["build_s"] = time.perf_counter() - t
        parts["build_spilled"] = built["spilled"]
        drv = LoadGen(svc, COLLECTION, corpus, mix, seed)
        t = time.perf_counter()
        _warm(svc, drv, mix)
        parts["warm_s"] = time.perf_counter() - t
    except BaseException:
        svc.shutdown()
        raise
    return svc, drv, corpus


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, t_start: float,
        peaks: Optional[dict] = None, controls: Sequence[str] = (),
        emit=lambda rec: print(json.dumps(rec), flush=True)) -> dict:
    """Run `cell` once on `devices`; returns the result line's object, with
    the numbers compared under its last key, `checks`.  `peaks` defaults to
    the table's entry for the devices' kind.  Each precision in `controls`
    also puts the reference, rounded to it, in the program's place on the
    same queries, and reports under `controls` what the comparison reads of
    it and whether it passes the same limits (the benchmark's own runs make
    none)."""
    from chipbench.trace import Tracer
    config, mix = cell.config, cell.traffic
    watch = CompileWatch()
    parts: Dict[str, float] = {"start_s": time.perf_counter() - t_start}
    svc, drv, corpus = prepare(config, mix, seed, *tr.pool_sizes(mix, seconds), parts)
    try:
        tracer = Tracer() if trace else None
        if tracer:
            tracer.start()
        t = time.perf_counter()
        w0 = _lead_in(drv, mix, config, emit)
        w1 = w0 + seconds
        drv.stop_at = w1
        _sleep_until(w0)
        parts["lead_in_s"] = time.perf_counter() - t
        setup_s = time.perf_counter() - t_start
        sched0 = svc.scheduler.stats()
        if tracer:
            with tracer.window():
                _sleep_until(w1)
        else:
            _sleep_until(w1)
        sched1 = svc.scheduler.stats()
        drv.finish()
        summary = tracer.stop_and_reduce() if tracer else None
        _settle_maintenance(svc)
        coll = drv.coll
        program_ids = _live_ids(coll)
        device = dev.describe(devices)
        limits = (tr.tombstone_limit(config),
                  coll.thresholds.maintenance_limits(coll.cfg.capacity, coll.spill_capacity)[1],
                  coll.delta_log_capacity)
    finally:
        svc.shutdown()
    ops, rebuilds = drv.log.ops, drv.log.rebuilds
    del drv, coll, svc
    gc.collect()

    t = time.perf_counter()
    rows = corpus.all_rows()
    tl = ref.Timeline.from_ops(ops, corpus.n_build, int(rows.shape[0]), origin=t_start)
    sample = ref.sample_queries(ops, t_start, w0, w1, int(mix["check_queries"]),
                                corpus.queries, seed)
    k = int(config["engine"]["k"])
    exact = ref.reference_topk(sample, rows, tl, corpus.metric, k)
    final = ref.final_state_checks(tl, program_ids)
    operand = config["engine"]["compute_dtype"]
    values = {**final, **ref.query_checks(sample, rows, tl, corpus.metric, exact, operand)}
    checks = ref.limits_for(config, values)
    ref_s = time.perf_counter() - t
    control_out = {}
    for precision in controls:
        c_s, c_ids, _ = ref.reference_topk(sample, rows, tl, corpus.metric, k,
                                           precision=precision)
        placed = dataclasses.replace(sample, ids=c_ids, scores=c_s)
        c_checks = ref.limits_for(config, {**final, **ref.query_checks(
            placed, rows, tl, corpus.metric, exact, operand)})
        control_out[precision] = {"correct": all(c.ok for c in c_checks),
                                  "checks": _checks(c_checks)}
    del rows

    r = Run(cell, seconds, w0, w1, setup_s, ops, rebuilds, sched0, sched1,
            peaks or dev.peaks(device["kind"]), live_rows=int(corpus.n_build),
            trace=summary)
    in_win = [op for op in ops if w0 <= op.due < w1]
    late = np.array([op.submit - op.due for op in in_win if not math.isnan(op.submit)])
    emit({"setup": {**parts, "total_s": setup_s, "cache_hits": watch.hits,
                    "cache_misses": watch.misses, "compiles": len(watch.compiles)}})
    emit({"window": {"seconds": seconds, "compiles": watch.between(w0, w1),
                     "due": {kd: len(r.due_in_window(kd)) for kd in ("query", "insert", "delete")},
                     "late_ms": _quantiles(late * 1e3)}})
    emit({"maintenance": _maintenance(r, limits)})
    emit({"reference": {"seconds": ref_s, "sample_rows": int(len(sample.q))}})
    if summary is not None:
        emit({"trace": {"inventory": summary.inventory,
                        "modules": {k_: list(v) for k_, v in sorted(
                            summary.modules.items(), key=lambda kv: -kv[1][1])[:20]}}})

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(r)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    if summary is not None:
        device["busy_s"], device["window_s"] = summary.busy_s, summary.window_s
    out = {"correct": all(c.ok for c in checks), "attempted": len(in_win),
           "failed": sum(op.error is not None for op in in_win),
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    if controls:
        out["controls"] = control_out
    out["checks"] = _checks(checks)
    return out


def _checks(checks: List[ref.Limit]) -> dict:
    return {c.name: {"value": c.value, "limit": c.limit,
                     "rule": ">=" if c.at_least else "<="} for c in checks}


def _quantiles(x: np.ndarray) -> dict:
    if not len(x):
        return {}
    return {"p50": float(np.percentile(x, 50)), "p99": float(np.percentile(x, 99)),
            "max": float(np.max(x))}


def _maintenance(r: Run, limits) -> dict:
    tomb, spill, log_cap = limits
    rows = []
    for b in r.rebuilds:
        rows.append({"seen_s": b.seen - r.w0, "published_s": b.published - r.w0,
                     "trigger": ("tombstone" if b.tombstones >= tomb else
                                 "spill" if b.spilled >= spill else "other"),
                     "tombstones": b.tombstones, "spilled": b.spilled,
                     "delta_log": b.backlog, "restart": b.backlog >= log_cap})
    return {"in_window": len(r.rebuilds_in_window()), "tombstone_limit": tomb,
            "spill_limit": spill, "rebuilds": rows}
