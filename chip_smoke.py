#!/usr/bin/env python3
"""Smoke run of the multi-tenant memory service on a TPU, at deployment size.

    python3 chip_smoke.py [--seed N]           # one chip, two tenants
    python3 chip_smoke.py --four-chips         # one sharded tenant per host

The deployment is AME's own setting: an agent's memory of HotpotQA-style
Wikipedia passages embedded at 768-d (a BEIR HotpotQA corpus under a 768-d
encoder such as Contriever or bge-base).  The passages are synthetic, drawn
from `--seed`: unit vectors around one topic centre per 16 passages, so a
query (a stored passage plus noise) has a handful of close neighbours, as a
multi-hop question does.  Nothing is downloaded.

One chip (the default): two HOT tenants of `EngineConfig(dim=768,
n_clusters=1024, list_capacity=512, nprobe=32, k=10)`, one storing float32
rows and one int8 codes, each built over 262,144 rows (half its 524,288
slots).  Both hold the same rows, so the int8 tenant's recall is compared
with the float32 tenant's on the same queries.  Through `MemoryService` the
run builds, inserts 8 batches of 32 rows, deletes 1,000 ids, queries (16
single probed queries, one 32-query full scan, one `query_many` across both
tenants), rebuilds, and queries again.

`--four-chips`: a sharded tenant on `jax.make_mesh((4,), ("shard",))` with
262,144 rows per chip, plus a second sharded tenant for a fused
`query_many`; build, insert, delete, `rebuild(name, shard=i)` for every
shard, then the fused query.  Nothing else runs.

Every phase prints one JSON line: wall seconds split into compile and
steady state (from JAX's compile events), and device bytes in use.  The
run fails unless recall@10 against the exact host oracle
(`core/metrics.brute_force_topk`) clears its floor, the live ids equal a
dict oracle after every write phase, no future or maintenance op failed,
and the state lives on the chip(s).  The last line of standard output is
`{"ok": true, "device": {...}}`.  Without a TPU the script exits non-zero
before doing any work; it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
FUTURE_TIMEOUT_S = 600.0    # any one op
WATCHDOG_S = 1140.0         # the whole run: dump stacks and exit non-zero

FULL_SCAN_FLOOR = 0.99      # exact scan, bf16 MXU operands
PROBED_FLOOR = 0.95         # nprobe 32 of 1,024 lists; a v5e measured >= 0.9875
INT8_VS_F32 = 0.95          # int8 recall >= this x float32 recall


@dataclasses.dataclass(frozen=True)
class Sizes:
    rows: int = 262_144         # per tenant; per chip when sharded
    dim: int = 768
    n_clusters: int = 1024
    list_capacity: int = 512
    nprobe: int = 32
    k: int = 10
    rows_per_topic: int = 16
    spill_capacity: int = 4096
    insert_batches: int = 8
    insert_rows: int = 32
    deletes: int = 1000
    probed_queries: int = 16
    scan_batch: int = 32
    many_batch: int = 8

    def engine_config(self, **kw):
        from repro.configs.base import EngineConfig
        return EngineConfig(dim=self.dim, n_clusters=self.n_clusters,
                            list_capacity=self.list_capacity,
                            nprobe=self.nprobe, k=self.k, index_policy="ivf",
                            **kw)


class CompileClock:
    """Wall seconds in which some thread of the process was tracing,
    lowering or compiling for JAX, and persistent cache hits and misses.

    Spans are merged before they are summed: a jit traced inside another
    one, or two scheduler threads compiling at once, count once.
    """

    SPANS = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self._spans = []
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if event in self.SPANS:
            with self._lock:
                self._spans.append((start, end))

    def seconds(self, t0=0.0, t1=float("inf")):
        """Compile seconds between `time.time()` stamps t0 and t1."""
        with self._lock:
            spans = sorted(self._spans)
        total, reach = 0.0, t0
        for start, end in spans:
            start, end = max(start, reach), min(end, t1)
            if end > start:
                total += end - start
                reach = end
        return total

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1


class Run:
    """Phase timing, checks and output for one smoke run."""

    def __init__(self, devices, clock):
        self.devices = devices
        self.clock = clock
        self.failures = []

    def emit(self, **rec):
        print(json.dumps(rec), flush=True)

    def device_bytes(self):
        out = []
        for d in self.devices:
            s = d.memory_stats() or {}
            out.append({k: s[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                          "bytes_limit") if k in s})
        return out

    @contextlib.contextmanager
    def phase(self, name, **tags):
        t0 = time.time()
        yield
        t1 = time.time()
        compile_s = self.clock.seconds(t0, t1)
        self.emit(phase=name, **tags, wall_s=t1 - t0, compile_s=compile_s,
                  steady_s=t1 - t0 - compile_s,
                  device_bytes=self.device_bytes())

    def check(self, name, ok, **detail):
        self.emit(check=name, ok=bool(ok), **detail)
        if not ok:
            self.failures.append(name)


class Tenant:
    """A collection and the host oracle of what it must hold.

    `bank[i]` is the row acknowledged under id `i`; `live` maps each id the
    collection must return to its bank row.
    """

    def __init__(self, name, coll, sizes, seed):
        self.name, self.coll, self.sizes = name, coll, sizes
        self.rng = np.random.default_rng(seed)
        n_topics = max(sizes.rows // sizes.rows_per_topic, 1)
        self.centres = self.rng.standard_normal((n_topics, sizes.dim),
                                                dtype=np.float32)
        self.bank = np.zeros((0, sizes.dim), np.float32)
        self.live = {}

    def draw(self, n, spread=0.5):
        x = self.centres[self.rng.integers(0, len(self.centres), n)]
        x = x + spread * self.rng.standard_normal(x.shape, dtype=np.float32)
        return x / np.linalg.norm(x, axis=1, keepdims=True)

    def add(self, rows):
        ids = np.arange(len(self.bank), len(self.bank) + len(rows),
                        dtype=np.int32)
        self.bank = np.concatenate([self.bank, rows]) if len(self.bank) \
            else rows
        self.live.update((int(i), int(i)) for i in ids)
        return ids

    def queries(self, n, seed, noise=0.03):
        """Stored passages plus noise; tenants with the same live rows draw
        the same queries for the same `seed`."""
        rng = np.random.default_rng(seed)
        ids = rng.choice(np.fromiter(self.live, np.int64), n, replace=False)
        q = self.bank[ids] + noise * rng.standard_normal(
            (n, self.sizes.dim), dtype=np.float32)
        return q / np.linalg.norm(q, axis=1, keepdims=True)

    def truth(self, q):
        """Exact top-k on the host CPU, in full f32."""
        import jax
        from repro.core import metrics
        ids = np.fromiter(self.live, np.int32)
        with jax.default_device(jax.devices("cpu")[0]), \
                jax.default_matmul_precision("highest"):
            return metrics.brute_force_topk(q, self.bank[ids], ids,
                                            self.sizes.k)

    def stored_ids(self):
        st = self.coll.snapshot()
        ids = np.concatenate([np.asarray(st.list_ids).ravel(),
                              np.asarray(st.spill_ids).ravel()])
        return ids[ids >= 0]


def await_op(svc, op):
    return svc.submit(op).result(timeout=FUTURE_TIMEOUT_S)


def check_live(run, t, when):
    got = t.stored_ids()
    want = set(t.live)
    have = set(got.tolist())
    run.check("live_ids", have == want and len(got) == len(have),
              tenant=t.name, after=when, live=len(have), oracle=len(want),
              lost=len(want - have), extra=len(have - want),
              duplicates=int(len(got) - len(have)))


def check_recall(run, t, name, got_ids, q, floor):
    from repro.core import metrics
    rec = metrics.recall_at_k(np.asarray(got_ids), t.truth(q))
    run.check("recall@10", rec >= floor, tenant=t.name, query=name,
              queries=len(q), recall=rec, floor=floor)
    return rec


def build_and_write(run, svc, t, n_rows):
    """Build over n_rows, then insert and delete, checking the oracle."""
    from repro.api import MemoryOp
    s = t.sizes
    rows = t.draw(n_rows)
    ids = t.add(rows)
    with run.phase("build", tenant=t.name, rows=n_rows):
        out = await_op(svc, MemoryOp("build", t.name, rows, ids=ids))
    run.check("build_no_dropped_rows",
              out["spilled"] <= s.spill_capacity * t.coll.n_shards,
              tenant=t.name, spilled=out["spilled"],
              spill_capacity=s.spill_capacity * t.coll.n_shards)
    check_live(run, t, "build")
    new = t.draw(s.insert_batches * s.insert_rows)
    new_ids = t.add(new)
    with run.phase("insert", tenant=t.name, batches=s.insert_batches,
                   rows=s.insert_rows):
        for i in range(0, len(new), s.insert_rows):
            await_op(svc, MemoryOp("insert", t.name, new[i:i + s.insert_rows],
                                   ids=new_ids[i:i + s.insert_rows]))
    check_live(run, t, "insert")
    gone = t.rng.choice(np.fromiter(t.live, np.int32), s.deletes,
                        replace=False)
    with run.phase("delete", tenant=t.name, ids=s.deletes):
        n_hit = await_op(svc, MemoryOp("delete", t.name, gone))
    for i in gone.tolist():
        del t.live[i]
    run.check("delete_hits", n_hit == s.deletes, tenant=t.name, hit=n_hit,
              requested=s.deletes)
    check_live(run, t, "delete")


def query_tenant(run, svc, t, when, seed):
    """16 single probed queries and one full-scan batch; returns recalls."""
    from repro.api import MemoryOp
    s = t.sizes
    qp = t.queries(s.probed_queries, seed)
    with run.phase("query_probed", tenant=t.name, after=when,
                   queries=s.probed_queries, batch=1):
        got = [await_op(svc, MemoryOp("query", t.name, q[None],
                                      path="probed"))[0][0]
               for q in qp]
    probed = check_recall(run, t, f"probed/{when}", np.stack(got), qp,
                          PROBED_FLOOR)
    qs = t.queries(s.scan_batch, seed + 1)
    with run.phase("query_full_scan", tenant=t.name, after=when,
                   batch=s.scan_batch):
        ids, _ = await_op(svc, MemoryOp("query", t.name, qs,
                                        path="full_scan"))
    full = check_recall(run, t, f"full_scan/{when}", ids, qs,
                        FULL_SCAN_FLOOR)
    return probed, full


def check_on_devices(run, t, n_devices):
    import jax
    leaves = [x for x in jax.tree.leaves(t.coll.snapshot())
              if x is not None]
    devs = {d for x in leaves for d in x.devices()}
    run.check("state_on_device",
              {d.platform for d in devs} == {run.devices[0].platform}
              and len(devs) == n_devices,
              tenant=t.name, leaves=len(leaves),
              devices=sorted(str(d) for d in devs))
    if n_devices > 1:
        # every slot-axis leaf must be split over the chips, not replicated
        # onto one of them
        lists = t.coll.snapshot().lists
        per = {s.device: s.data.shape for s in lists.addressable_shards}
        run.check("lists_sharded",
                  len(per) == n_devices and all(
                      shp[1] * n_devices == lists.shape[1]
                      for shp in per.values()),
                  tenant=t.name, shards={str(d): list(v)
                                         for d, v in per.items()})


def check_service(run, svc, tenants):
    stats = svc.stats()
    m = stats["maintenance"]
    run.check("maintenance_clean", m.get("failed", 0) == 0
              and m.get("last_error") is None, maintenance=m)
    for t in tenants:
        c = stats["collections"][t.name]
        run.check("tenant_hot", c["residency"] == "hot", tenant=t.name,
                  residency=c["residency"], live=c["live"],
                  index_bytes=t.coll.index_nbytes())


def run_one_chip(run, sizes, seed):
    from repro.api import MemoryOp, MemoryService
    svc = MemoryService()
    tenants = []
    try:
        for i, dtype in enumerate(("float32", "int8")):
            name = "f32" if dtype == "float32" else "int8"
            coll = svc.create_collection(
                name, sizes.engine_config(store_dtype=dtype), seed=seed,
                spill_capacity=sizes.spill_capacity)
            # same seed: both tenants hold and receive the same rows
            tenants.append(Tenant(name, coll, sizes, seed))
        for t in tenants:
            build_and_write(run, svc, t, sizes.rows)
        before = {t.name: query_tenant(run, svc, t, "writes", seed + 10)
                  for t in tenants}

        qm = tenants[0].queries(sizes.many_batch, seed + 20)
        with run.phase("query_many", tenants=[t.name for t in tenants],
                       batch=sizes.many_batch):
            many = svc.query_many([(t.name, qm) for t in tenants])
        for t, (ids, _) in zip(tenants, many):
            solo, _ = await_op(svc, MemoryOp("query", t.name, qm))
            run.check("query_many_equals_query",
                      np.array_equal(ids, solo), tenant=t.name)
            check_recall(run, t, "query_many", ids, qm, PROBED_FLOOR)

        for t in tenants:
            with run.phase("rebuild", tenant=t.name):
                out = await_op(svc, MemoryOp("rebuild", t.name))
            run.check("rebuild_not_aborted", not out.get("aborted", False),
                      tenant=t.name, spilled=out.get("spilled"))
            check_live(run, t, "rebuild")
        after = {t.name: query_tenant(run, svc, t, "rebuild", seed + 30)
                 for t in tenants}

        for when, rec in (("writes", before), ("rebuild", after)):
            for j, path in enumerate(("probed", "full_scan")):
                f32, q8 = rec["f32"][j], rec["int8"][j]
                run.check("int8_vs_f32", q8 >= INT8_VS_F32 * f32,
                          query=f"{path}/{when}", f32=f32, int8=q8,
                          ratio_floor=INT8_VS_F32)
        for t in tenants:
            check_on_devices(run, t, 1)
        check_service(run, svc, tenants)
    finally:
        svc.shutdown()


def run_four_chips(run, sizes, seed):
    import jax
    from repro.api import MemoryOp, MemoryService
    n = 4
    mesh = jax.make_mesh((n,), ("shard",))
    cfg = sizes.engine_config(shard_db=True)
    svc = MemoryService()
    try:
        big = Tenant("sharded", svc.create_collection(
            "sharded", cfg, mesh=mesh, seed=seed,
            spill_capacity=sizes.spill_capacity), sizes, seed)
        small = Tenant("sharded-b", svc.create_collection(
            "sharded-b", cfg, mesh=mesh, seed=seed + 1,
            spill_capacity=sizes.spill_capacity), sizes, seed + 1)
        build_and_write(run, svc, big, n * sizes.rows)
        rows = small.draw(sizes.rows)
        ids = small.add(rows)
        with run.phase("build", tenant=small.name, rows=sizes.rows):
            await_op(svc, MemoryOp("build", small.name, rows, ids=ids))
        check_live(run, small, "build")
        for i in range(n):
            with run.phase("rebuild", tenant=big.name, shard=i):
                out = await_op(svc, MemoryOp("rebuild", big.name, shard=i))
            run.check("rebuild_not_aborted", not out.get("aborted", False),
                      tenant=big.name, shard=i, spilled=out.get("spilled"))
            check_live(run, big, f"rebuild shard {i}")

        qa = big.queries(sizes.scan_batch, seed + 10)
        qb = small.queries(sizes.scan_batch, seed + 11)
        stacks = svc.stats()["stack_cache"]["misses"]
        with run.phase("query_many_fused", tenants=[big.name, small.name],
                       batch=sizes.scan_batch):
            many = svc.query_many([(big.name, qa), (small.name, qb)])
        # both tenants share a batch signature, so the window runs as one
        # stacked dispatch: exactly one new stack is built
        stacked = svc.stats()["stack_cache"]["misses"] - stacks
        run.check("query_many_fused", stacked == 1, new_stacks=stacked)
        for t, q, (ids, _) in ((big, qa, many[0]), (small, qb, many[1])):
            solo, _ = await_op(svc, MemoryOp("query", t.name, q))
            run.check("query_many_equals_query", np.array_equal(ids, solo),
                      tenant=t.name)
            check_recall(run, t, "query_many_fused", ids, q,
                         FULL_SCAN_FLOOR)
        for t in (big, small):
            check_on_devices(run, t, n)
        check_service(run, svc, (big, small))
    finally:
        svc.shutdown()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase, on four chips")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {devices[0].platform}; "
                 "nothing was run")
    n = 4 if args.four_chips else 1
    if len(devices) < n:
        sys.exit(f"chip_smoke: --four-chips needs 4 chips, found "
                 f"{len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    from repro.kernels import ops
    cache_dir = compile_cache.enable()
    run = Run(devices[:n], CompileClock())
    run.emit(device_kind=devices[0].device_kind, devices=len(devices),
             jax=jax.__version__, compile_cache=cache_dir,
             kernels_interpreted=ops.interpret_kernels())
    run.check("kernels_compiled", not ops.interpret_kernels())

    t0 = time.time()
    (run_four_chips if args.four_chips else run_one_chip)(
        run, Sizes(), args.seed)
    run.emit(total_s=time.time() - t0, compile_s=run.clock.seconds(t0),
             cache_hits=run.clock.cache_hits,
             cache_misses=run.clock.cache_misses,
             failed_checks=run.failures)
    faulthandler.cancel_dump_traceback_later()
    if run.failures:
        sys.exit(f"chip_smoke: failed checks: {run.failures}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": n}}), flush=True)


if __name__ == "__main__":
    main()
