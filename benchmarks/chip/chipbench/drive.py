"""Drive `MemoryService.submit` with a traffic mix and record every
operation: when it was due, submitted and completed, and what it returned.

Open loop: one thread submits each operation at its due time (late, when the
service's submission window blocks it), one waiter per operation kind
records completions in submission order, and follow-ups (an agent's insert
after its recall) are submitted by a thread of their own, due when their
parent completed.  Closed loop: `clients` threads each submit, wait, record.
A monitor thread polls the public maintenance counters for rebuilds.
"""
from __future__ import annotations

import math
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from chipbench import traffic as tr

WAIT_S = 120.0          # the longest any one operation may take before it fails


@dataclass
class Op:
    kind: str
    due: float
    rows: int
    ids: Optional[np.ndarray] = None      # insert / delete ids
    qrow: int = 0                         # first query-pool row
    submit: float = math.nan
    done: float = math.nan
    error: Optional[str] = None
    result: Optional[tuple] = None        # query: (ids [B, k], scores [B, k])
    then: Optional[dict] = None


@dataclass
class Rebuild:
    seen: float                           # first poll that saw it in flight
    published: float = math.nan
    tombstones: int = 0                   # most tombstone pressure before it
    spilled: int = 0                      # most spill pressure before it
    backlog: int = 0                      # longest delta log seen during it


@dataclass
class Log:
    ops: List[Op] = field(default_factory=list)
    rebuilds: List[Rebuild] = field(default_factory=list)
    lock: threading.Lock = field(default_factory=threading.Lock)

    def add(self, op: Op) -> None:
        with self.lock:
            self.ops.append(op)


class LoadGen:
    """Feeds one collection of one service; owns the id bookkeeping."""

    def __init__(self, svc, coll_name: str, corpus, mix: dict, seed: int,
                 after: Optional["LoadGen"] = None):
        """`after`: a load generator whose traffic on this collection came before;
        this one carries on its insert, delete and query cursors."""
        from repro.api import MemoryOp
        self._MemoryOp = MemoryOp
        self.svc, self.name, self.corpus, self.mix, self.seed = (
            svc, coll_name, corpus, mix, seed)
        self.coll = svc.collection(coll_name)
        self.log = Log()
        self._ins_cursor = 0           # next insert-pool row
        self._del_cursor = 0           # next build id to delete (oldest first)
        self._q_cursor = 0
        if after is not None:
            self._ins_cursor, self._del_cursor, self._q_cursor = (
                after._ins_cursor, after._del_cursor, after._q_cursor)
        self._cursor_lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: dict = {}
        self._waiters = {k: queue.Queue() for k in ("query", "insert", "delete")}
        self._follow = queue.Queue()
        self.published = threading.Event()
        self._rebuilds_seen = 0
        self.failures: List[str] = []
        self.stop_at = math.inf

    # -- operations ---------------------------------------------------------
    def make(self, kind: str, rows: int, due: float) -> Op:
        with self._cursor_lock:
            if kind == "insert":
                pool = len(self.corpus.inserts)
                start = self._ins_cursor
                self._ins_cursor += rows
                ids = self.corpus.n_build + np.arange(start, start + rows,
                                                      dtype=np.int64)
                if start + rows > pool:
                    raise RuntimeError("the insert pool ran out; make it larger")
                return Op(kind, due, rows, ids=ids)
            if kind == "delete":
                start = self._del_cursor
                self._del_cursor += rows
                if start + rows > self.corpus.n_build:
                    raise RuntimeError("deletes ran past the stored rows")
                return Op(kind, due, rows, ids=np.arange(start, start + rows,
                                                         dtype=np.int64))
            start = self._q_cursor
            self._q_cursor = (start + rows) % len(self.corpus.queries)
            return Op(kind, due, rows, qrow=start)

    def _payload(self, op: Op):
        if op.kind == "insert":
            j = int(op.ids[0] - self.corpus.n_build)
            return self._MemoryOp("insert", self.name,
                                  self.corpus.inserts[j:j + op.rows],
                                  ids=op.ids.astype(np.int32), concurrent=True)
        if op.kind == "delete":
            return self._MemoryOp("delete", self.name, op.ids.astype(np.int32))
        q = np.take(self.corpus.queries, range(op.qrow, op.qrow + op.rows),
                    axis=0, mode="wrap")
        return self._MemoryOp("query", self.name, q)

    def submit(self, op: Op):
        self.log.add(op)
        op.submit = time.perf_counter()
        try:
            fut = self.svc.submit(self._payload(op))
        except Exception as e:                      # noqa: BLE001 - recorded
            op.error, op.done = repr(e), time.perf_counter()
            return None
        return fut

    def settle(self, op: Op, fut) -> None:
        """Wait for `fut` and record the completion on `op`."""
        if fut is None:
            return
        if not fut.wait(WAIT_S):
            op.error = "no answer within %.0f s" % WAIT_S
            return
        op.done = time.perf_counter()
        err = fut.exception(0)
        if err is not None:
            op.error = repr(err)
        elif op.kind == "query":
            ids, scores = fut.result(0)
            op.result = (np.asarray(ids), np.asarray(scores))

    def run_sync(self, kind: str, rows: int) -> Op:
        op = self.make(kind, rows, time.perf_counter())
        self.settle(op, self.submit(op))
        if op.error:
            raise RuntimeError(f"{kind} failed in set-up: {op.error}")
        return op

    # -- threads ------------------------------------------------------------
    def _start(self, role: str, target, *args) -> None:
        def guarded():
            try:
                target(*args)
            except Exception as e:                  # noqa: BLE001 - reported
                self.failures.append(f"{role}: {e!r}")
                self._stop.set()
        t = threading.Thread(target=guarded, daemon=True, name=f"chipbench-{role}")
        t.start()
        self._threads[role] = t

    def _waiter(self, kind: str) -> None:
        q = self._waiters[kind]
        while True:
            item = q.get()
            if item is None:
                return
            op, fut = item
            self.settle(op, fut)
            if op.then and op.error is None:
                self._follow.put(self.make(op.then["op"], int(op.then["rows"]), op.done))

    def _follower(self) -> None:
        while True:
            op = self._follow.get()
            if op is None:
                return
            self._waiters[op.kind].put((op, self.submit(op)))

    def _open_loop(self, t0: float) -> None:
        streams = self.mix["streams"]
        for t, i in tr.schedule(self.mix, self.seed):
            due = t0 + t
            if self._stop.is_set() or due >= self.stop_at:
                return
            delay = due - time.perf_counter()
            if delay > 0 and self._stop.wait(delay):
                return
            s = streams[i]
            op = self.make(s["op"], int(s.get("rows", 1)), due)
            op.then = s.get("then")
            self._waiters[op.kind].put((op, self.submit(op)))

    def _closed_client(self) -> None:
        s = self.mix["streams"][0]
        while not self._stop.is_set() and time.perf_counter() < self.stop_at:
            op = self.make(s["op"], int(s.get("rows", 1)), time.perf_counter())
            self.settle(op, self.submit(op))

    def _monitor(self) -> None:
        maint = self.svc.maintenance
        current: Optional[Rebuild] = None
        peak_t = peak_s = 0
        while not self._stop.wait(0.01):
            p = self.coll.maintenance_pressure()
            inflight = maint is not None and self.name in maint.stats()["inflight"]
            n = self.coll.counters["rebuilds"]
            now = time.perf_counter()
            if current is None:
                peak_t, peak_s = max(peak_t, p["tombstones"]), max(peak_s, p["spilled"])
                if inflight:
                    current = Rebuild(now, tombstones=peak_t, spilled=peak_s)
            else:
                current.backlog = max(current.backlog, p["delta_backlog"])
            if n > self._rebuilds_seen:
                self._rebuilds_seen = n
                r = current or Rebuild(now, tombstones=peak_t, spilled=peak_s)
                r.published = now
                with self.log.lock:
                    self.log.rebuilds.append(r)
                current, peak_t, peak_s = None, 0, 0
                self.published.set()

    def start(self) -> float:
        """Start the traffic now; returns the schedule's start time.  It
        runs until `stop_at` (which the caller may set later) or `finish`."""
        t0 = time.perf_counter()
        self._rebuilds_seen = self.coll.counters["rebuilds"]
        self._start("monitor", self._monitor)
        if self.mix["loop"] == "closed":
            for c in range(int(self.mix["clients"])):
                self._start(f"client{c}", self._closed_client)
        else:
            for kind in self._waiters:
                self._start(f"wait-{kind}", self._waiter, kind)
            self._start("follow", self._follower)
            self._start("open-loop", self._open_loop, t0)
        return t0

    def finish(self) -> None:
        """Stop submitting, wait for every operation, stop every thread.
        Queries settle first, so every follow-up is queued before its
        waiter is told to end."""
        self._stop.set()
        order = ([r for r in self._threads if r.startswith(("client", "open-loop"))]
                 + ["wait-query", "follow", "wait-insert", "wait-delete", "monitor"])
        ends = {"wait-query": (self._waiters["query"], None),
                "follow": (self._follow, None),
                "wait-insert": (self._waiters["insert"], None),
                "wait-delete": (self._waiters["delete"], None)}
        for role in order:
            t = self._threads.get(role)
            if t is None:
                continue
            if role in ends:
                q, sentinel = ends[role]
                q.put(sentinel)
            t.join(WAIT_S + 10)
        alive = [t.name for t in self._threads.values() if t.is_alive()]
        if alive:
            self.failures.append(f"threads still running: {alive}")
        if self.failures:
            raise RuntimeError("; ".join(self.failures))
