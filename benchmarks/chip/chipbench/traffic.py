"""The general traffic generator: a schedule of operations from the
parameters in `traffic/<name>.json` and the seed.

A mix is open or closed loop.  An open-loop mix is a list of streams, each
an operation kind with a batch size (`rows`), an arrival law and a rate:

- `"arrival": "poisson"`: a Poisson process of `rate_per_s` arrivals a
  second, held to a fixed total: the schedule runs in blocks of
  `block_s` seconds (60 by default), each holding exactly
  `rate_per_s * block_s` arrivals whose gaps are the quantiles of the
  exponential law, in an order drawn from the seed.  Arrivals bunch and
  thin from second to second as a Poisson source's do, while every seed
  offers the same work per block and the same set of gaps in another
  order.
- `"arrival": "fixed"`: one arrival every `1 / rate_per_s` seconds, shifted
  by `phase` periods.

A stream may name a follow-up (`"then": {"op": "insert", "rows": 4}`),
submitted when its operation completes: an agent's turn recalls, then writes.
A closed-loop mix runs `clients` callers, each submitting its next request
when the previous one completes.

Inserts take the next unused rows of the insert pool; deletes take the
oldest ids still live, first in first out, as a streaming runbook's steady
churn does.
"""
from __future__ import annotations

import heapq
from typing import Iterator, List, Tuple

import numpy as np

from chipbench.data import seed_words


def _poisson_block(n: int, span: float, rng: np.random.Generator) -> np.ndarray:
    """Offsets in [0, span) of `n` arrivals of one block."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps = rng.permutation(gaps * (span / gaps.sum()))
    return np.cumsum(gaps) - gaps / 2


def stream_times(stream: dict, seed: int, index: int) -> Iterator[float]:
    """Due times (seconds from the schedule's start) of one stream."""
    rate = float(stream["rate_per_s"])
    if stream["arrival"] == "fixed":
        period = 1.0 / rate
        k = 0
        while True:
            yield (k + float(stream.get("phase", 0.0))) * period
            k += 1
    elif stream["arrival"] == "poisson":
        span = float(stream.get("block_s", 60.0))
        n = rate * span
        if n != int(n) or n < 1:
            raise ValueError("a poisson stream needs a whole rate_per_s * block_s >= 1")
        rng = np.random.default_rng([*seed_words(seed), index])
        start = 0.0
        while True:
            for t in _poisson_block(int(n), span, rng):
                yield start + float(t)
            start += span
    else:
        raise ValueError(f"unknown arrival law {stream['arrival']!r}")


def schedule(traffic: dict, seed: int) -> Iterator[Tuple[float, int]]:
    """(due time, stream index) of every open-loop arrival, in time order;
    endless, so the caller stops it."""
    def tagged(i: int, stream: dict) -> Iterator[Tuple[float, int]]:
        for t in stream_times(stream, seed, i):
            yield t, i
    return heapq.merge(*(tagged(i, s) for i, s in enumerate(traffic["streams"])))


def take_until(sched: Iterator[Tuple[float, int]], end: float) -> List[Tuple[float, int]]:
    out = []
    for t, i in sched:
        if t >= end:
            break
        out.append((t, i))
    return out


def rows_per_second(traffic: dict, op: str) -> float:
    """Rows per second the mix offers to `op`, follow-ups included."""
    total = 0.0
    for s in traffic.get("streams", []):
        if s["op"] == op:
            total += float(s["rate_per_s"]) * int(s.get("rows", 1))
        then = s.get("then")
        if then and then["op"] == op:
            total += float(s["rate_per_s"]) * int(then["rows"])
    return total


def pool_sizes(traffic: dict, seconds: float) -> Tuple[int, int]:
    """(insert rows, query rows) to make for a run of `seconds` measured
    seconds plus the lead-in; a longer run wraps round the pools."""
    span = seconds + float(traffic.get("lead_in", {}).get("pool_extra_s", 10.0))
    if traffic["loop"] == "closed":
        batch = int(traffic["streams"][0].get("rows", 1))
        return 0, int(traffic.get("query_pool_batches", 256)) * batch
    n_ins = int(np.ceil(rows_per_second(traffic, "insert") * span))
    n_q = int(np.ceil(rows_per_second(traffic, "query") * span))
    return n_ins, n_q


def tombstone_limit(config: dict) -> int:
    """The tombstone count at which the deployment's maintenance rebuilds
    (the program's rule: a fraction of capacity, floored by a minimum)."""
    e, t = config["engine"], config.get("thresholds", {})
    capacity = int(e["n_clusters"]) * int(e["list_capacity"])
    return max(int(t.get("maintenance_min_pending", 64)),
               int(float(t.get("maintenance_tombstone_frac", 0.1)) * capacity))


def maintenance_plan(config: dict, traffic: dict, seconds: float, seed: int) -> dict:
    """Tombstone-triggered rebuilds that the schedule's deletes cause in the
    window, reckoned from the schedule itself.

    The window opens `lead_in.after_publish_s` after a rebuild published; that
    rebuild's snapshot was taken `assumed.rebuild_s` before its publish, and
    each later rebuild triggers once `tombstone_limit` deletes have landed
    since the last snapshot.  Returns the trigger times (seconds from the
    window's start) and the least distance from the window's end to a trigger
    or to a publish."""
    limit = tombstone_limit(config)
    rebuild_s = float(config.get("assumed", {}).get("rebuild_s", 0.0))
    delay = float(traffic.get("lead_in", {}).get("after_publish_s", 0.0))
    deletes = [(t, int(traffic["streams"][i].get("rows", 1)))
               for t, i in take_until(schedule(traffic, seed), 4 * seconds + 60)
               if traffic["streams"][i]["op"] == "delete"]
    if not deletes:
        return {"triggers": [], "limit": limit, "end_margin_s": float("inf")}
    # place the window so the publish that opens the lead-in's rebuild falls
    # at schedule time t0 = the first trigger of the schedule + rebuild_s
    snap, acc, triggers = None, 0, []
    for t, n in deletes:
        acc += n
        if acc >= limit:
            triggers.append(t)
            snap, acc = t, 0
    if snap is None:
        return {"triggers": [], "limit": limit, "end_margin_s": float("inf")}
    w0 = triggers[0] + rebuild_s + delay
    inside = [t - w0 for t in triggers[1:] if w0 <= t < w0 + seconds]
    events = [t - w0 for t in triggers[1:]] + [t - w0 + rebuild_s for t in triggers[1:]]
    margin = min(abs(seconds - e) for e in events)
    return {"triggers": inside, "limit": limit, "end_margin_s": margin,
            "cycle_s": limit / rows_per_second(traffic, "delete")}
