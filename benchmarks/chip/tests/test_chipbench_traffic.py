"""The traffic generator: schedules from the seed, the maintenance cadence
stream-churn is built for, and agent-turns' freedom from maintenance.
These call the harness's functions, not its command."""
import itertools
import json

import numpy as np
import pytest

import tiny
from chipbench import traffic as tr
from chipbench.data import Corpus

SEEDS = [1, 2, 3141592653, 2**31 + 17, 9876543210123]


def _load(kind, name):
    return json.loads((tiny.BENCH / kind / f"{name}.json").read_text())


STREAM, AGENT = _load("traffic", "stream-churn"), _load("traffic", "agent-turns")
MSTURING = _load("configs", "msturing-100-streaming")
HOTPOT = _load("configs", "hotpotqa-bge-base-768")
SECONDS = json.loads((tiny.BENCH.parents[1] / "BENCHMARK.json").read_text())["run_seconds"]


def _first(mix, seed, n=2000):
    return list(itertools.islice(tr.schedule(mix, seed), n))


@pytest.mark.parametrize("mix", [STREAM, AGENT], ids=["stream-churn", "agent-turns"])
def test_same_seed_same_schedule(mix):
    assert _first(mix, 3141592653) == _first(mix, 3141592653)
    assert _first(mix, 3141592653) != _first(mix, 3141592654)


@pytest.mark.parametrize("mix", [STREAM, AGENT], ids=["stream-churn", "agent-turns"])
def test_every_seed_offers_the_same_work(mix):
    # the same count per stream in each 60 s block for every seed, while
    # Poisson arrivals bunch differently from second to second
    horizon = 120
    per_block, per_second = [], []
    for seed in SEEDS:
        ev = tr.take_until(tr.schedule(mix, seed), horizon)
        assert all(a <= b for (a, _), (b, _) in zip(ev, ev[1:]))
        blocks = np.zeros((horizon // 60, len(mix["streams"])), int)
        seconds = np.zeros((horizon, len(mix["streams"])), int)
        for t, i in ev:
            blocks[int(t // 60), i] += 1
            seconds[int(t), i] += 1
        per_block.append(blocks)
        per_second.append(seconds)
    for blocks in per_block[1:]:
        assert (blocks == per_block[0]).all()
    for i, s in enumerate(mix["streams"]):
        counts = np.array([sec[:, i] for sec in per_second])
        if s["arrival"] == "poisson":
            assert counts.std() > 0.5 * np.sqrt(s["rate_per_s"])
        else:
            assert (counts == counts[0]).all()


def _gaps(offsets):
    """The gaps g of one block, from its offsets cumsum(g) - g/2."""
    g = [2 * offsets[0]]
    for a, b in zip(offsets, offsets[1:]):
        g.append(2 * (b - a) - g[-1])
    return np.array(g)


def test_poisson_gaps_are_one_set_in_another_order():
    s = {"arrival": "poisson", "rate_per_s": 50, "block_s": 2}
    a = np.array(list(itertools.islice(tr.stream_times(s, 1, 0), 100)))
    b = np.array(list(itertools.islice(tr.stream_times(s, 2, 0), 100)))
    assert a.max() < 2 and b.max() < 2
    ga, gb = _gaps(a), _gaps(b)
    assert not np.allclose(ga, gb)
    assert np.allclose(np.sort(ga), np.sort(gb)) and ga.sum() == pytest.approx(2.0)
    # the gaps are the exponential law's quantiles: mean 1/rate, widest ~ln(2n)/rate
    assert ga.mean() == pytest.approx(1 / 50)
    assert ga.max() == pytest.approx(np.log(200) / 50, rel=0.05)


def test_stream_churn_rebuilds_the_same_count_for_every_seed():
    plans = [tr.maintenance_plan(MSTURING, STREAM, SECONDS, seed) for seed in SEEDS]
    counts = {len(p["triggers"]) for p in plans}
    assert len(counts) == 1 and counts.pop() >= 3
    # no trigger or publish within a few seconds of the window's end, so a
    # cycle's jitter cannot move a rebuild in or out of the window
    assert min(p["end_margin_s"] for p in plans) >= 2.0
    # the cadence: one rebuild per tombstone_limit deleted rows
    assert plans[0]["cycle_s"] == pytest.approx(
        tr.tombstone_limit(MSTURING) / tr.rows_per_second(STREAM, "delete"))


def test_stream_churn_keeps_the_delta_log_and_stored_rows():
    rebuild_s = MSTURING["assumed"]["rebuild_s"]
    write_ops = sum(s["rate_per_s"] for s in STREAM["streams"] if s["op"] != "query")
    # writes logged during one rebuild stay far below the 1,024-op delta log
    assert write_ops * rebuild_s < 1024 / 4
    # deletes of a whole run (prefill, lead-in, window) stay within the
    # older halves of the stored clusters, so no cluster ever empties
    lead = STREAM["lead_in"]
    span = SECONDS + lead["trigger_after_s"] + rebuild_s + lead["after_publish_s"] + 5
    assert tr.tombstone_limit(MSTURING) + tr.rows_per_second(STREAM, "delete") * span \
        < MSTURING["rows"] // 2


def test_stream_churn_queries_reach_the_rows_a_run_deletes():
    # on the tiny deployment: the rows a run deletes (the oldest ids) are
    # among many queries' nearest, next to live rows of the same cluster
    corpus = Corpus(tiny.CLUSTERS, 3141592653, 64, 256)
    build = np.asarray(corpus.build)
    q = corpus.queries
    d = (q * q).sum(1)[:, None] - 2 * q @ build.T + (build * build).sum(1)[None]
    top = np.argsort(d, axis=1)[:, :10]
    n_deleted = tiny.CLUSTERS["rows"] // 4
    deleted_near = (top < n_deleted).any(axis=1)
    assert 0.15 < deleted_near.mean() < 0.85
    # ... and the same queries still have live rows of their own cluster
    newer = (top >= tiny.CLUSTERS["rows"] // 2).sum(axis=1)[deleted_near]
    assert (newer >= 1).all() and newer.mean() >= 4


def test_agent_turns_never_reaches_a_maintenance_threshold():
    assert tr.rows_per_second(AGENT, "delete") == 0
    assert tr.maintenance_plan(HOTPOT, AGENT, SECONDS, 1)["triggers"] == []
    # spill pressure depends on where the rows land, which no rate fixes:
    # every chip run records its rebuilds on the `maintenance` line (none)


def test_pools_cover_a_run():
    n_ins, n_q = tr.pool_sizes(STREAM, SECONDS)
    assert n_ins >= tr.rows_per_second(STREAM, "insert") * (SECONDS + 5)
    assert n_q >= tr.rows_per_second(STREAM, "query") * (SECONDS + 5)
    batch = _load("traffic", "batch-retrieval")
    assert tr.pool_sizes(batch, SECONDS) == (0, batch["query_pool_batches"] * 32)
