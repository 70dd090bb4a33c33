"""A run with the timed path broken underneath reads `correct` false, once
for each fault a one-chip cell of this service can have: a write that
returns the state unchanged, a batch half left out, an answer altered
where it is produced, and an acknowledged delete that queries still see.
(No cell spans chips, so no exchange can be left out.)  The harness's look
for a chip is skipped; everything else is a run."""
import jax
import jax.numpy as jnp
import pytest

import tiny
from repro.core import index as ivf


def _unchanged_insert(real):
    def insert(state, x, ids, cfg):
        return state, 0 * ids.shape[0]
    return insert


def _half_insert(real):
    def insert(state, x, ids, cfg):
        h = x.shape[0] // 2
        return real(state, x[:h], ids[:h], cfg)
    return insert


def _altered_probe(real):
    def query(state, q, cfg, k, nprobe):
        ids, scores = real(state, q, cfg, k, nprobe)
        return ids.at[:, 0].set(ids[:, -1]), scores
    return query


def _half_scan(real):
    def query(state, q, cfg, k):
        h = q.shape[0] // 2
        ids, scores = real(state, q[:h], cfg, k)
        return ids.repeat(2, axis=0)[:q.shape[0]], scores.repeat(2, axis=0)[:q.shape[0]]
    return query


def _marked_delete(real):
    # a tombstone keeps the row's id as -(id) - 2: still negative, so every
    # rebuild, snapshot and sound scan treats the row as gone
    plain = jax.jit(ivf._delete)

    def delete(state, ids):
        new, n = plain(state, ids)

        def mark(old, now):
            return jnp.where((now == -1) & (old >= 0), -old - 2, now)
        return new._replace(list_ids=mark(state.list_ids, new.list_ids),
                            spill_ids=mark(state.spill_ids, new.spill_ids)), n
    return delete


def _probe_ignoring_tombstones(real):
    def query(state, q, cfg, k, nprobe):
        def unmark(a):
            return jnp.where(a < -1, -a - 2, a)
        return real(state._replace(list_ids=unmark(state.list_ids),
                                   spill_ids=unmark(state.spill_ids)), q, cfg, k, nprobe)
    return query


TOMBSTONES_IGNORED = [("delete_shared", _marked_delete), ("delete", _marked_delete),
                      ("query_probed", _probe_ignoring_tombstones)]

# fault: (what is patched, the deployment, the mix, the checks it must fail)
FAULTS = {
    "write_returns_state_unchanged": ([("insert_shared", _unchanged_insert)],
                                      tiny.TOPICS, tiny.AGENT, None),
    "half_the_insert_batch_left_out": ([("insert_shared", _half_insert)],
                                       tiny.TOPICS, tiny.AGENT, None),
    "answer_altered_where_produced": ([("query_probed", _altered_probe)],
                                      tiny.TOPICS, tiny.AGENT, None),
    "half_the_query_batch_left_out": ([("query_full_scan", _half_scan)],
                                      tiny.TOPICS, tiny.BATCH, None),
    # the final index holds no deleted row; only the window's answers show it
    "probe_serves_acknowledged_deletes": (TOMBSTONES_IGNORED, tiny.CLUSTERS, tiny.STREAM,
                                          {"wrong_ids"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(fault, monkeypatch):
    patches, config, mix, must_fail = FAULTS[fault]
    for name, make in patches:
        monkeypatch.setattr(ivf, name, make(getattr(ivf, name)))
    out = tiny.run(tiny.cell(config, mix), seconds=1.5)
    failed = {k for k, v in out["checks"].items()
              if not (v["value"] >= v["limit"] if v["rule"] == ">=" else v["value"] <= v["limit"])}
    assert not out["correct"] and failed, out["checks"]
    assert (must_fail or failed) <= failed, out["checks"]
