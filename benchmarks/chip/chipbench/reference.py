"""The plain reference and the comparison that decides `correct`.

The reference imports nothing of the program.  It is exact top-k over the
rows that were live, in float32 at the highest matmul precision on the
device, with float64 on the host for the scores of the rows a query
returned, plus an oracle of every acknowledged write built from the
load generator's own record of when each operation was submitted and acknowledged.

What a query may see.  A query submitted at `s` and seen complete at `c`
must see every row acknowledged before `s` and not deleted before `c`
("certainly live"), and may see any row submitted before `c` and not
acknowledged deleted before `s` ("possibly live").  The exact top-k over
the certainly-live rows is the yardstick: an exact search over any set
between the two scores at least as high, rank by rank.

Numbers compared (each against its limit):

- `lost_rows`: acknowledged inserts (and stored rows) never deleted that
  the final index does not hold; limit 0.
- `resurrected_rows`: acknowledged deletes, never-inserted or duplicated
  ids that the final index holds; limit 0.
- `wrong_ids`: returned ids that were not possibly live, repeated within
  one answer, or missing from an answer that had k live rows to give;
  limit 0.
- `score_err`: the widest gap, over |q|*|x|, between a returned score and
  the score of the same row in the precision the configuration states
  (`engine.compute_dtype`: the product's operands rounded to it, summed
  exactly) or its exact score, whichever is nearer; a score computed in
  that precision or above reads rounding alone, one computed below it
  reads its own rounding.  Limit from the configuration.
- `recall_at_10`: per query, the share of the k returned rows that belong
  to the top k: distinct, possibly live, and scoring at least the k-th best
  certainly-live row (ties within `TIE` count), averaged over the sampled
  queries; its floor is the configuration's target.

Scores are the service's: the inner product for `ip`, and
`2 q.x - |x|^2` (the negated squared distance less |q|^2) for `l2`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SLACK_S = 1e-4          # time stamps closer than this count as unordered
TIE = 1e-5              # relative score tolerance of the k-th score in recall


@dataclass
class Timeline:
    """Per id (row index) the times, relative to the run's origin, at
    which its insert was submitted / acknowledged and its delete was
    submitted / acknowledged; inf where it never happened."""
    ins_sub: np.ndarray
    ins_ack: np.ndarray
    del_sub: np.ndarray
    del_ack: np.ndarray

    @classmethod
    def from_ops(cls, ops, n_build: int, n_all: int, origin: float) -> "Timeline":
        inf = np.full(n_all, np.inf)
        t = cls(inf.copy(), inf.copy(), inf.copy(), inf.copy())
        t.ins_sub[:n_build] = -np.inf
        t.ins_ack[:n_build] = -np.inf
        for op in ops:
            if op.kind not in ("insert", "delete") or op.ids is None:
                continue
            ack = op.done - origin if op.error is None else np.inf
            sub = op.submit - origin
            if op.kind == "insert":
                t.ins_sub[op.ids], t.ins_ack[op.ids] = sub, ack
            else:
                t.del_sub[op.ids], t.del_ack[op.ids] = sub, ack
        return t

    def final_sets(self) -> Tuple[np.ndarray, np.ndarray]:
        """(must be live, must be gone) once every operation has settled."""
        acked = self.ins_ack < np.inf
        live = acked & (self.del_sub == np.inf)
        gone = (self.ins_sub == np.inf) | (self.del_ack < np.inf)
        return live, gone


@dataclass
class Sample:
    """Query rows to check: vectors, submit / complete times (relative to
    the run's origin), and what the timed path returned for each."""
    q: np.ndarray              # f32 [n, d]
    s: np.ndarray              # [n]
    c: np.ndarray              # [n]
    ids: np.ndarray            # [n, k]
    scores: np.ndarray         # [n, k]


def final_state_checks(tl: Timeline, program_ids: np.ndarray) -> Dict[str, int]:
    live, gone = tl.final_sets()
    n_all = len(live)
    ids = np.asarray(program_ids, np.int64)
    unknown = int(np.sum((ids < 0) | (ids >= n_all)))
    known = ids[(ids >= 0) & (ids < n_all)]
    dup = len(known) - len(np.unique(known))
    held = np.zeros(n_all, bool)
    held[known] = True
    return {"lost_rows": int(np.sum(live & ~held)),
            "resurrected_rows": int(np.sum(gone & held)) + unknown + dup}


def _certain(tl: Timeline, s: np.ndarray, c: np.ndarray):
    """Device mask [b, n_all] of the rows certainly live for each query."""
    def f(a):
        return jnp.asarray(np.clip(a, -1e30, 1e30), jnp.float32)
    return ((f(tl.ins_ack)[None] <= f(s)[:, None] - SLACK_S)
            & (f(tl.del_sub)[None] >= f(c)[:, None] + SLACK_S))


def _possible(tl: Timeline, ids: np.ndarray, s: np.ndarray, c: np.ndarray):
    """Host mask [n, k]: whether each returned id was possibly live."""
    return ((tl.ins_sub[ids] <= c[:, None] + SLACK_S)
            & (tl.del_ack[ids] >= s[:, None] - SLACK_S))


def _scores(q, rows, norms2, metric):
    s = jnp.matmul(q, rows.T, precision=jax.lax.Precision.HIGHEST)
    return 2.0 * s - norms2[None] if metric == "l2" else s


def quantize(x, precision: str):
    """Round each row of x to a lower precision, scaled per row, and back:
    `fp8` is float8 e4m3 with the row's largest magnitude at 448; `int8`
    is symmetric int8 with it at 127."""
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30)
    if precision == "fp8":
        scale = amax / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    if precision == "int8":
        scale = amax / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    raise ValueError(f"unknown control precision {precision!r}")


def reference_topk(sample: Sample, rows, tl: Timeline, metric: str, k: int,
                   block: int = 64, precision: Optional[str] = None):
    """Exact top-k over each query's certainly-live rows: (scores [n, k],
    ids [n, k], row norms [n, k]).  With `precision` the operands are first
    rounded to it: that is the control, the reference put in the program's
    place at a precision below the configuration's."""
    norms2 = jnp.sum(rows * rows, axis=1)
    lhs_rows = rows if precision is None else quantize(rows, precision)
    out_s, out_i = [np.zeros((0, k), np.float32)], [np.zeros((0, k), np.int64)]
    for b0 in range(0, len(sample.q), block):
        q = jnp.asarray(sample.q[b0:b0 + block])
        if precision is not None:
            q = quantize(q, precision)
        certain = _certain(tl, sample.s[b0:b0 + block], sample.c[b0:b0 + block])
        sc = jnp.where(certain, _scores(q, lhs_rows, norms2, metric), -jnp.inf)
        top, idx = jax.lax.top_k(sc, k)
        out_s.append(np.asarray(top))
        out_i.append(np.asarray(idx))
    ids = np.concatenate(out_i)
    return np.concatenate(out_s), ids, np.sqrt(np.asarray(norms2)[ids])


def _rounded(x: np.ndarray, dtype: str) -> np.ndarray:
    return np.asarray(jnp.asarray(x, jnp.float32).astype(dtype).astype(jnp.float32),
                      np.float64)


def query_checks(sample: Sample, rows, tl: Timeline, metric: str,
                 ref: Tuple[np.ndarray, np.ndarray, np.ndarray],
                 operand_dtype: str = "float32") -> Dict[str, float]:
    """`wrong_ids`, `score_err` and `recall_at_10` of the sampled answers
    against `ref`, the output of `reference_topk` without a precision;
    `operand_dtype` is the precision the configuration states for the
    score's product."""
    ref_s, _, ref_norm = ref
    n_all = rows.shape[0]
    wrong, err, hits = 0, 0.0, []
    ids = np.asarray(sample.ids, np.int64)
    known = (ids >= 0) & (ids < n_all)
    possible = _possible(tl, np.where(known, ids, 0), sample.s, sample.c)
    got_rows = np.asarray(rows[jnp.asarray(np.where(known, ids, 0))], np.float64)
    q64 = sample.q.astype(np.float64)
    dots = np.einsum("nkd,nd->nk", got_rows, q64)
    n2 = np.sum(got_rows * got_rows, axis=-1)
    exact = 2 * dots - n2 if metric == "l2" else dots
    s_dots = np.einsum("nkd,nd->nk", _rounded(got_rows, operand_dtype),
                       _rounded(q64, operand_dtype))
    stated = 2 * s_dots - n2 if metric == "l2" else s_dots
    qn = np.linalg.norm(q64, axis=1)
    for j in range(len(ids)):
        ok = known[j] & possible[j]
        wrong += int(np.sum((ids[j] >= 0) & ~ok))
        valid_ids = ids[j][ok]
        wrong += len(valid_ids) - len(np.unique(valid_ids))
        have_k = np.isfinite(ref_s[j]).sum()
        wrong += max(0, int(have_k) - int(np.sum(ok)))
        scale = qn[j] * np.sqrt(n2[j])
        if ok.any():
            got = sample.scores[j].astype(np.float64)
            e = (np.minimum(np.abs(got - exact[j]), np.abs(got - stated[j]))
                 / np.maximum(scale, 1e-30))
            err = max(err, float(np.max(e[ok])))
        kth = float(ref_s[j][-1])
        tol = TIE * qn[j] * ref_norm[j][-1]
        _, first = np.unique(np.where(ok, ids[j], -1), return_index=True)
        top = np.zeros(len(ids[j]), bool)
        top[first] = True
        hits.append(float(np.mean(top & ok & (exact[j] >= kth - tol))))
    return {"wrong_ids": wrong, "score_err": err,
            "recall_at_10": float(np.mean(hits)) if hits else 0.0}


@dataclass
class Limit:
    name: str
    value: float
    limit: float
    at_least: bool = False      # the value must reach the limit, not stay under

    @property
    def ok(self) -> bool:
        return self.value >= self.limit if self.at_least else self.value <= self.limit


def limits_for(config: dict, values: Dict[str, float]) -> List[Limit]:
    lim = config["limits"]
    return [Limit("lost_rows", values["lost_rows"], 0),
            Limit("resurrected_rows", values["resurrected_rows"], 0),
            Limit("wrong_ids", values["wrong_ids"], 0),
            Limit("score_err", values["score_err"], float(lim["score_err"])),
            Limit("recall_at_10", values["recall_at_10"], float(lim["recall_at_10"]),
                  at_least=True)]


def sample_queries(ops, origin: float, w0: float, w1: float, n_rows: int,
                   queries: np.ndarray, seed: int) -> Sample:
    """A seeded sample of the query operations due in [w0, w1) that were
    answered, whole operations, until it holds `n_rows` query rows."""
    done = [op for op in ops if op.kind == "query" and w0 <= op.due < w1
            and op.error is None and op.result is not None]
    rng = np.random.default_rng([int(seed) % (2**63), 7])
    picked, rows = [], 0
    for i in rng.permutation(len(done)):
        if rows >= n_rows:
            break
        picked.append(done[i])
        rows += done[i].rows
    qs, s, c, ids, sc = [], [], [], [], []
    for op in picked:
        idx = np.arange(op.qrow, op.qrow + op.rows) % len(queries)
        qs.append(queries[idx])
        s.append(np.full(op.rows, op.submit - origin))
        c.append(np.full(op.rows, op.done - origin))
        ids.append(op.result[0].reshape(op.rows, -1))
        sc.append(op.result[1].reshape(op.rows, -1))
    d = queries.shape[1]
    if not picked:
        return Sample(np.zeros((0, d), np.float32), np.zeros(0), np.zeros(0),
                      np.zeros((0, 0), np.int64), np.zeros((0, 0), np.float32))
    return Sample(np.concatenate(qs), np.concatenate(s), np.concatenate(c),
                  np.concatenate(ids), np.concatenate(sc))

