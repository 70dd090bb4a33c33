"""Rows and queries of a deployment, made on the device from the seed.

Two shapes of corpus, named by the configuration's `data.kind`:

- `topics`: unit vectors (inner product), one topic centre per
  `rows_per_topic` stored passages; a passage is its centre plus
  `spread` noise, normalised.  Queries are stored passages plus
  `query_noise`, normalised; new passages fall on random topics.
- `clusters`: raw vectors (L2), `rows_per_cluster` rows around each
  centre.  Each cluster's stored rows are of two ages: the older half of
  every cluster comes first in id order, cluster after cluster, then the
  newer halves in the same order.  Oldest-first deletes thus empty the
  older half of one cluster after another and, within the rows a run
  deletes (fewer than half), never a whole cluster.  The insert stream is
  clustered too, visiting the clusters in a seeded order.  Queries are
  fresh points of clusters drawn uniformly from all of them, so their
  nearest rows include rows deleted before and during the window.

The same seed gives the same arrays, so the reference regenerates them after
the program has gone instead of taking anything the program holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> tuple:
    """Two 32-bit words from a seed of any size."""
    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(s[0]), int(s[1])


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_build", "group", "dim", "spread", "n_insert", "n_query", "query_noise"))
def _generate(key, *, kind, n_build, group, dim, spread, n_insert, n_query,
              query_noise):
    k_c, k_b, k_i, k_it, k_q, k_qn = jax.random.split(key, 6)
    n_groups = n_build // group
    centres = jax.random.normal(k_c, (n_groups, dim), jnp.float32)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    if kind == "topics":
        member = jnp.arange(n_build) // group
    else:
        # older halves of all clusters first, then the newer halves
        member = (jnp.arange(n_build) % (n_build // 2)) // (group // 2)
    build = (centres[member]
             + spread * jax.random.normal(k_b, (n_build, dim), jnp.float32))
    noise_i = spread * jax.random.normal(k_i, (n_insert, dim), jnp.float32)
    if kind == "topics":
        build = unit(build)
        inserts = unit(centres[jax.random.randint(k_it, (n_insert,), 0, n_groups)]
                       + noise_i)
        src = build[jax.random.randint(k_q, (n_query,), 0, n_build)]
        queries = unit(src + query_noise
                       * jax.random.normal(k_qn, (n_query, dim), jnp.float32))
    else:
        order = jax.random.permutation(k_it, n_groups)
        inserts = centres[order[(jnp.arange(n_insert) // group) % n_groups]] + noise_i
        queries = (centres[jax.random.randint(k_q, (n_query,), 0, n_groups)]
                   + spread * jax.random.normal(k_qn, (n_query, dim), jnp.float32))
    return build, inserts, queries


class Corpus:
    """The rows (device), the insert pool and the query pool (host) of one
    run.  Row `i` of `build` has id `i`; insert-pool row `j` gets id
    `n_build + j` when it is inserted, in stream order."""

    def __init__(self, config: dict, seed: int, n_insert: int, n_query: int):
        d = config["data"]
        self.kind = d["kind"]
        self.metric = config["engine"]["metric"]
        self.dim = int(config["engine"]["dim"])
        self.n_build = int(config["rows"])
        self.group = int(d["rows_per_topic"] if self.kind == "topics"
                         else d["rows_per_cluster"])
        if self.n_build % self.group or (self.kind == "clusters" and self.group % 2):
            raise ValueError("rows must be a whole number of clusters of an even size")
        self._args = dict(kind=self.kind, n_build=self.n_build, group=self.group,
                          dim=self.dim, spread=float(d["spread"]),
                          n_insert=max(int(n_insert), 1), n_query=max(int(n_query), 1),
                          query_noise=float(d.get("query_noise", 0.0)))
        self._key = jax.random.PRNGKey(seed_words(seed)[0])
        self.build, inserts, queries = _generate(self._key, **self._args)
        self.inserts, self.queries = jax.device_get((inserts, queries))

    def all_rows(self):
        """Device f32[n_build + n_insert, dim]: every row that may ever hold
        an id, in id order, made again from the seed."""
        build, inserts, _ = _generate(self._key, **self._args)
        return jnp.concatenate([build, inserts], axis=0)
