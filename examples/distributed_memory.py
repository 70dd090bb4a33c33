"""Beyond-paper: the agentic memory sharded over a device mesh.

    PYTHONPATH=src python examples/distributed_memory.py

The paper's engine is single-device.  This example runs the distributed
tier through the same multi-tenant API as the on-device one: a collection
created with `shard_db=True` and a mesh shards its IVF lists row-wise over
every device the process sees (on a CPU, ask for several with
`XLA_FLAGS=--xla_force_host_platform_device_count=8`), each shard scans
locally with the fused-GEMM path,
and candidates merge into a global top-k — a billion-vector memory behind
the same `MemoryService` calls.  Includes distributed insert routing,
cross-collection fused batched queries over sharded tenants (one shard_map
dispatch for G tenants), shard-local deletes + rebuild (one shard
compacted, siblings untouched — see docs/ARCHITECTURE.md), and sharded
save/load.
"""
import jax
import numpy as np

from repro.api import MemoryService
from repro.configs.base import EngineConfig
from repro.core import metrics


def main():
    mesh = jax.make_mesh((jax.device_count(),), ("shard",))
    cfg = EngineConfig(dim=128, n_clusters=128, list_capacity=64,
                       nprobe=16, k=5, use_kernel=False, kmeans_iters=4,
                       shard_db=True)
    rng = np.random.default_rng(0)
    n = 16_384
    x = rng.standard_normal((n, cfg.dim), dtype=np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    ids = np.arange(n, dtype=np.int32)

    svc = MemoryService()
    svc.create_collection("planet", cfg, mesh=mesh)
    svc.build("planet", x, ids=ids)
    print(f"distributed build ok: lists sharded over "
          f"{mesh.devices.size} devices "
          f"(per-device rows ~ {cfg.capacity // mesh.devices.size})")

    q = x[:8] + 0.02 * rng.standard_normal((8, cfg.dim), dtype=np.float32)
    got_ids, scores = svc.query("planet", q, k=5)
    true = metrics.brute_force_topk(q, x, ids, 5)
    rec = metrics.recall_at_k(np.asarray(got_ids), true)
    print(f"distributed query recall@5 = {rec:.3f}")

    new = rng.standard_normal((256, cfg.dim), dtype=np.float32)
    spilled = svc.insert("planet", new,
                         ids=np.arange(n, n + 256, dtype=np.int32))
    print(f"distributed insert: 256 rows routed to shards "
          f"({spilled} spilled)")
    got_ids2, _ = svc.query("planet", new[:4], k=1)
    hit = np.mean(np.asarray(got_ids2)[:, 0] >= n)
    print(f"fresh inserts retrievable: {hit:.0%} of probes "
          f"return a new id at rank 1")

    # shard-local maintenance: tombstone rows, compact ONE shard at a time
    n_hit = svc.delete("planet", np.arange(512))
    coll = svc.collection("planet")
    hot = int(np.argmax([s["tombstones"]
                         for s in coll.maintenance_pressure()["shards"]]))
    v_before = coll.shard_versions()
    out = svc.rebuild("planet", shard=hot)
    v_after = coll.shard_versions()
    untouched = sum(a == b for a, b in zip(v_before, v_after))
    print(f"deleted {n_hit} rows; shard-local rebuild of shard {hot} "
          f"reclaimed its tombstones in {out['rebuild_s']:.2f}s "
          f"({untouched}/{len(v_after)} sibling shards untouched)")

    # cross-collection fused queries work for sharded tenants too: G
    # same-mesh tenants batched in one window cost ONE shard_map dispatch
    # (each device stacks its G shard-local blocks lane-wise), bitwise-
    # equal to querying each tenant on its own
    svc.create_collection("moon", cfg, mesh=mesh)
    svc.build("moon", rng.standard_normal((4_096, cfg.dim),
                                          dtype=np.float32))
    (planet_r, moon_r) = svc.query_many([("planet", q), ("moon", q)], k=5)
    solo_ids, solo_scores = svc.query("planet", q, k=5)
    assert np.array_equal(planet_r[0], solo_ids)
    assert np.array_equal(planet_r[1], solo_scores)
    print("fused 2-tenant sharded window == per-tenant dist_query "
          "(one dispatch, bitwise-equal results)")
    svc.drop_collection("moon")

    # sharded persistence: one checkpoint namespace per shard
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        svc.save(d)
        restored = MemoryService.load(d, mesh=mesh, maintenance=False)
        st = restored.collection("planet").stats()
        print(f"sharded save/load round-trip: {st['live']} live rows on "
              f"{st['shards']} shards")
        restored.shutdown()
    svc.shutdown()


if __name__ == "__main__":
    main()
