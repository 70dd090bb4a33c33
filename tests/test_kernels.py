"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles.

Shape/dtype sweeps + hypothesis property tests, per the deliverable spec.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import live_ids as _live_ids

from repro.configs.base import EngineConfig
from repro.core import index as ivf
from repro.kernels import ops, ref

# hypothesis is a dev-only dep (requirements-dev.txt); the property tests
# below importorskip it so the deterministic sweeps still run without it.

jax.config.update("jax_platform_name", "cpu")


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# scan_scores
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,n,d", [
    (4, 100, 64), (128, 512, 512), (1, 1000, 256), (33, 777, 192),
])
@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_scan_scores_matches_ref(b, n, d, metric):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    q = _rand(k1, (b, d))
    db = _rand(k2, (n, d))
    ids = jnp.arange(n, dtype=jnp.int32)
    norms = jnp.sum(db**2, axis=1) if metric == "l2" else None
    got = ops.scan_scores(q, db, ids, norms, metric=metric,
                          block_m=8, block_n=128, block_k=128)
    want = ref.scan_scores_ref(q, db, ids, norms, metric=metric)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_scan_scores_masks_tombstones():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    q, db = _rand(k1, (8, 128)), _rand(k2, (256, 128))
    ids = jnp.where(jnp.arange(256) % 3 == 0, -1, jnp.arange(256)).astype(jnp.int32)
    got = ops.scan_scores(q, db, ids, block_m=8, block_n=128, block_k=128)
    assert bool(jnp.all(got[:, ::3] == -jnp.inf))
    assert bool(jnp.all(jnp.isfinite(got[:, 1::3])))


def test_scan_scores_unfused_baseline_close():
    """Ablation flag: pre-converted copy path gives the same ranking."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    q, db = _rand(k1, (16, 256)), _rand(k2, (512, 256))
    ids = jnp.arange(512, dtype=jnp.int32)
    fused = ops.scan_scores(q, db, ids, block_m=8, block_n=128, block_k=128)
    unfused = ops.scan_scores(q, db, ids, fused_conversion=False,
                              block_m=8, block_n=128, block_k=128)
    np.testing.assert_allclose(fused, unfused, rtol=3e-2, atol=3e-2)


def test_scan_scores_property():
    """Property: kernel == oracle for arbitrary (unpadded) shapes."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        b=st.integers(1, 40), n=st.integers(1, 600),
        d=st.sampled_from([32, 96, 128, 320]),
        seed=st.integers(0, 2**31 - 1),
    )
    def check(b, n, d, seed):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        q, db = _rand(k1, (b, d)), _rand(k2, (n, d))
        ids = jnp.arange(n, dtype=jnp.int32)
        got = ops.scan_scores(q, db, ids, block_m=8, block_n=128, block_k=128)
        want = ref.scan_scores_ref(q, db, ids)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    check()


# ---------------------------------------------------------------------------
# kmeans_assign
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,c,d", [(64, 8, 64), (500, 128, 256), (1000, 96, 128)])
def test_kmeans_assign_matches_ref(m, c, d):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = _rand(k1, (m, d))
    cent = _rand(k2, (c, d))
    idx, dist = ops.kmeans_assign(x, cent, block_m=8, block_c=128, block_k=128)
    ridx, rdist = ref.kmeans_assign_ref(x, cent)
    # bf16 rounding can flip near-ties; require distance agreement instead of
    # exact index agreement on the tie set.
    np.testing.assert_allclose(dist, rdist, rtol=3e-2, atol=3e-2)
    agree = np.mean(np.asarray(idx) == np.asarray(ridx))
    assert agree > 0.98, f"assignment agreement {agree}"


def test_kmeans_assign_exact_on_separated_clusters():
    """With well-separated clusters the argmin must be exact."""
    key = jax.random.PRNGKey(4)
    c, d, per = 16, 128, 32
    cent = _rand(key, (c, d), scale=20.0)
    x = jnp.repeat(cent, per, axis=0) + _rand(jax.random.PRNGKey(5), (c * per, d), scale=0.05)
    idx, _ = ops.kmeans_assign(x, cent, block_m=8, block_c=128, block_k=128)
    want = jnp.repeat(jnp.arange(c, dtype=jnp.int32), per)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))


def test_kmeans_assign_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(m=st.integers(1, 300), c=st.integers(2, 200),
           seed=st.integers(0, 2**31 - 1))
    def check(m, c, seed):
        d = 64
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        x, cent = _rand(k1, (m, d), scale=5.0), _rand(k2, (c, d), scale=5.0)
        idx, dist = ops.kmeans_assign(x, cent, block_m=8, block_c=128,
                                      block_k=128)
        assert idx.shape == (m,) and dist.shape == (m,)
        assert bool(jnp.all((idx >= 0) & (idx < c)))
        # returned dist must equal the dist of the returned index (self-
        # consistency).  The kernel's fused Data-Adaptation path rounds
        # operands to bf16 before the MXU dot (fp32 accumulate); the oracle
        # must use the same arithmetic, or cancellation in cnorm - 2*dot
        # makes fp32-vs-bf16 diffs blow up.
        cnorm = jnp.sum(cent.astype(jnp.float32) ** 2, axis=1)
        dots = jax.lax.dot_general(
            x.astype(jnp.bfloat16), cent.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        picked = cnorm[idx] - 2 * dots[jnp.arange(m), idx]
        np.testing.assert_allclose(dist, picked, rtol=1e-5, atol=1e-4)

    check()


# ---------------------------------------------------------------------------
# segsum_gemm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,c,d", [(100, 8, 64), (512, 128, 256), (999, 64, 128)])
def test_segsum_matches_ref(m, c, d):
    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    x = _rand(k1, (m, d))
    assign = jax.random.randint(k2, (m,), 0, c).astype(jnp.int32)
    sums, counts = ops.segsum_gemm(x, assign, n_clusters=c,
                                   block_m=8, block_c=128, block_d=128)
    rsums, rcounts = ref.segsum_gemm_ref(x, assign, n_clusters=c)
    np.testing.assert_allclose(counts, rcounts, atol=0)      # counts exact
    np.testing.assert_allclose(sums, rsums, rtol=3e-2, atol=3e-2)


def test_segsum_ignores_negative_assignments():
    x = jnp.ones((64, 128), jnp.float32)
    assign = jnp.where(jnp.arange(64) < 32, 0, -1).astype(jnp.int32)
    sums, counts = ops.segsum_gemm(x, assign, n_clusters=128,
                                   block_m=8, block_c=128, block_d=128)
    assert counts[0] == 32.0
    assert bool(jnp.all(counts[1:] == 0))
    np.testing.assert_allclose(sums[0], 32.0 * jnp.ones(128), rtol=1e-6)


# ---------------------------------------------------------------------------
# IVF index edge cases (probe clamping, delete hit counts, delta replay)
# ---------------------------------------------------------------------------

_IVF_CFG = EngineConfig(dim=128, n_clusters=128, list_capacity=16, nprobe=8,
                        k=4, use_kernel=False, kmeans_iters=2)


def _small_index(n=256, seed=7):
    key = jax.random.PRNGKey(seed)
    x = _rand(key, (n, _IVF_CFG.dim))
    ids = jnp.arange(n, dtype=jnp.int32)
    state, _ = ivf.build(jax.random.PRNGKey(seed + 1), x, ids, _IVF_CFG,
                         spill_capacity=512)
    return state, x, ids


def test_query_probed_clamps_nprobe_to_cluster_count():
    """nprobe > n_clusters must not crash the centroid top_k (k > axis)."""
    state, x, _ = _small_index()
    q = x[:4]
    ids_all, scores_all = ivf.query_probed(state, q, _IVF_CFG, 4,
                                           _IVF_CFG.n_clusters)
    ids_over, scores_over = ivf.query_probed(state, q, _IVF_CFG, 4,
                                             _IVF_CFG.n_clusters + 37)
    np.testing.assert_array_equal(np.asarray(ids_over), np.asarray(ids_all))
    np.testing.assert_allclose(np.asarray(scores_over),
                               np.asarray(scores_all), rtol=1e-6)


def test_delete_returns_actual_hit_count():
    state, _, _ = _small_index()
    # 5 present ids + 3 absent ones: only real tombstones are counted
    req = jnp.asarray([0, 1, 2, 3, 4, 9000, 9001, 9002], jnp.int32)
    new, n = ivf.delete_shared(state, req)
    assert int(n) == 5
    assert int(new.num_deleted) == 5
    # deleting the same ids again tombstones nothing
    _, n2 = ivf.delete_shared(new, req)
    assert int(n2) == 0


def test_replay_reapplies_delta_log_in_order():
    """replay(rebuilt, log) == applying the same ops directly."""
    state, x, _ = _small_index()
    key = jax.random.PRNGKey(11)
    fresh = _rand(key, (24, _IVF_CFG.dim))
    new_ids = jnp.arange(1000, 1024, dtype=jnp.int32)
    log = [
        ivf.DeltaOp("insert", fresh, new_ids),
        ivf.DeltaOp("delete", None, jnp.asarray([0, 1, 1005], jnp.int32)),
        ivf.DeltaOp("insert", fresh[:8] + 0.1,
                    jnp.arange(2000, 2008, dtype=jnp.int32)),
    ]
    rebuilt, _ = ivf.rebuild(jax.random.PRNGKey(12), state, _IVF_CFG)
    replayed, spilled, tombstoned = ivf.replay(rebuilt, log, _IVF_CFG)
    assert spilled >= 0
    assert tombstoned == 3            # 0, 1, and the freshly-inserted 1005
    want = (set(range(256)) | set(range(1000, 1024))
            | set(range(2000, 2008))) - {0, 1, 1005}
    assert _live_ids(replayed) == want
    with pytest.raises(ValueError):
        ivf.replay(replayed, [ivf.DeltaOp("upsert", None, new_ids)], _IVF_CFG)


def test_segsum_property_mass_conservation():
    """Property: total counts == #valid rows; column sums == masked column sums."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(m=st.integers(1, 400), c=st.sampled_from([4, 32, 100, 128]),
           seed=st.integers(0, 2**31 - 1))
    def check(m, c, seed):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        x = _rand(k1, (m, 64))
        assign = jax.random.randint(k2, (m,), -1, c).astype(jnp.int32)
        sums, counts = ops.segsum_gemm(x, assign, n_clusters=c,
                                       block_m=8, block_c=128, block_d=128)
        n_valid = int(jnp.sum(assign >= 0))
        assert int(jnp.sum(counts)) == n_valid
        # oracle in the kernel's arithmetic: the Data-Adaptation path rounds
        # x to bf16 before the one-hot GEMM (fp32 accumulate), so an fp32
        # oracle drifts by ~sqrt(m)*2^-8 and trips any tight tolerance at
        # m~hundreds
        xb = x.astype(jnp.bfloat16).astype(jnp.float32)
        want_total = jnp.sum(jnp.where((assign >= 0)[:, None], xb, 0.0),
                             axis=0)
        np.testing.assert_allclose(jnp.sum(sums, axis=0), want_total,
                                   rtol=1e-4, atol=1e-3)

    check()


# ---------------------------------------------------------------------------
# query_probed: the in-place list scan against the gather reference
# ---------------------------------------------------------------------------

def _width_major(shape, dtype=None):
    """The layout a TPU gives a store whose width is no multiple of 128
    (f32[1024, 2048, 100] is width-major, slots minor); a leading lane
    axis stays major."""
    n = len(shape)
    if n == 4:
        return (0, 3, 1, 2)
    return (n - 1,) + tuple(range(n - 1))


def _unit(key, shape):
    x = jax.random.normal(key, shape)
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def _probed_state(dim, metric, spill):
    """16 lists; with `spill`, lists of 8 slots that 160 rows overflow,
    else lists of 64 slots that 96 rows never fill.  Every 7th id is then
    tombstoned, in the lists and the spill alike."""
    cfg = EngineConfig(dim=dim, n_clusters=16,
                       list_capacity=8 if spill else 64, nprobe=4, k=4,
                       metric=metric, aligned=False, kmeans_iters=2)
    n = 160 if spill else 96
    x = _unit(jax.random.PRNGKey(dim), (n, dim))
    ids = jnp.arange(n, dtype=jnp.int32)
    ref_cfg = dataclasses.replace(cfg, use_kernel=False)
    state, _ = ivf.build(jax.random.PRNGKey(3), x, ids, ref_cfg,
                         spill_capacity=64)
    dead = jnp.arange(0, n, 7, dtype=jnp.int32)
    state, _ = ivf.delete_shared(state, dead)
    return cfg, ref_cfg, state, x, set(np.asarray(dead).tolist())


@pytest.mark.parametrize("spill", [False, True], ids=["no_spill", "spill"])
@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("nprobe", [1, 5, 1000])
@pytest.mark.parametrize("dim", [128, 100], ids=["d128", "d100"])
@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_query_probed_in_place_matches_gather_reference(
        monkeypatch, metric, dim, nprobe, batch, spill):
    """The kernel path answers as the `use_kernel=False` gather path does:
    the same ids in the same order, scores within bf16-operand tolerance;
    tombstones never come back and live spill rows are found.  The
    unaligned width is read through the width-major view a TPU lays it
    out in."""
    if dim % 128:
        monkeypatch.setattr(ops, "layout_order", _width_major)
    cfg, ref_cfg, state, x, dead = _probed_state(dim, metric, spill)
    assert ivf.scans_in_place(cfg) and not ivf.scans_in_place(ref_cfg)
    spill_ids = np.asarray(state.spill_ids)
    live_spill = spill_ids[(spill_ids >= 0)]
    assert (len(live_spill) > 0) == spill
    # a live spill row (when there is one), then rows far apart, so the
    # batch's probe sets differ
    first = int(live_spill[0]) if spill else 1
    q = x[jnp.asarray([first, 40, 90][:batch])]
    got_ids, got_scores = ivf.query_probed(state, q, cfg, 4, nprobe)
    want_ids, want_scores = ivf.query_probed(state, q, ref_cfg, 4, nprobe)
    np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(got_scores),
                               np.asarray(want_scores), rtol=1e-5, atol=1e-5)
    assert not dead & set(np.asarray(got_ids).ravel().tolist())
    if spill:
        assert int(got_ids[0, 0]) == first
    if batch > 1 and nprobe < 16:
        cscores = ref.scan_scores_ref(
            q, state.centroids, jnp.arange(16, dtype=jnp.int32),
            ivf._metric_norms(state.centroids, metric), metric=metric)
        _, probes = jax.lax.top_k(ivf._order_scores(cscores, metric), nprobe)
        assert len({tuple(p) for p in np.asarray(probes).tolist()}) > 1


@pytest.mark.parametrize("shape", [(2, 16, 256, 100), (1, 8, 2048, 768)],
                         ids=["one_block", "blocks"])
@pytest.mark.parametrize("metric", ["ip", "l2"])
@pytest.mark.parametrize("order", [(0, 1, 2), (2, 0, 1)],
                         ids=["rows", "planes"])
def test_scan_lists_reads_each_layout_in_place(order, metric, shape):
    """Both views of the lists (row-major, width-major) score every lane's
    probed lists as scanning their gathered rows does, for lanes with
    different probe tables, whether a list fits one block or is split
    over several (2,048 slots of 768-d)."""
    from repro.kernels import scan_scores as sk
    g, c, l, d = shape
    b = 3
    lists = _rand(jax.random.PRNGKey(5), (g, c, l, d))
    q = _rand(jax.random.PRNGKey(6), (g, b, d))
    probes = jax.random.permutation(
        jax.random.PRNGKey(9), jnp.arange(g * b * 2, dtype=jnp.int32) % c
    ).reshape(g, b, 2)
    got = sk.scan_lists(q, lists, probes, order=order, metric=metric,
                        interpret=True)
    for h in range(g):
        for i in range(b):
            rows = lists[h][probes[h, i]].reshape(-1, d)
            norms = ivf._metric_norms(rows, metric)
            want = ref.scan_scores_ref(q[h, i:i + 1], rows,
                                       jnp.zeros(rows.shape[0], jnp.int32),
                                       norms, metric=metric)
            np.testing.assert_allclose(np.asarray(got[h, i]).ravel(),
                                       np.asarray(want[0]), rtol=1e-5,
                                       atol=1e-4)


@pytest.mark.parametrize("mapped", ["lanes", "queries"])
def test_scan_lists_vmap_maps_onto_the_lane_axis(monkeypatch, mapped):
    """Under vmap the mapped axis becomes the kernel's lane axis, whether
    each element has its own store (fused lanes) or shares one, and the
    answers are those of one call per mapped element."""
    monkeypatch.setattr(ops, "layout_order", _width_major)
    lists = _rand(jax.random.PRNGKey(7), (2, 16, 128, 100))
    q = _rand(jax.random.PRNGKey(8), (2, 3, 100))
    probes = jnp.asarray([[[0, 1], [2, 3], [4, 5]],
                          [[6, 7], [8, 9], [1, 1]]], jnp.int32)

    def scan(qq, ll, pp):
        return ops.scan_lists(qq, ll, pp, metric="l2")

    if mapped == "lanes":
        got = jax.jit(jax.vmap(scan))(q, lists, probes)
        want = [scan(q[h], lists[h], probes[h]) for h in range(2)]
    else:
        got = jax.jit(jax.vmap(scan, in_axes=(0, None, 0)))(q, lists[0],
                                                            probes)
        want = [scan(q[h], lists[0], probes[h]) for h in range(2)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.stack(want)),
                               rtol=1e-6, atol=1e-5)
