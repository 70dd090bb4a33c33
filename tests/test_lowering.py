"""Lowering / sharding-spec regression tests.

The real 512-device dry-run runs in ``launch/dryrun.py`` (it must own jax
device-count init).  These tests exercise the SAME lowering machinery —
param/cache/batch shardings, train/prefill/decode step construction — on a
1x1 mesh with reduced configs, so a broken PartitionSpec rule or cache spec
fails in CI, not at sweep time.  Plus fault-tolerance unit coverage.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import ShapeConfig
from repro.launch import dryrun
from repro.models import lm, specs

TINY_SHAPES = {
    "train": ShapeConfig("tiny_train", "train", 64, 4),
    "prefill": ShapeConfig("tiny_prefill", "prefill", 64, 2),
    "decode": ShapeConfig("tiny_decode", "decode", 64, 4),
}

ARCHS = ["granite-3-2b", "olmoe-1b-7b", "gemma2-9b", "zamba2-2.7b",
         "rwkv6-1.6b", "seamless-m4t-large-v2", "qwen2-vl-7b"]


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("kind", ["train", "decode"])
def test_lower_cell_reduced(arch, kind):
    cfg = registry.reduced_arch(arch)
    shape = TINY_SHAPES[kind]
    mesh = _mesh()
    lowered = dryrun.lower_cell(cfg, shape, mesh)
    hlo = lowered.as_text()
    assert len(hlo) > 100


def test_param_specs_cover_every_leaf():
    """Every param leaf gets a valid PartitionSpec (divisibility-sane)."""
    for arch in ARCHS:
        cfg = registry.reduced_arch(arch)
        mesh = _mesh()
        sp = specs.param_specs(cfg, mesh)
        shapes = jax.eval_shape(
            lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
        n_spec = len(jax.tree.leaves(sp))
        n_par = len(jax.tree.leaves(shapes))
        assert n_spec == n_par, (arch, n_spec, n_par)


def test_cache_specs_match_cache_tree():
    for arch in ("granite-3-2b", "zamba2-2.7b", "rwkv6-1.6b"):
        cfg = registry.reduced_arch(arch)
        mesh = _mesh()
        caches = jax.eval_shape(
            lambda: lm.init_caches(cfg, 4, 64, jnp.dtype(cfg.dtype)))
        cs = specs.cache_specs(cfg, mesh, caches)
        assert (len(jax.tree.leaves(cs))
                == len(jax.tree.leaves(caches))), arch


def test_input_specs_no_allocation():
    """input_specs returns ShapeDtypeStructs only (never allocates)."""
    cfg = registry.reduced_arch("granite-3-2b")
    for shape in TINY_SHAPES.values():
        si = dryrun.input_specs(cfg, shape)
        for leaf in jax.tree.leaves(si):
            assert isinstance(leaf, jax.ShapeDtypeStruct), type(leaf)


# ---------------------------------------------------------------------------
# fault tolerance / elastic units
# ---------------------------------------------------------------------------

def test_elastic_best_grid():
    from repro.distributed.elastic import best_grid
    assert best_grid(256) == (16, 16)
    assert best_grid(512) == (32, 16)
    assert best_grid(24) == (3, 8)          # lost a host: 24 devices
    assert best_grid(7) == (7, 1)           # prime fallback
    d, m = best_grid(48)
    assert d * m == 48


def test_straggler_monitor_flags_outlier(monkeypatch):
    import types
    from repro.distributed import fault
    from repro.distributed.fault import StragglerMonitor
    # a step clock the test advances, so a loaded host's sleep jitter
    # cannot make an ordinary step look like a straggler
    now = [0.0]
    monkeypatch.setattr(fault, "time",
                        types.SimpleNamespace(perf_counter=lambda: now[0]))

    def step(seconds):
        mon.start()
        now[0] += seconds
        return mon.stop()

    mon = StragglerMonitor(threshold=2.0)
    for _ in range(10):
        out = step(0.002)
        assert not out["straggler"]
    out = step(0.05)
    assert out["straggler"]
    assert mon.flagged == 1


def test_preemption_guard_requests_checkpoint():
    from repro.distributed.fault import PreemptionGuard
    g = PreemptionGuard(install=False)
    assert not g.should_checkpoint
    g.request()
    assert g.should_checkpoint
    g.reset()
    assert not g.should_checkpoint
