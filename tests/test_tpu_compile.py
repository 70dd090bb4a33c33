"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached, so each test
compiles one `kernels/ops.py` wrapper at deployment width (dim 768, 262,144
rows, 1,024 centroids) for a described v5e chip, with the kernel compiled,
not interpreted, and checks the Mosaic kernel is in the program.  A tiling
or VMEM refusal then fails here instead of on the chip.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import EngineConfig
from repro.core import index as ivf
from repro.kernels import ops

DIM, ROWS, CENTROIDS, BATCH = 768, 262_144, 1_024, 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch, no_persistent_cache):
    # the wrappers ask the default backend, which is the CPU here; steer
    # them to the chip's path, as a TPU process would take it
    monkeypatch.setattr(ops, "interpret_kernels", lambda: False)


@pytest.fixture
def chip_layouts(monkeypatch, one_chip):
    # the in-place scans read each operand through the layout the default
    # device gives it; ask the described chip instead, as a TPU process
    # would ask its own
    from jax.experimental.layout import Layout
    dev = next(iter(one_chip.device_set))

    def layout_order(shape, dtype=jnp.float32):
        return tuple(Layout.from_pjrt_layout(dev.client.get_default_layout(
            np.dtype(dtype), tuple(shape), dev)).major_to_minor)

    monkeypatch.setattr(ops, "layout_order", layout_order)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_scan_scores_compiles_for_v5e(one_chip, compiled_kernels, metric):
    norms = _spec(one_chip, (ROWS,)) if metric == "l2" else None
    _assert_kernel(ops.scan_scores.lower(
        _spec(one_chip, (BATCH, DIM)), _spec(one_chip, (ROWS, DIM)),
        _spec(one_chip, (ROWS,), jnp.int32), norms, metric=metric))


@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_scan_scores_q8_compiles_for_v5e(one_chip, compiled_kernels, metric):
    norms = _spec(one_chip, (ROWS,)) if metric == "l2" else None
    _assert_kernel(ops.scan_scores_q8.lower(
        _spec(one_chip, (BATCH, DIM)), _spec(one_chip, (ROWS, DIM), jnp.int8),
        _spec(one_chip, (ROWS,), jnp.int32), _spec(one_chip, (ROWS,)),
        _spec(one_chip, (ROWS,)), norms, metric=metric))


def test_kmeans_assign_compiles_for_v5e(one_chip, compiled_kernels):
    _assert_kernel(ops.kmeans_assign.lower(
        _spec(one_chip, (ROWS, DIM)), _spec(one_chip, (CENTROIDS, DIM))))


def test_segsum_gemm_compiles_for_v5e(one_chip, compiled_kernels):
    _assert_kernel(ops.segsum_gemm.lower(
        _spec(one_chip, (ROWS, DIM)), _spec(one_chip, (ROWS,), jnp.int32),
        n_clusters=CENTROIDS))


@pytest.mark.parametrize("dim,slots,metric,spill", [
    (768, 1024, "ip", 4096), (100, 2048, "l2", 16384),
    (1024, 592, "ip", 4096), (1024, 1464, "ip", 4096)],
    ids=["hotpotqa768", "msturing100", "paper100k", "paper1m"])
def test_query_probed_reads_the_store_in_place_on_v5e(
        one_chip, compiled_kernels, chip_layouts, dim, slots, metric, spill):
    """At the benchmark deployments' sizes, and at the list capacities of
    `configs/ame_paper.py` (no multiple of 128; 1,464 slots of 1024-d fill
    no 4 MiB block), a B=1 probed query compiles with the store passed to
    the list-scan kernel as it lies: no op of the program makes an f32
    array as large as the 32 probed lists (a copy or relayout of the
    store, a gather or padded copy of the probed rows)."""
    cfg = EngineConfig(dim=dim, n_clusters=CENTROIDS, list_capacity=slots,
                       nprobe=32, k=10, metric=metric, aligned=False)
    c, l, d = CENTROIDS, slots, dim
    state = ivf.IVFState(
        _spec(one_chip, (c, d)), _spec(one_chip, (c, l, d)),
        _spec(one_chip, (c, l), jnp.int32), _spec(one_chip, (c,), jnp.int32),
        _spec(one_chip, (spill, d)), _spec(one_chip, (spill,), jnp.int32),
        _spec(one_chip, (), jnp.int32), _spec(one_chip, (), jnp.int32))
    hlo = ivf.query_probed.lower(state, _spec(one_chip, (1, d)), cfg, 10,
                                 32).compile().as_text()
    assert "tpu_custom_call" in hlo
    probed = 32 * l * d
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = f32\[([\d,]+)\]\S* ([\w-]+)\(",
                     line)
        if m and np.prod([int(n) for n in m.group(1).split(",")]) >= probed:
            assert m.group(2) in ("parameter", "bitcast"), line[:160]
