"""The chip: refuse anything but a TPU, look up its peaks, read its memory.

Peaks come from `peaks.json` beside this package, keyed by the
`device_kind` JAX reports; a device that is not in the table is an error.
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import List

from chipbench.spec import BENCH_DIR, REPO_ROOT

# Inside the checkout, at a fixed path: the path is part of the cache's key.
CACHE_DIR = REPO_ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache into the checkout, for this
    process and for the program's own `compile_cache.enable()`; cache every
    program, however fast it compiled, so a warm run compiles nothing."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CACHE_DIR)


def require_tpu(chips: int) -> List:
    """The first `chips` TPU devices; raises `NoChip` otherwise."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    if not devices or devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {devices[0].platform if devices else 'none'}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX reports {len(devices)}")
    return devices[:chips]


def peaks(device_kind: str, path: Path = BENCH_DIR / "peaks.json") -> dict:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]


def describe(devices) -> dict:
    """The result line's `device`: as JAX reports it, with the peak bytes in
    use on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
