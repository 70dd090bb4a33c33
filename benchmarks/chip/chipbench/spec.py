"""Find a cell, its configuration, its traffic mix and its metric readers by
name, from `BENCHMARK.json` and the files beside this package."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_DIR.parents[1]


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    read: Callable        # read(run) -> float | None


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as loaded
    traffic: dict         # the traffic file, as loaded
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_reader(path: Path) -> Callable:
    """Load `read` from a metric's reader file (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no metric reader at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(entries: List[dict], cell: str, base: Path) -> List[Metric]:
    out = []
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out.append(Metric(m["name"], m["unit"], m["better"], m["source"],
                          load_reader(base / "metrics" / f"{m['name']}.py")))
    return out


def load_cell(name: str, spec_path: Path = REPO_ROOT / "BENCHMARK.json",
              base: Path = BENCH_DIR) -> Cell:
    """The cell `name` of the benchmark file at `spec_path`.

    Configuration files are found by the `file` the benchmark gives (a path
    from the directory that holds the benchmark file); traffic mixes and
    metric readers by their names under `base`."""
    spec = json.loads(Path(spec_path).read_text())
    cells: Dict[str, dict] = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((Path(spec_path).parent / configs[w["config"]]["file"])
                        .read_text())
    traffic = json.loads((base / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, int(w["chips"]), config, traffic,
                _metrics(spec["end_to_end"], name, base),
                _metrics(spec["per_layer"], name, base))
