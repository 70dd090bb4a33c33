"""A new cell or metric needs only new files and a `BENCHMARK.json` entry:
a configuration, a traffic mix and a metric reader loaded from a
temporary directory run through the harness unchanged."""
import json

import tiny
from chipbench import spec


def test_cell_from_new_files_only(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "tiny-topics.json").write_text(json.dumps(tiny.TOPICS))
    mix = dict(tiny.AGENT, streams=[{"op": "query", "arrival": "poisson", "rate_per_s": 30,
                                     "rows": 1}])
    (tmp_path / "traffic" / "recall-only.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "queries_done.extra.py").write_text(
        "def read(run):\n"
        "    return float(len([op for op in run.due_in_window('query')"
        " if op.error is None]))\n")
    (tmp_path / "metrics" / "query_p50_ms.py").write_text(
        (tiny.BENCH / "metrics" / "query_p50_ms.py").read_text())
    bench = {
        "configs": [{"name": "tiny-topics", "source": "test", "reduced": [], "why": "test",
                     "file": "configs/tiny-topics.json"}],
        "workloads": [{"name": "tiny.recall-only", "config": "tiny-topics",
                       "traffic": "recall-only", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "query_p50_ms", "unit": "ms", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "queries_done.extra", "unit": "queries", "better": "higher",
                       "source": "program_counter", "layer": "front door",
                       "moves": "query_p50_ms", "workloads": ["tiny.recall-only"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("tiny.recall-only", spec_path=tmp_path / "BENCHMARK.json",
                          base=tmp_path)
    assert [m.name for m in cell.per_layer] == ["queries_done.extra"]
    e2e = tiny.run(cell, seconds=1.5)
    traced = tiny.run(cell, seconds=1.5, trace=True)
    assert e2e["correct"] and traced["correct"]
    assert e2e["metrics"]["query_p50_ms"]["value"] > 0
    assert traced["metrics"]["queries_done.extra"]["value"] >= 30


def test_the_repo_cells_load():
    bench = json.loads((tiny.BENCH.parents[1] / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m.name == "setup_s" for m in cell.end_to_end)
