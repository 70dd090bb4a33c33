"""Capture a profiler trace of the measured window and reduce it.

The reduction works on plain events (plane, line, name, start, duration in
nanoseconds), so a test can hand it a trace made up by hand:

- the window is the host annotation `WINDOW` the harness opens and closes
  around the measured seconds;
- a device is a plane named `/device:TPU:<n>`; its busy time is the union
  of the intervals of the events on its `XLA Ops` line (its `XLA Modules`
  line where no op line exists), clipped to the window; `busy_s` is the
  mean over the devices;
- each idle gap of a device is named by the host event that overlaps it
  most (`<thread>:<event>`);
- module time sums the `XLA Modules` events by module name, with the
  compiler's `(<id>)` suffix dropped; op time sums `XLA Ops` events by
  `<module>/<op> <shape>`, each op placed in the module that was running it.
"""
from __future__ import annotations

import bisect
import re
import shutil
import tempfile
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import numpy as np

WINDOW = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS, MODULES = "XLA Ops", "XLA Modules"


class Ev(NamedTuple):
    plane: str
    line: str
    name: str
    start: int          # ns
    dur: int            # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Summary:
    window_s: float
    busy_s: float                                  # mean over devices
    devices: int
    modules: Dict[str, Tuple[int, float]]          # name -> (count, seconds)
    ops: Dict[str, float]                          # "<module>/<op>" -> seconds
    gaps: List[Tuple[str, float]]                  # longest idle gaps, named
    inventory: Dict[str, List[str]] = field(default_factory=dict)

    def module(self, prefix: str) -> Tuple[int, float]:
        """(count, seconds) of the modules whose name starts with `prefix`."""
        n = s = 0
        for name, (c, t) in self.modules.items():
            if name.startswith(prefix):
                n, s = n + c, s + t
        return n, s

    def op_seconds(self, module_prefix: str, op_prefix: str) -> float:
        return sum(t for key, t in self.ops.items()
                   if key.split("/", 1)[0].startswith(module_prefix)
                   and key.split("/", 1)[1].startswith(op_prefix))

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def module_base(name: str) -> str:
    return re.sub(r"\(.*$", "", name).strip()


def op_base(name: str) -> str:
    """`scan_scores.1 f32[128,1052672]` from the HLO text the trace names an
    op by (`%scan_scores.1 = f32[128,1052672]{1,0:T(8,128)} custom-call(...)`):
    the instruction's name and the shape it produces, without its layout."""
    lhs, _, rhs = name.partition(" = ")
    shape = "(tuple)" if rhs.startswith("(") else re.sub(r"\{[^{}]*\}", "", rhs.split(" ", 1)[0])
    return (lhs.lstrip("%") + (" " + shape if shape else "")).strip()


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _name_gaps(host: List[Ev], gaps: List[Tuple[int, int]]) -> List[str]:
    """Name each gap by the host event that overlaps it most (the shorter
    one of two that overlap it alike); "idle" where none does."""
    if not host:
        return ["idle"] * len(gaps)
    starts = np.array([e.start for e in host], np.int64)
    ends = np.array([e.end for e in host], np.int64)
    durs = ends - starts
    names = []
    for a, b in gaps:
        ov = np.minimum(ends, b) - np.maximum(starts, a)
        best = int(np.max(ov))
        if best <= 0:
            names.append("idle")
            continue
        cand = np.flatnonzero(ov == best)
        e = host[int(cand[np.argmin(durs[cand])])]
        names.append(f"{e.line}:{e.name}")
    return names


def reduce(events: Iterable[Ev], n_gaps: int = 10) -> Summary:
    events = list(events)
    win = [e for e in events if e.name == WINDOW and not DEVICE_PLANE.match(e.plane)]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    w0, w1 = win[0].start, win[0].end
    host = [e for e in events if not DEVICE_PLANE.match(e.plane)
            and e.name != WINDOW and e.dur > 0 and e.end > w0 and e.start < w1]
    by_plane: Dict[str, Dict[str, List[Ev]]] = defaultdict(lambda: defaultdict(list))
    inventory: Dict[str, set] = defaultdict(set)
    for e in events:
        inventory[e.plane].add(e.line)
        if DEVICE_PLANE.match(e.plane) and e.end > w0 and e.start < w1:
            by_plane[e.plane][e.line].append(e)
    busy_total, gaps = 0.0, []
    modules: Dict[str, List] = defaultdict(lambda: [0, 0.0])
    ops: Dict[str, float] = defaultdict(float)
    for lines in by_plane.values():
        mods = sorted(lines.get(MODULES, []), key=lambda e: e.start)
        busy_src = lines.get(OPS) or mods
        spans = _union([(max(e.start, w0), min(e.end, w1)) for e in busy_src])
        busy_total += sum(b - a for a, b in spans) / 1e9
        edges = [w0] + [x for s in spans for x in s] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
        for e in mods:
            m = modules[module_base(e.name)]
            m[0] += 1
            m[1] += (min(e.end, w1) - max(e.start, w0)) / 1e9
        mstarts = [e.start for e in mods]
        for e in lines.get(OPS, []):
            i = bisect.bisect_right(mstarts, e.start) - 1
            owner = module_base(mods[i].name) if i >= 0 and mods[i].end >= e.start else "?"
            ops[f"{owner}/{op_base(e.name)}"] += (min(e.end, w1) - max(e.start, w0)) / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    top = gaps[:n_gaps]
    named = [(name, (b - a) / 1e9) for name, (a, b) in zip(_name_gaps(host, top), top)]
    n_dev = len(by_plane)
    return Summary(window_s=(w1 - w0) / 1e9,
                   busy_s=busy_total / n_dev if n_dev else 0.0, devices=n_dev,
                   modules={k: (v[0], v[1]) for k, v in modules.items()},
                   ops=dict(ops), gaps=named,
                   inventory={k: sorted(v) for k, v in inventory.items()})


def events_from_file(path: Path) -> List[Ev]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Ev(plane.name, line.name, e.name, int(e.start_ns),
                              int(e.duration_ns)))
    return out


class Tracer:
    """Profiles the process from `start()` to `stop()` into a scratch
    directory, then reduces the trace; `window()` annotates the measured
    seconds."""

    def __init__(self):
        self.dir: Optional[str] = None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    @contextmanager
    def window(self):
        import jax
        with jax.profiler.TraceAnnotation(WINDOW):
            yield

    def stop_and_reduce(self) -> Summary:
        import jax
        jax.profiler.stop_trace()
        try:
            files = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                raise FileNotFoundError("the profiler wrote no trace")
            return reduce(events_from_file(files[-1]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
