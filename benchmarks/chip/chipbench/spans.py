"""Put the device's idle time in a traced window down to the request phases
the program was in, from its own `ame.` spans.

The program opens a span at each layer boundary (`repro.core.tracing`), on
the profiler's clock, with the request id (`req`) and phase arguments as
the event's stats.  The reduction works on plain values, so a test can hand
it a trace made up by hand:

- a request is in flight from its submission to the end of its task:
  `[ame.task start - queued_us, ame.task end]`;
- each stretch of device idle time is named by the active span that started
  last, as `<span>:<kind>` with the kind of the span's request; where no
  span is active but a task waits in the scheduler's queue it is
  `ame.queued:<kind>`, and where neither, `no request in flight`;
- `idle_by_span` sums the idle seconds under each name, so it sums to the
  idle time; `inflight_idle_s` is the idle time inside the union of
  in-flight intervals; both are means over devices, like `busy_s`;
- `spans` gives each span name's count, total and self seconds (self: less
  the spans nested in it on its thread), clipped to the window;
- `device_wait_ms` gives per request kind the mean `ame.device` span: the
  device time of the op's program plus its wait behind other device work,
  as the service sees it.

A trace with no `ame.` span (a program without them) attributes nothing:
`attribute` returns None and `SpanTracer` reduces as `trace.Tracer` does.
"""
from __future__ import annotations

import heapq
import shutil
import warnings
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Tuple

from chipbench import trace
from chipbench.trace import DEVICE_PLANE, MODULES, OPS, WINDOW, Ev

PREFIX = "ame."
NO_REQUEST = "no request in flight"


class Span(NamedTuple):
    thread: str             # one host line of the trace
    name: str
    start: int              # ns
    dur: int                # ns
    args: dict

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Attribution:
    window_s: float
    idle_s: float                                   # mean over devices
    inflight_idle_s: float
    idle_by_span: Dict[str, float]
    gaps: List[Tuple[str, float]]                   # longest idle gaps, named
    spans: Dict[str, Tuple[int, float, float]]      # name -> (count, total, self)
    device_wait_ms: Dict[str, float]                # request kind -> mean ms


def read(path: Path) -> Tuple[List[Ev], List[Span]]:
    """The trace's events, as `trace.events_from_file` gives them, and its
    `ame.` spans with their arguments."""
    from jax.profiler import ProfileData
    events, spans = [], []
    with warnings.catch_warnings():
        # the reader's stats type warns as it is built, for every event
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    ev = Ev(plane.name, line.name, e.name, int(e.start_ns),
                            int(e.duration_ns))
                    events.append(ev)
                    if ev.name.startswith(PREFIX):
                        spans.append(Span(f"{plane.name}:{line.name}#{i}", ev.name,
                                          ev.start, ev.dur, dict(e.stats)))
    return events, spans


def _device_gaps(events: List[Ev], w0: int, w1: int) -> Dict[str, List[Tuple[int, int]]]:
    """Each device's idle intervals in the window, as `trace.reduce` finds them."""
    lines: Dict[str, Dict[str, list]] = defaultdict(lambda: defaultdict(list))
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.end > w0 and e.start < w1:
            lines[e.plane][e.line].append((max(e.start, w0), min(e.end, w1)))
    out = {}
    for plane, by_line in lines.items():
        busy = trace._union(by_line.get(OPS) or by_line.get(MODULES, []))
        edges = [w0] + [x for s in busy for x in s] + [w1]
        out[plane] = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return out


def _overlap(xs: List[Tuple[int, int]], ys: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = total = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _label(s: Span, kinds: Dict[int, str]) -> str:
    kind = s.args.get("kind") or kinds.get(s.args.get("req"))
    return f"{s.name}:{kind}" if kind else s.name


def _name_idle(gaps: List[Tuple[int, int]], cands: List[Tuple[int, int, int, str]]):
    """Per gap, idle ns by name: at each instant the active candidate
    (start, end, rank, label) of highest rank, then latest start, names it."""
    cands = sorted(cands)
    heap: list = []
    i, out = 0, []
    for a, b in gaps:
        named: Dict[str, int] = defaultdict(int)
        t = a
        while t < b:
            while i < len(cands) and cands[i][0] <= t:
                s, e, rank, label = cands[i]
                heapq.heappush(heap, (-rank, -s, e, label))
                i += 1
            while heap and heap[0][2] <= t:
                heapq.heappop(heap)
            nxt = min(b, cands[i][0] if i < len(cands) else b)
            if heap:
                nxt = min(nxt, heap[0][2])
            named[heap[0][3] if heap else NO_REQUEST] += nxt - t
            t = nxt
        out.append(named)
    return out


def _self_times(spans: List[Span], w0: int, w1: int) -> Dict[str, Tuple[int, float, float]]:
    agg: Dict[str, List] = defaultdict(lambda: [0, 0, 0])
    by_thread: Dict[str, List[Span]] = defaultdict(list)
    for s in spans:
        if s.end > w0 and s.start < w1:
            by_thread[s.thread].append(s)

    def clipped(s: Span) -> int:
        return min(s.end, w1) - max(s.start, w0)

    for ss in by_thread.values():
        stack: List[Span] = []
        for s in sorted(ss, key=lambda s: (s.start, -s.dur)):
            while stack and stack[-1].end <= s.start:
                stack.pop()
            a = agg[s.name]
            a[0] += 1
            a[1] += clipped(s)
            a[2] += clipped(s)
            if stack:
                agg[stack[-1].name][2] -= clipped(s)
            stack.append(s)
    return {k: (n, tot / 1e9, own / 1e9) for k, (n, tot, own) in agg.items()}


def attribute(events: List[Ev], spans: List[Span], n_gaps: int = 10) -> Optional[Attribution]:
    if not spans:
        return None
    win = [e for e in events if e.name == WINDOW and not DEVICE_PLANE.match(e.plane)]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    w0, w1 = win[0].start, win[0].end
    tasks = [s for s in spans if s.name == "ame.task"]
    kinds = {s.args.get("req"): s.args.get("kind") for s in tasks}
    kinds.update({s.args.get("req"): s.args.get("kind") for s in spans
                  if s.name == "ame.submit" and s.args.get("req") not in kinds})
    queued = [(s.start - int(1e3 * s.args.get("queued_us", 0.0)), s.start) for s in tasks]
    inflight = trace._union([(max(a, w0), min(s.end, w1)) for (a, _), s in zip(queued, tasks)
                             if s.end > w0 and a < w1])
    cands = [(s.start, s.end, 1, _label(s, kinds)) for s in spans]
    cands += [(a, b, 0, f"ame.queued:{s.args.get('kind')}")
              for (a, b), s in zip(queued, tasks) if b > a]
    gaps = _device_gaps(events, w0, w1)
    n_dev = max(len(gaps), 1)
    idle = inflight_idle = 0
    by_span: Dict[str, float] = defaultdict(float)
    named_gaps = []
    for dev_gaps in gaps.values():
        idle += sum(b - a for a, b in dev_gaps)
        inflight_idle += _overlap(dev_gaps, inflight)
        for (a, b), named in zip(dev_gaps, _name_idle(dev_gaps, cands)):
            for label, ns in named.items():
                by_span[label] += ns / 1e9 / n_dev
            named_gaps.append((max(named, key=named.get), (b - a) / 1e9))
    named_gaps.sort(key=lambda g: -g[1])
    waits: Dict[str, List[int]] = defaultdict(list)
    for s in spans:
        if s.name == "ame.device" and w0 <= s.start < w1:
            waits[kinds.get(s.args.get("req"), "?")].append(s.dur)
    return Attribution(
        window_s=(w1 - w0) / 1e9, idle_s=idle / 1e9 / n_dev,
        inflight_idle_s=inflight_idle / 1e9 / n_dev,
        idle_by_span=dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        gaps=named_gaps[:n_gaps], spans=_self_times(spans, w0, w1),
        device_wait_ms={k: sum(v) / len(v) / 1e6 for k, v in waits.items()})


class SpanTracer(trace.Tracer):
    """A `trace.Tracer` that also attributes the window's idle time to the
    program's spans (`attribution`, None where it has none) before the
    trace is deleted; the `Summary` it returns is `trace.reduce`'s."""

    attribution: Optional[Attribution] = None

    def stop_and_reduce(self) -> trace.Summary:
        import jax
        jax.profiler.stop_trace()
        try:
            files = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                raise FileNotFoundError("the profiler wrote no trace")
            events, spans = read(files[-1])
            self.attribution = attribute(events, spans)
            return trace.reduce(events)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
