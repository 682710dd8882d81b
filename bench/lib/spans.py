"""Host spans of a profiler trace as per-thread trees: each span's self
time, and the device's idle gaps put under the innermost span covering
them.

The spans are the harness's (``bench.``) and the program's (``mv4pg.``,
written by ``repro.utils.trace.span``), each on the host thread (trace
line) that wrote it.  Spans of one thread nest, so one stack pass over
them sorted by start finds every span's parent, however many siblings a
step holds.  :func:`split` reduces them over the ``bench.window`` span
against the device operations that :mod:`bench.lib.trace` reads.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from bench.lib import trace as trace_mod

PREFIXES = ("bench.", "mv4pg.")


@dataclass(frozen=True)
class Span:
    name: str
    thread: int        # host trace line that wrote it
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Split:
    gaps: Dict[str, float]         # innermost span -> idle device seconds
    span_self_s: Dict[str, float]  # span -> window seconds not in a child


def spans_from_xplane(path: str) -> List[Span]:
    """The harness and program spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    thread = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append(Span(e.name, thread, float(e.start_ns),
                                    float(e.duration_ns)))
    return out


def spans_from_dir(trace_dir: str) -> List[Span]:
    """The spans of the trace that ``jax.profiler`` wrote last under
    ``trace_dir`` (as :func:`bench.lib.trace.events_from_dir`)."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return spans_from_xplane(paths[-1]) if paths else []


def span_trees(spans: List[Span]) -> Tuple[List[Span], List[int]]:
    """Spans sorted by thread and start (a parent before a child that starts
    with it), and each one's parent index (-1 for a root)."""
    order = sorted(spans, key=lambda s: (s.thread, s.start_ns, -s.dur_ns))
    parent = [-1] * len(order)
    stack: List[int] = []
    for i, s in enumerate(order):
        while stack and (order[stack[-1]].thread != s.thread
                         or order[stack[-1]].end_ns <= s.start_ns):
            stack.pop()
        if stack:
            parent[i] = stack[-1]
        stack.append(i)
    return order, parent


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def self_times(order: List[Span], parent: List[int], w0: float,
               w1: float) -> Dict[str, float]:
    """Per span name, seconds inside ``[w0, w1]`` less what its child spans
    cover there."""
    own = [_overlap(s.start_ns, s.end_ns, w0, w1) for s in order]
    for s, p in zip(order, parent):
        if p >= 0:
            own[p] -= _overlap(s.start_ns, s.end_ns, w0, w1)
    out: Dict[str, float] = {}
    for s, t in zip(order, own):
        if t > 0:
            out[s.name] = out.get(s.name, 0.0) + t / 1e9
    return out


def innermost(order: List[Span], points: List[float]
              ) -> List[Optional[Span]]:
    """For each point (ascending), the innermost span covering it: per
    thread, a sweep with the stack of open spans; across threads, the
    shortest of those."""
    best: List[Optional[Span]] = [None] * len(points)
    for th in sorted({s.thread for s in order}):
        spans = [s for s in order if s.thread == th]
        stack: List[Span] = []
        j = 0
        for k, x in enumerate(points):
            while j < len(spans) and spans[j].start_ns <= x:
                s = spans[j]
                j += 1
                while stack and stack[-1].end_ns <= s.start_ns:
                    stack.pop()
                stack.append(s)
            while stack and stack[-1].end_ns < x:
                stack.pop()
            if stack and (best[k] is None or stack[-1].dur_ns < best[k].dur_ns):
                best[k] = stack[-1]
    return best


def split(events: List[trace_mod.Event], spans: List[Span]
          ) -> Optional[Split]:
    """The idle gaps of the first device inside the ``bench.window`` span,
    each under the innermost span covering its midpoint (``host.other``
    where none does), and every span's self time in the window.  None
    when there is no window span or no device operation."""
    windows = [s for s in spans if s.name == trace_mod.WINDOW_SPAN]
    ops = [e for e in events if e.kind == "op"]
    if not windows or not ops:
        return None
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    dev = min(e.device for e in ops)
    busy = trace_mod._union([(max(e.start_ns, w0), min(e.end_ns, w1))
                             for e in ops if e.device == dev
                             and e.end_ns > w0 and e.start_ns < w1])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    order, parent = span_trees([s for s in spans
                                if s.name != trace_mod.WINDOW_SPAN])
    gaps: Dict[str, float] = {}
    for (a, b), s in zip(idle, innermost(order, [(a + b) / 2
                                                  for a, b in idle])):
        name = s.name if s is not None else "host.other"
        gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9
    return Split(gaps, self_times(order, parent, w0, w1))
