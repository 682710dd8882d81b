"""From a profiler trace to device busy time, per-program device time and
idle gaps attributed to the harness's own host spans.

A trace is first flattened to :class:`Event` rows, so the reduction runs the
same on a trace written by ``jax.profiler`` and on a small recorded one kept
with the tests.  Device operations are the events of the ``XLA Ops`` line
of each ``/device:TPU:<n>`` plane; each carries the name of the compiled
module it ran in (``jit__program``, ``jit__hop_segment``, ...).  Host spans
are the events the harness wrote with ``TraceAnnotation`` (names starting
with ``bench.``).
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass(frozen=True)
class Event:
    kind: str          # "op" | "module" | "span"
    device: int        # device index; -1 for host spans
    name: str
    module: str        # compiled module of an op ("" if unknown)
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _module_name(raw: str) -> str:
    return re.sub(r"\(\d+\)$", "", raw.strip())


def events_from_xplane(path: str) -> List[Event]:
    """Flatten one ``.xplane.pb`` to events (device ops, modules, spans)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                kind = "op" if line.name == OPS_LINE else "module"
                for e in line.events:
                    mod = ""
                    for s in e.stats:
                        if s[0] == "hlo_module":
                            mod = _module_name(str(s[1]))
                    name = e.name
                    if kind == "module":
                        mod = _module_name(name)
                    else:   # "%fusion.3 = s32[...] fusion(...)" -> "fusion.3"
                        name = name.split(" = ")[0].lstrip("%").strip()
                    out.append(Event(kind, dev, name, mod,
                                     float(e.start_ns), float(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.append(Event("span", -1, e.name, "",
                                         float(e.start_ns),
                                         float(e.duration_ns)))
    return out


def events_from_dir(trace_dir: str) -> List[Event]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return []
    return events_from_xplane(paths[-1])


def save_events(events: List[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([[e.kind, e.device, e.name, e.module, e.start_ns, e.dur_ns]
                   for e in events], f)


def load_events(path: str) -> List[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float                         # mean over devices
    devices: int
    op_s: Dict[str, float]                # "module/op" -> device seconds
    module_s: Dict[str, float]            # module -> device seconds
    gaps: Dict[str, float]                # host span -> idle device seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def prefix_s(self, prefix: str) -> Optional[float]:
        """Device seconds of ops in modules whose name starts with
        ``prefix``, per device; None when no such module ran."""
        hit = [v for k, v in self.module_s.items() if k.startswith(prefix)]
        return sum(hit) / self.devices if hit else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(events: List[Event]) -> Optional[Summary]:
    """Reduce a flattened trace over its ``bench.window`` span.  Returns
    None when the trace holds no window span or no device operation."""
    windows = [e for e in events if e.kind == "span" and e.name == WINDOW_SPAN]
    ops = [e for e in events if e.kind == "op"]
    if not windows or not ops:
        return None
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    devices = sorted({e.device for e in ops})
    modules = sorted((e.start_ns, e.end_ns, e.module, e.device)
                     for e in events if e.kind == "module")
    mod_starts = [m[0] for m in modules]

    def module_of(e: Event) -> str:
        if e.module:
            return e.module
        i = bisect.bisect_right(mod_starts, e.start_ns) - 1
        while i >= 0 and modules[i][3] != e.device:
            i -= 1
        if i >= 0 and modules[i][1] >= e.start_ns:
            return modules[i][2]
        return "?"

    busy: Dict[int, List[Tuple[float, float]]] = {d: [] for d in devices}
    op_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    for e in ops:
        a, b = max(e.start_ns, w0), min(e.end_ns, w1)
        if b <= a:
            continue
        busy[e.device].append((a, b))
        mod = module_of(e)
        key = f"{mod}/{e.name}"
        op_s[key] = op_s.get(key, 0.0) + (b - a) / 1e9
        module_s[mod] = module_s.get(mod, 0.0) + (b - a) / 1e9
    unions = {d: _union(iv) for d, iv in busy.items()}
    busy_s = sum(sum(b - a for a, b in u) for u in unions.values()) \
        / len(devices) / 1e9

    # idle gaps of the first device, each put under the innermost harness
    # span that covers its midpoint
    # (harness spans nest a few deep, so the innermost cover of a point is
    # among the last few spans that start before it)
    spans = sorted((e for e in events if e.kind == "span"
                    and e.name != WINDOW_SPAN), key=lambda e: e.start_ns)
    span_starts = [s.start_ns for s in spans]
    gaps: Dict[str, float] = {}
    u = unions[devices[0]]
    edges = [w0] + [x for iv in u for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        best = None
        i = bisect.bisect_right(span_starts, mid)
        for s in spans[max(i - 8, 0):i]:
            if s.end_ns >= mid and (best is None or s.dur_ns < best.dur_ns):
                best = s
        name = best.name if best is not None else "host.other"
        gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9
    return Summary((w1 - w0) / 1e9, busy_s, len(devices), op_s, module_s,
                   gaps)
