"""From a profiler trace to the numbers the per-layer readers use.

The trace of a ``--trace 1`` run holds the device's operations (line "XLA
Ops" of each ``/device:TPU:<n>`` plane), the programs they belong to (line
"XLA Modules") and the benchmark's own host spans (``bench.*``, written
with ``jax.profiler.TraceAnnotation``).  The span ``bench.window`` marks
the measured window; everything is clipped to it.

- busy: the union of the intervals in which an operation ran, per device,
  averaged over the devices;
- op time: each operation's time, by name without its instance suffix
  (``%fusion.172.remat2`` and ``fusion.3`` are both ``fusion``);
- idle gaps: each interval of the window in which no operation ran, given
  to the innermost ``bench.*`` span open at its middle (``no_span`` when
  none is).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
_OP = re.compile(r"^%?([^ .=]+)")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float                 # ns, on the trace's one clock
    dur: float                   # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                # averaged over devices
    op_s: Dict[str, float]       # summed over devices
    module_s: Dict[str, List[float]]
    idle_gaps: Dict[str, float]  # averaged over devices
    devices: int


def op_name(raw: str) -> str:
    m = _OP.match(raw.strip())
    return m.group(1) if m else raw


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _innermost(spans: Sequence[Event], starts: Sequence[float],
               t: float) -> str:
    """Name of the latest-starting span open at ``t``; ``spans`` sorted by
    start, ``starts`` their starts."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if spans[i].end > t:
            return spans[i].name
        i -= 1
    return "no_span"


def reduce(devices: Sequence[Tuple[List[Event], List[Event]]],
           spans: Sequence[Event]) -> Optional[Summary]:
    """``devices``: per device (ops, modules); ``spans``: the host's
    ``bench.*`` spans, ``bench.window`` among them.  None when the trace
    has no window or no device operation in it."""
    windows = [s for s in spans if s.name == WINDOW]
    if not windows or not devices:
        return None
    w0, w1 = windows[0].start, windows[0].end
    inner = sorted((s for s in spans if s.name != WINDOW),
                   key=lambda s: s.start)
    starts = [s.start for s in inner]
    busy = 0.0
    op_s: Dict[str, float] = collections.defaultdict(float)
    mod_s: Dict[str, List[float]] = collections.defaultdict(list)
    gaps: Dict[str, float] = collections.defaultdict(float)
    for ops, modules in devices:
        iv = []
        for ev in ops:
            s, e = max(ev.start, w0), min(ev.end, w1)
            if e > s:
                iv.append((s, e))
                op_s[op_name(ev.name)] += (e - s) / 1e9
        merged = _union(iv)
        busy += sum(e - s for s, e in merged) / 1e9
        t = w0
        for s, e in merged + [(w1, w1)]:
            if s > t:
                gaps[_innermost(inner, starts, (t + s) / 2)] += (s - t) / 1e9
            t = max(t, e)
        for ev in modules:
            if w0 <= ev.start < w1:
                mod_s[ev.name].append(ev.dur / 1e9)
    n = len(devices)
    if busy <= 0.0:
        return None
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy / n,
                   op_s=dict(op_s), module_s=dict(mod_s),
                   idle_gaps={k: v / n for k, v in gaps.items()}, devices=n)


def load(log_dir: str) -> Tuple[List[Tuple[List[Event], List[Event]]],
                                  List[Event]]:
    """Read the ``.xplane.pb`` the profiler wrote under ``log_dir``:
    (per TPU device (ops, modules), host ``bench.*`` spans)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return [], []
    data = ProfileData.from_file(sorted(paths)[-1])
    devices, spans = [], []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Event(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [Event(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
            devices.append((ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith("bench."))
    return devices, spans


def top(d: Dict[str, float], n: int, keep: Sequence[str] = ()
        ) -> List[List]:
    """The ``n`` largest entries as [name, value], largest first, with each
    name of ``keep`` among them (at its value, or 0)."""
    others = [k for k, _ in sorted(d.items(), key=lambda kv: -kv[1])
              if k not in keep]
    names = list(keep) + others[:max(0, n - len(keep))]
    return [[k, d.get(k, 0.0)]
            for k in sorted(names, key=lambda k: -d.get(k, 0.0))]
