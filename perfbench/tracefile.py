"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device time of each jitted program, device busy and idle
time inside the traced window, the device ops that took most time, and
the host span each idle gap fell in.

The traced window is the host span ``bench.traced`` that the harness opens
right after the profiler starts and closes before it stops; host spans
``bench.wait`` mark time in which the engine had no work (the generator
waiting for the next arrival), which the idle share leaves out.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.traced"
WAIT_SPAN = "bench.wait"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start: float     # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Trace:
    """What one traced window holds. Times are seconds on the trace's own
    clock; ``window`` bounds every list."""

    window: Tuple[float, float]
    modules: Dict[str, List[Event]]      # per device plane, whole runs
    ops: Dict[str, List[Event]]          # per device plane, op runs
    host: List[Event]                    # the harness thread's spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def program_runs(self, fragment: str, device: Optional[str] = None
                     ) -> List[Event]:
        """Runs of the programs whose name holds ``fragment``, in time
        order, on ``device`` (default: the first device plane)."""
        dev = device or min(self.modules)
        return sorted((e for e in self.modules[dev] if fragment in e.name),
                      key=lambda e: e.start)

    def busy_intervals(self, device: str) -> List[Tuple[float, float]]:
        evs = self.ops.get(device) or self.modules.get(device) or []
        return _union([(e.start, e.end) for e in evs])

    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the device planes."""
        devs = [d for d in self.modules if self.modules[d] or self.ops[d]]
        if not devs:
            return 0.0
        return sum(_length(self.busy_intervals(d)) for d in devs) / len(devs)

    def idle_with_work(self, device: Optional[str] = None
                       ) -> List[Tuple[float, float]]:
        """Idle intervals of ``device`` in the window, outside the spans in
        which the engine had no work."""
        dev = device or min(self.modules)
        idle = _subtract([self.window], self.busy_intervals(dev))
        waits = _union([(e.start, e.end) for e in self.host
                        if e.name.startswith(WAIT_SPAN)])
        return _subtract(idle, waits)

    def work_s(self) -> float:
        waits = _union([(e.start, e.end) for e in self.host
                        if e.name.startswith(WAIT_SPAN)])
        return _length(_subtract([self.window], waits))

    def top_ops(self, n: int = 10, device: Optional[str] = None
                ) -> List[Tuple[str, float]]:
        """Device seconds by op (its HLO name and result shape); loops are
        left out, their bodies' ops are counted."""
        dev = device or min(self.modules)
        tot: Dict[str, float] = collections.Counter()
        for e in self.ops.get(dev) or self.modules.get(dev) or []:
            name = _op(e.name)
            if not name.startswith("%while"):
                tot[name] += e.end - e.start
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10, device: Optional[str] = None
                  ) -> List[Tuple[str, float]]:
        """Idle time with work, summed by the innermost host span that
        covers each gap's middle (``(no host span)`` where none does)."""
        tot: Dict[str, float] = collections.Counter()
        for a, b in self.idle_with_work(device):
            mid = 0.5 * (a + b)
            cover = [e for e in self.host if e.start <= mid <= e.end]
            name = min(cover, key=lambda e: e.end - e.start).name \
                if cover else "(no host span)"
            tot[_plain(name)] += b - a
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def _plain(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op(name: str) -> str:
    """``%fusion.148 = bf16[16384,16,8,80]{...} fusion(...)`` ->
    ``%fusion.148 bf16[16384,16,8,80]``."""
    m = re.match(r"(%[\w.-]+) = \(?([a-z0-9]+\[[\d,]*\])?", name)
    return " ".join(g for g in m.groups() if g) if m else name


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _subtract(base, cut):
    """``base`` minus the union ``cut`` (both sorted, disjoint)."""
    out = []
    for a, b in base:
        cur = a
        for c, d in cut:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < b:
            out.append((cur, b))
    return out


def _length(iv) -> float:
    return sum(b - a for a, b in iv)


def _clip(evs: List[Event], w: Tuple[float, float]) -> List[Event]:
    out = []
    for e in evs:
        a, b = max(e.start, w[0]), min(e.end, w[1])
        if b > a:
            out.append(Event(e.name, a, b))
    return out


def load(path: str) -> Trace:
    """Read one ``.xplane.pb``. Device planes are ``/device:*`` planes other
    than the host's; the harness thread is the host line that holds the
    ``bench.traced`` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    window = None
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and MODULE_LINE in lines:
            modules[plane.name] = _events(lines[MODULE_LINE])
            ops[plane.name] = _events(lines[OPS_LINE]) \
                if OPS_LINE in lines else []
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                spans = [e for e in evs if e.name == WINDOW_SPAN]
                if spans:
                    window = (spans[0].start, spans[0].end)
                    host = evs
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span on any host line")
    if not modules:
        raise ValueError(f"{path}: no device plane with a {MODULE_LINE!r} "
                         f"line")
    return Trace(window,
                 {d: [e for e in v if window[0] <= e.start and
                      e.end <= window[1]] for d, v in modules.items()},
                 {d: _clip(v, window) for d, v in ops.items()},
                 _clip(host, window))


def _events(line) -> List[Event]:
    return [Event(_plain(e.name), e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]
