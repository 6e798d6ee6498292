"""The program's own spans in a traced window, grouped by the engine
iteration that holds them.

The program opens a ``jax.profiler.TraceAnnotation`` named
``<layer>.<phase>`` for each phase of its host work (``engine.iteration``,
``sched.schedule``, ``sched.complete``, ``engine.sync``, ``prefix.match``,
``prefix.insert``, ``prefix.evict``, ...). They land on the host line of
the thread that runs the engine, which is the harness thread, so they are
in ``Trace.host``. A program without these spans yields no iteration, and
every reader built on this module then returns None.
"""

from __future__ import annotations

import bisect
from typing import List, Tuple

from perfbench.tracefile import Event, Trace, _length, _subtract, _union

ITERATION = "engine.iteration"
SYNC = "engine.sync"
SCHED = ("sched.schedule", "sched.complete")
PREFIX = "prefix."


def iterations(trace: Trace) -> List[Tuple[Event, List[Event]]]:
    """Each ``engine.iteration`` span wholly inside the window, with the
    host spans that lie within it. A span the window cut ends exactly on
    a bound (``tracefile._clip``), and is left out."""
    w0, w1 = trace.window
    host = sorted(trace.host, key=lambda e: e.start)
    starts = [e.start for e in host]
    out = []
    for it in host:
        if it.name != ITERATION or not (w0 < it.start and it.end < w1):
            continue
        lo = bisect.bisect_left(starts, it.start)
        hi = bisect.bisect_right(starts, it.end)
        out.append((it, [e for e in host[lo:hi]
                         if e is not it and e.end <= it.end]))
    return out


def covered(evs: List[Event]) -> float:
    """Seconds covered by ``evs`` (overlaps counted once)."""
    return _length(_union([(e.start, e.end) for e in evs]))


def self_time(outer: List[Event], inner: List[Event]) -> float:
    """Seconds covered by ``outer`` and not by ``inner``."""
    return _length(_subtract(_union([(e.start, e.end) for e in outer]),
                             _union([(e.start, e.end) for e in inner])))


def mean_ms(values: List[float]):
    return 1e3 * sum(values) / len(values) if values else None
