"""The readers of the program's own spans over a trace built by hand:
host, scheduler and radix time per engine iteration."""

import pytest

from perfbench import driver, spans, spec
from perfbench.tracefile import Event, Trace, _clip

DEV = "/device:TPU:0"
WINDOW = (1.0, 2.0)


def _trace(host):
    modules = [Event("jit__decode_fn", 1.2, 1.3)]
    return Trace(WINDOW, {DEV: modules}, {DEV: list(modules)},
                 _clip([Event("bench.traced", *WINDOW)] + host, WINDOW))


def _read(name, trace):
    return spec.Bench().reader(name)(driver.Run({}, {}, 1.0, [], [], {},
                                                trace=trace))


# two whole iterations, and one at each edge of the window
HOST = [
    # cut by the window's start
    Event("engine.iteration", 0.9, 1.05),
    Event("sched.schedule", 0.9, 1.02),
    # iteration 1: 100 ms, 40 ms waiting for the device
    Event("bench.poll", 1.1, 1.25),
    Event("service.poll", 1.1, 1.25),
    Event("engine.iteration", 1.1, 1.2),
    Event("sched.schedule", 1.1, 1.13),
    Event("prefix.match", 1.11, 1.12),
    Event("prefix.evict", 1.115, 1.125),
    Event("engine.chunk", 1.13, 1.14),
    Event("engine.sync", 1.14, 1.18),
    Event("sched.complete", 1.18, 1.2),
    Event("prefix.insert", 1.19, 1.195),
    # iteration 2: 50 ms, no radix work
    Event("engine.iteration", 1.3, 1.35),
    Event("sched.schedule", 1.3, 1.31),
    Event("engine.sync", 1.31, 1.34),
    Event("sched.complete", 1.34, 1.345),
    # cut by the window's end
    Event("engine.iteration", 1.9, 2.1),
    Event("sched.schedule", 1.9, 1.95),
    Event("engine.sync", 1.95, 2.05),
]


def test_only_iterations_wholly_inside_the_window_are_selected():
    its = spans.iterations(_trace(HOST))
    assert [(it.start, it.end) for it, _ in its] == [(1.1, 1.2), (1.3, 1.35)]
    kids = [k.name for k in its[1][1]]
    assert kids == ["sched.schedule", "engine.sync", "sched.complete"]
    # spans outside the iteration are not its children
    assert "service.poll" not in [k.name for k in its[0][1]]


def test_host_time_is_the_iteration_less_its_syncs():
    want = ((0.1 - 0.04) + (0.05 - 0.03)) / 2
    assert _read("host_ms_per_iter", _trace(HOST)) == \
        pytest.approx(1e3 * want)


def test_scheduler_time_is_self_time():
    # iteration 1: schedule 30 ms less the 15 ms of overlapping match and
    # evict; complete 20 ms less the 5 ms insert. Iteration 2: 15 ms.
    want = ((0.03 - 0.015) + (0.02 - 0.005) + (0.01 + 0.005)) / 2
    assert _read("sched_ms_per_iter", _trace(HOST)) == \
        pytest.approx(1e3 * want)


def test_radix_time_counts_overlaps_once_and_idle_iterations_as_zero():
    want = (0.015 + 0.005 + 0.0) / 2
    assert _read("radix_ms_per_iter", _trace(HOST)) == \
        pytest.approx(1e3 * want)


@pytest.mark.parametrize("name", ["host_ms_per_iter", "sched_ms_per_iter",
                                  "radix_ms_per_iter"])
def test_nothing_is_read_without_a_whole_iteration(name):
    # a program without the spans, an iteration cut by each edge, no trace
    assert _read(name, _trace([Event("bench.poll", 1.1, 1.4)])) is None
    assert _read(name, _trace(HOST[:2] + HOST[-3:])) is None
    assert _read(name, None) is None


def test_idle_gaps_are_named_by_the_innermost_program_span():
    tr = _trace(HOST)
    busy = [Event("jit__decode_fn", 1.2, 1.3), Event("jit__decode_fn",
                                                     1.35, 2.0)]
    tr.modules[DEV] = tr.ops[DEV] = busy
    # idle 1.0-1.2 (middle 1.1: bench.poll, service.poll, the iteration
    # and sched.schedule cover it) and 1.3-1.35 (in engine.sync)
    assert dict(tr.idle_gaps()) == {"sched.schedule": pytest.approx(0.2),
                                    "engine.sync": pytest.approx(0.05)}
