"""The reduction of a recorded TPU profiler trace: a traced run of the
tiny test cell on one v5e, with the numbers that run printed beside it."""

import json
import os

import pytest

from perfbench import driver, spec, tracefile

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "fixtures", "tpu_trace.xplane.pb")
with open(os.path.join(HERE, "fixtures", "tpu_trace.json")) as f:
    PRINTED = json.load(f)


@pytest.fixture(scope="module")
def trace():
    return tracefile.load(TRACE)


def test_the_window_and_device_planes_are_found(trace):
    assert list(trace.modules) == ["/device:TPU:0"]
    assert trace.window_s == pytest.approx(PRINTED["device"]["window_s"])
    assert trace.busy_s() == pytest.approx(PRINTED["device"]["busy_s"])
    assert 0 < trace.busy_s() < trace.window_s


def test_program_runs_lie_inside_the_window_and_under_busy_time(trace):
    dec = trace.program_runs("_decode_fn")
    pre = trace.program_runs("_prefill_chunk_fn")
    assert dec and pre
    for e in dec + pre:
        assert trace.window[0] <= e.start < e.end <= trace.window[1]
    assert sum(e.end - e.start for e in dec + pre) <= trace.busy_s()
    ms = 1e3 * sum(e.end - e.start for e in dec) / len(dec)
    assert ms == pytest.approx(PRINTED["metrics"]["decode_step_ms"]["value"])


def test_idle_time_is_attributed_in_full(trace):
    idle = sum(b - a for a, b in trace.idle_with_work())
    gaps = trace.idle_gaps(n=1000)
    assert sum(s for _, s in gaps) == pytest.approx(idle)
    assert 0 <= idle <= trace.work_s() <= trace.window_s
    share = 100 * idle / trace.work_s()
    assert share == pytest.approx(
        PRINTED["metrics"]["device_idle_share"]["value"])
    ops = trace.top_ops()
    assert ops and all(not n.startswith("%while") for n, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)


def test_the_breakdown_printed_by_the_run_is_reproduced(trace):
    got = [[n, s] for n, s in trace.top_ops()]
    want = PRINTED["breakdown"]["device_ops"]
    assert [n for n, _ in got] == [n for n, _ in want]
    assert [s for _, s in got] == pytest.approx([s for _, s in want])


def test_readers_over_the_recorded_trace(trace):
    run = driver.Run({}, {}, 1.0, [], [], {}, trace=trace)
    assert spec.Bench().reader("decode_step_ms")(run) == pytest.approx(
        PRINTED["metrics"]["decode_step_ms"]["value"])
