"""Telemetry layer: trace conservation invariants, exporter structure, and
the zero-cost-when-disabled guarantee.

Conservation properties checked over traced sim runs (example-based and,
when hypothesis is installed, over random router policies/share modes):

- every request span opened (``b``) is closed exactly once (``e``) —
  finish or drop, never both, never neither;
- every ``lease.acquire`` has a matching ``lease.release`` with a cause;
- per iteration, prefill chunk tokens minus rescinded chunk tokens plus
  decode tokens equals the iteration event's ``tokens`` (the scheduler's
  ``plan.token_count()``).
"""

import dataclasses
import json
import time
import tracemalloc
from collections import Counter, defaultdict

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.telemetry import (NO_SPAN, Tracer, merge_events, percentile,
                                  to_chrome_trace, validate_trace_events)
from repro.serving.simulator import (SimBackend, make_shared_prefix_workload,
                                     make_workload, simulate_paged,
                                     simulate_router)


def _traced_paged(n=60, **kw):
    kw.setdefault("num_blocks", 300)
    kw.setdefault("block_size", 16)
    kw.setdefault("max_tokens_per_iter", 512)
    reqs = make_workload(n, rate=30.0, seed=3, max_len=512)
    return simulate_paged(reqs, trace=True, **kw)


# ---------------------------------------------------------------- invariants


def check_span_conservation(events):
    """Every request span begins once and ends once."""
    opened = Counter(e.rid for e in events
                     if e.cat == "request" and e.ph == "b")
    closed = Counter(e.rid for e in events
                     if e.cat == "request" and e.ph == "e")
    assert opened, "no request spans traced"
    for rid, n in opened.items():
        assert n == 1, f"request {rid} opened {n} times"
        assert closed[rid] == 1, \
            f"request {rid} opened once but closed {closed[rid]} times"
    assert set(closed) == set(opened), "span closed without a begin"


def check_lease_conservation(events):
    acq = Counter((e.instance, e.rid) for e in events
                  if e.cat == "lease" and e.name == "acquire")
    rel = Counter((e.instance, e.rid) for e in events
                  if e.cat == "lease" and e.name == "release")
    assert acq == rel, f"unbalanced leases: acquired {acq - rel or '{}'} " \
                       f"never released; released {rel - acq or '{}'} " \
                       f"never acquired"
    for e in events:
        if e.cat == "lease" and e.name == "release":
            assert e.args["cause"] in ("finish", "preempt")


def check_token_conservation(events):
    """chunk tokens - rescinded chunk tokens + decodes == iteration tokens,
    per (instance, iteration)."""
    chunks = defaultdict(int)
    rescinds = defaultdict(int)
    iters = {}
    for e in events:
        key = (e.instance, e.it)
        if e.cat == "req" and e.name == "chunk":
            chunks[key] += e.args["length"]
        elif e.cat == "req" and e.name == "chunk_rescind":
            rescinds[key] += e.args["length"]
        elif e.name == "iteration" and e.cat == "engine":
            iters[key] = (e.args["tokens"], e.args["decodes"])
    assert iters, "no iteration events traced"
    seen_keys = set(chunks) | set(rescinds) | set(iters)
    for key in seen_keys:
        tokens, decodes = iters.get(key, (0, 0))
        planned = chunks[key] - rescinds[key] + decodes
        assert planned == tokens, \
            f"instance {key[0]} iteration {key[1]}: chunks {chunks[key]} " \
            f"- rescinds {rescinds[key]} + decodes {decodes} != " \
            f"iteration tokens {tokens}"


def check_all(events):
    check_span_conservation(events)
    check_lease_conservation(events)
    check_token_conservation(events)


# ------------------------------------------------------------- example-based


def test_paged_trace_conservation():
    res = _traced_paged()
    assert res.events and res.timelines
    check_all(res.events)


def test_paged_trace_has_preemption_with_cause():
    # tight page budget forces preemptions; each must name its trigger
    res = _traced_paged(n=80, num_blocks=120)
    pre = [e for e in res.events if e.cat == "sched" and e.name == "preempt"]
    assert pre, "tight-memory run produced no preemption events"
    for e in pre:
        assert e.args["kind"] in ("victim", "self")
        assert e.args["trigger"] is not None
        assert e.rid is not None  # the victim
    check_all(res.events)  # rescinds/preempts keep the invariants


def test_refusal_events_carry_why():
    res = _traced_paged(n=80, num_blocks=120)
    whys = {e.args["why"] for e in res.events if e.cat == "sched" and e.name == "refuse"}
    assert whys <= {"solo_wait", "budget_sliver", "no_pages"}
    assert whys, "constrained run never refused an admission"


def test_router_trace_conservation_and_tracks():
    reqs = make_shared_prefix_workload(50, rate=30.0, n_groups=3, seed=5)
    res = simulate_router(reqs, n_instances=3, policy="round_robin",
                          prefix_share=True, blocks_per_instance=400,
                          trace=True)
    check_all(res.events)
    instances = {e.instance for e in res.events}
    assert {0, 1, 2} <= instances  # one track per child
    assert 3 in instances  # plus the router's own track
    assert any(e.cat == "router" and e.name == "place"
               for e in res.events)
    assert any(e.cat == "board" and e.name == "publish"
               for e in res.events)
    assert any(e.cat == "board" and e.name == "lookup"
               for e in res.events)
    assert set(res.timelines) == {0, 1, 2}
    assert all(rows for rows in res.timelines.values())


def test_zero_copy_router_emits_lease_lifecycle():
    # round_robin scatters a shared prefix, so somebody must borrow
    reqs = make_shared_prefix_workload(40, rate=100.0, n_groups=2,
                                       prefix_len=64, suffix_len=16,
                                       out_len=16, seed=3,
                                       group_draw="random")
    from repro.core.distkv.netmodel import NetworkModel
    res = simulate_router(reqs, n_instances=3, policy="round_robin",
                          prefix_share=True, share_mode="zero_copy",
                          blocks_per_instance=128, net=NetworkModel(),
                          trace=True)
    assert res.borrowed_pages > 0, "zero_copy must actually borrow"
    names = {(e.cat, e.name) for e in res.events}
    assert ("net", "lease") in names
    assert ("lease", "borrow") in names
    assert ("lease", "lend") in names
    check_all(res.events)


# ------------------------------------------------------------------ property


@settings(max_examples=8, deadline=None)
@given(policy=st.sampled_from(["round_robin", "least_loaded",
                               "prefix_affinity"]),
       share_mode=st.sampled_from(["copy", "zero_copy"]),
       seed=st.integers(min_value=0, max_value=40),
       n_instances=st.integers(min_value=2, max_value=4))
def test_router_trace_conservation_property(policy, share_mode, seed,
                                            n_instances):
    from repro.core.distkv.netmodel import NetworkModel
    reqs = make_shared_prefix_workload(30, rate=40.0, n_groups=2, seed=seed)
    res = simulate_router(reqs, n_instances=n_instances, policy=policy,
                          prefix_share=True, share_mode=share_mode,
                          blocks_per_instance=300, net=NetworkModel(),
                          trace=True)
    check_all(res.events)
    assert not validate_trace_events(to_chrome_trace(res.events))


# ------------------------------------------------------------------ exporter


def test_chrome_trace_structure(tmp_path):
    res = _traced_paged(n=20)
    obj = to_chrome_trace(res.events)
    assert validate_trace_events(obj) == []
    evs = obj["traceEvents"]
    # metadata names the instance's track
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name" and e["pid"] == 0 for e in meta)
    # ts is µs of virtual time; spans carry the request id
    span = next(e for e in evs if e["ph"] == "b")
    assert span["id"] == span["args"]["rid"]
    ev = next(e for e in evs if e["ph"] == "X" and e["name"] == "iteration")
    assert ev["dur"] >= 0 and isinstance(ev["ts"], float)
    # round-trips through the file exporter
    from repro.core.telemetry import export_chrome_trace
    out = tmp_path / "t.json"
    export_chrome_trace(res.events, out)
    assert validate_trace_events(json.loads(out.read_text())) == []


def test_validate_trace_events_catches_problems():
    assert validate_trace_events("nope")
    assert validate_trace_events([{"ph": "X", "name": "a", "ts": 0.0,
                                  "pid": 0}])  # X without dur
    assert validate_trace_events([{"ph": "b", "name": "a", "cat": "r",
                                   "ts": 0.0, "pid": 0, "id": 1}])  # no end
    good = [{"ph": "i", "name": "a", "ts": 0.0, "pid": 0, "s": "t"}]
    assert validate_trace_events(good) == []


def test_metrics_csv_and_json_export(tmp_path):
    res = _traced_paged(n=20)
    from repro.core.telemetry import export_metrics_csv, export_metrics_json
    csv_path = tmp_path / "m.csv"
    n = export_metrics_csv(res.timelines, csv_path)
    assert n == sum(len(r) for r in res.timelines.values())
    header = csv_path.read_text().splitlines()[0].split(",")
    assert header[:3] == ["instance", "ts", "iteration"]
    assert "kv_util_frac" in header and "tokens" in header
    export_metrics_json(res.timelines, tmp_path / "m.json")
    rows = json.loads((tmp_path / "m.json").read_text())
    assert len(rows) == n and rows[0]["instance"] == 0


def test_sim_timeline_counts_prefix_tokens_cumulatively():
    res = simulate_paged(make_shared_prefix_workload(40, rate=40.0, seed=3),
                         num_blocks=3000, prefix_cache=True, trace=True)
    rows = res.timelines[0]
    hits = [r["prefix_hit_tokens"] for r in rows]
    looks = [r["prefix_lookup_tokens"] for r in rows]
    assert all("prefix_hit_rate" not in r for r in rows)
    assert hits == sorted(hits) and looks == sorted(looks)
    # a window's hit rate is the ratio of the counters' differences
    assert hits[-1] / looks[-1] == pytest.approx(res.prefix_hit_rate)
    assert hits[-1] > 0


def test_tracer_ring_buffer_overwrites_oldest():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant("t", f"e{i}", ts=float(i))
    evs = tr.events()
    assert len(evs) == 4
    assert [e.name for e in evs] == ["e6", "e7", "e8", "e9"]
    assert tr.emitted == 10 and tr.dropped == 6


def test_merge_events_sorts_by_ts():
    a, b = Tracer(instance=0), Tracer(instance=1)
    a.instant("t", "x", ts=2.0)
    b.instant("t", "y", ts=1.0)
    merged = merge_events([a, None, b])
    assert [e.name for e in merged] == ["y", "x"]


# ----------------------------------------------------------------- percentile


def test_percentile_shared_helper():
    assert percentile([], 99) == float("inf")
    assert percentile([5.0], 99) == 5.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 0) == 1.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 100) == 4.0  # no index overflow
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert percentile([1.0, 2.0], 200) == 2.0  # q clamped


def test_service_stats_p99_uses_helper():
    res = _traced_paged(n=20)
    assert res.p99_tbt == percentile(res.max_tbts, 99)


# ------------------------------------------------------------- zero overhead


def test_disabled_tracer_constructs_nothing():
    """With trace=False no Event/args objects may be built: tracemalloc,
    filtered to the telemetry module files, must see zero allocations."""
    import repro.core.telemetry.metrics as metrics_mod
    import repro.core.telemetry.tracer as tracer_mod
    reqs = make_workload(30, rate=30.0, seed=1, max_len=256)
    simulate_paged(reqs, num_blocks=200, trace=False)  # warm caches
    flt = [tracemalloc.Filter(True, m.__file__)
           for m in (tracer_mod, metrics_mod)]
    tracemalloc.start(5)
    try:
        simulate_paged(reqs, num_blocks=200, trace=False)
        snap = tracemalloc.take_snapshot().filter_traces(flt)
    finally:
        tracemalloc.stop()
    leaked = sum(s.size for s in snap.statistics("filename"))
    assert leaked == 0, f"disabled path allocated {leaked} bytes " \
                        f"inside the telemetry layer"


def test_backend_telemetry_attrs_default_none():
    b = SimBackend(num_blocks=100)
    assert b.trace is None and b.metrics is None
    assert b.scheduler.trace is None


# ---------------------------------------------------------------- spans


def test_span_pushes_a_complete_event_on_a_wall_clock_tracer():
    tr = Tracer()
    tr.now, tr.t_step0, tr.iteration = 5.0, time.monotonic(), 7
    with tr.span("sched", "schedule", rid=3, a=1) as args:
        args["b"] = 2
    (ev,) = tr.events()
    assert (ev.cat, ev.name, ev.ph, ev.rid, ev.it) == \
        ("sched", "schedule", "X", 3, 7)
    assert ev.args == {"a": 1, "b": 2}
    assert ev.ts >= 5.0 and ev.dur >= 0.0


def test_span_on_a_virtual_clock_tracer_pushes_nothing():
    tr = Tracer(clock=lambda: 1.0)
    with tr.span("prefix", "match", k=1) as args:
        assert args == {"k": 1}
    assert len(tr) == 0 and tr.emitted == 0


def test_nested_spans_keep_their_order():
    tr = Tracer()
    tr.now, tr.t_step0 = 0.0, time.monotonic()
    with tr.span("engine", "iteration"):
        with tr.span("sched", "schedule"):
            pass
        with tr.span("engine", "sync"):
            time.sleep(0.001)
    by = {e.name: e for e in tr.events()}
    # pushed at exit: inner spans first
    assert [e.name for e in tr.events()] == ["schedule", "sync", "iteration"]
    it, sch, syn = by["iteration"], by["schedule"], by["sync"]
    assert it.ts <= sch.ts <= sch.ts + sch.dur <= syn.ts
    assert syn.ts + syn.dur <= it.ts + it.dur
    assert syn.dur >= 0.001


def test_disabled_site_enters_the_shared_null_span():
    assert NO_SPAN.__enter__() is None
    with NO_SPAN as args:
        assert args is None


# ------------------------------------------------------- the paged engine


@pytest.fixture(scope="module")
def tiny_engine_setup():
    import jax
    from repro.configs import smoke_config
    from repro.models import Model
    cfg = dataclasses.replace(smoke_config("h2o-danube-1.8b"),
                              sliding_window=None)
    params = Model(cfg, remat=False).init(jax.random.PRNGKey(0))
    return cfg, params


def _tiny_engine(setup, telemetry):
    from repro.serving.engine import EngineConfig, PagedEngine
    cfg, params = setup
    return PagedEngine(cfg, params, EngineConfig(
        num_pages=64, page_size=8, max_slots=4, max_tokens_per_iter=32,
        enable_prefix_cache=True, enable_telemetry=telemetry))


def _serve(eng, seed, n=3):
    """Requests sharing a two-page prefix, through LLMService."""
    from repro.serving.api import LLMService, SamplingParams
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, 200, 16).tolist()
    prompts = [shared + rng.integers(1, 200, 5 + 3 * i).tolist()
               for i in range(n)]
    svc = LLMService(eng)
    return svc, svc.generate(prompts, SamplingParams(max_new_tokens=3))


def test_disabled_tracer_constructs_nothing_in_the_engine(tiny_engine_setup):
    """The same zero-allocation guarantee on a CPU PagedEngine stepping
    through LLMService with the prefix cache on and telemetry off."""
    import repro.core.telemetry.metrics as metrics_mod
    import repro.core.telemetry.tracer as tracer_mod
    eng = _tiny_engine(tiny_engine_setup, telemetry=False)
    assert eng.trace is None and eng.prefix_cache.trace is None
    _serve(eng, seed=0)  # compile every shape outside the measurement
    flt = [tracemalloc.Filter(True, m.__file__)
           for m in (tracer_mod, metrics_mod)]
    tracemalloc.start(5)
    try:
        _serve(eng, seed=1)
        snap = tracemalloc.take_snapshot().filter_traces(flt)
    finally:
        tracemalloc.stop()
    leaked = sum(s.size for s in snap.statistics("filename"))
    assert leaked == 0, f"disabled engine path allocated {leaked} bytes " \
                        f"inside the telemetry layer"


def test_engine_spans_nest_on_one_host_line_of_a_profiler_trace(
        tiny_engine_setup, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    eng = _tiny_engine(tiny_engine_setup, telemetry=True)
    _serve(eng, seed=0)  # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        _serve(eng, seed=1)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = ("engine.iteration", "sched.schedule", "engine.sync",
             "sched.complete", "service.poll", "prefix.match",
             "prefix.insert")
    lines = [line for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             if any(e.name == "engine.iteration" for e in line.events)]
    assert len(lines) == 1
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for e in lines[0].events if e.name in names]
    assert {n for n, _, _ in evs} == set(names)
    decoding = 0
    for name, a, b in evs:
        if name != "engine.iteration":
            continue
        kids = sorted((s, n) for n, s, e in evs
                      if a <= s and e <= b and n != "engine.iteration"
                      and not n.startswith("prefix."))
        order = [n for _, n in kids]
        assert order[0] == "sched.schedule" and order[-1] == "sched.complete"
        if "engine.sync" in order:
            decoding += 1
            assert order.index("engine.sync") > 0
        # every iteration lies inside one service.poll
        assert any(n == "service.poll" and s <= a and b <= e
                   for n, s, e in evs)
    assert decoding > 0


def test_engine_ring_keeps_iteration_and_chunk_events(tiny_engine_setup):
    eng = _tiny_engine(tiny_engine_setup, telemetry=True)
    svc, outs = _serve(eng, seed=2)
    evs = eng.trace.events()
    iters = [e for e in evs if e.cat == "engine" and e.name == "iteration"]
    chunks = [e for e in evs if e.cat == "engine" and e.name == "chunk"]
    assert len(iters) == eng.iterations
    assert all(set(e.args) == {"tokens", "decodes", "chunks"} for e in iters)
    assert sum(e.args["chunks"] for e in iters) == len(chunks)
    for e in chunks:
        assert set(e.args) == {"start", "length", "last"}
        assert e.rid is not None and e.dur >= 0.0
    # every span is a ring event too
    assert {(e.cat, e.name) for e in evs if e.ph == "X"} >= {
        ("engine", "sync"), ("sched", "schedule"), ("sched", "complete"),
        ("prefix", "match"), ("prefix", "insert")}
    assert eng.trace.dropped == 0
    # the timeline keeps the cumulative hit and lookup tokens
    rows = svc.stats().timelines[0]
    last = rows[-1]
    assert "prefix_hit_rate" not in last
    assert last["prefix_hit_tokens"] == eng.prefix_cache.hit_tokens > 0
    assert last["prefix_lookup_tokens"] == eng.prefix_cache.lookup_tokens


def test_tokens_are_stamped_when_they_reach_the_host(tiny_engine_setup):
    from repro.core.scheduling.request import Request
    eng = _tiny_engine(tiny_engine_setup, telemetry=True)
    req = Request(0, 0.0, list(range(1, 12)), max_new_tokens=3)
    eng.add_request(req)
    now = 100.0
    eng.step(now)  # the whole prompt in one chunk: the first token
    syncs = [e for e in eng.trace.events()
             if e.name == "sync" and e.it == 0]
    assert syncs and req.first_token_time is not None
    # at least the step's start plus the host time up to the token's sync
    assert req.first_token_time >= max(e.ts + e.dur for e in syncs) > now
    assert req.last_token_time == req.first_token_time
    eng.step(now + 1.0)
    assert req.last_token_time > now + 1.0
    assert req.max_tbt == req.last_token_time - req.first_token_time
