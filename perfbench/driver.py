"""One run of one cell: set-up (weights from the seed, the engine, warm-up of
every program shape the traffic can reach, a lead-in of the traffic), the
measured window, the drain, and the comparison with the plain reference.

The timed path is the program's public serving entry: ``LLMService.submit``
and ``LLMService.poll`` over a ``PagedEngine``. The harness stamps every
token with its own clock when ``poll`` hands it over, and times each
request from when it was due (open loop) or sent (closed loop).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import generator, published, roofline, spec, tracefile
from perfbench.stats import percentile

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_S = 8.0          # longest traced window
PROFILER_LEAD_S = 10.0  # profiler start before the window


@dataclasses.dataclass
class Rec:
    """What the client saw of one request."""

    rid: int
    spec: generator.Spec
    due: float
    window: bool
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False


@dataclasses.dataclass
class Run:
    """The record a run leaves for the metric readers. ``counters`` holds
    the window's change of every counter in the engine's telemetry registry
    (telemetry is on in traced runs) and of the prefix cache's
    ``prefix_hit_tokens`` and ``prefix_lookup_tokens``; ``traced_events``
    every ring event of the traced iterations, in emission order."""

    model: Dict
    peaks: Dict
    window_s: float
    requests: List[Rec]
    decode_polls: List[List[int]]
    counters: Dict
    memory_peak_bytes: int = 0
    trace: Optional[tracefile.Trace] = None
    traced_decodes: List[List[int]] = dataclasses.field(default_factory=list)
    traced_chunks: List = dataclasses.field(default_factory=list)
    traced_events: List = dataclasses.field(default_factory=list)
    admits: Dict[int, float] = dataclasses.field(default_factory=dict)


class Compiles:
    """Counts the backend compilations (and compile-cache loads) JAX
    reports, by the harness clock."""

    def __init__(self):
        import jax
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.times.append(time.monotonic())

    def between(self, a: float, b: float) -> int:
        return sum(a <= t < b for t in self.times)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def start_jax():
    """JAX with its persistent compilation cache at a fixed path inside the
    checkout, caching every program; returns the devices. Call before
    anything else imports JAX."""
    path = os.path.join(spec.ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.devices()


# -- the program ------------------------------------------------------------------


def arch_config(name: str, conf: Dict):
    """The program's ``ArchConfig`` for a configuration file, by the
    program's own ``repro.configs.from_published`` where it has one, else
    by ``published.from_published``."""
    import repro.configs
    translate = getattr(repro.configs, "from_published", None) or \
        published.from_published
    return translate(name, conf["model"], conf["source"])


def build(name: str, conf: Dict, seed: int, telemetry: bool):
    """Weights from the seed (one jitted call), then the engine."""
    import jax
    from repro.models import Model
    from repro.serving.engine import EngineConfig, PagedEngine
    from perfbench import weights
    cfg = arch_config(name, conf)
    model = Model(cfg, remat=False)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, [s.n for s in model.plan], seed)
    jax.block_until_ready(params)
    ecfg = EngineConfig(**conf["engine"], enable_telemetry=telemetry)
    return PagedEngine(cfg, params, ecfg), params


# -- warm-up ----------------------------------------------------------------------


def _pow2s(lo: int, hi: int) -> List[int]:
    out, p = [], lo
    while True:
        out.append(p)
        if p >= hi:
            return out
        p *= 2


def warm_up(svc, ecfg, mix: Dict, vocab: int) -> int:
    """Drive through the service every program shape the traffic can
    reach: a prefill chunk of every power-of-two length up to the
    iteration's token budget, ending at every power-of-two count of pages
    up to the longest prompt; where the mix shares prefixes, copy-on-write
    of a shared boundary page for 1 up to ``max_slots`` admissions in one
    iteration; and the decode step.
    Chunks are placed by prefix-cache hits on one long prompt whose first
    token (0) no traffic prompt starts with. Returns the requests sent."""
    from repro.serving.api import SamplingParams
    ps = ecfg.page_size
    budget = ecfg.max_tokens_per_iter
    longest = generator.max_prompt_len(mix)
    rng = np.random.default_rng(0)
    base = [generator.WARMUP_TOKEN] + \
        rng.integers(1, vocab, longest - 1).tolist()

    def tail(n):
        return rng.integers(1, vocab, n).tolist()

    def serve(prompts, max_new=1):
        sp = SamplingParams(temperature=0.0, max_new_tokens=max_new)
        for p in prompts:
            svc.submit(p, sp)
        while svc.pending:
            svc.poll()

    serve([base], max_new=2)
    sent = 1
    cached = ecfg.enable_prefix_cache
    max_pages = -(-longest // ps)
    for n_pages in _pow2s(1, max_pages):
        end = min(n_pages * ps, longest)
        if -(-end // ps) <= n_pages // 2:
            continue
        for s in _pow2s(8, min(budget, longest)):
            c = min(s, budget, end - 1 if cached else end)
            if c <= s // 2 and s > 8:
                continue
            h = end - c if cached else 0
            serve([base[:h] + tail(c)])
            sent += 1
    if cached and mix["prompt"]["kind"] == "document":
        for k in range(1, ecfg.max_slots + 1):
            serve([base[:ps * k + 3] + tail(8) for _ in range(k)])
            sent += k
    return sent


# -- the window -------------------------------------------------------------------


def drive(svc, engine, mix: Dict, traffic: generator.Traffic, window_s: float,
          trace_dir: Optional[str], compiles: Compiles):
    """Lead-in, window and drain. The drain sends nothing and lasts until
    every request sent in the window has had its first token, or
    ``drain_s``. Returns (records, decode polls in the window, counters
    over the window, timing marks, traced-window state)."""
    import jax
    from repro.serving.api import SamplingParams
    open_loop = generator.is_open_loop(mix)
    lead = float(mix["lead_in_s"])
    w0, w1 = lead, lead + window_s
    drain_end = w1 + float(mix["drain_s"])
    # the profiler starts in the lead-in, so that its start-up stall is
    # over before the window; the traced span is the window's last
    # ``trace_s`` seconds, and the profiler stops in the drain
    trace_s = min(window_s, TRACE_S)
    t_start = max(0.0, w0 - PROFILER_LEAD_S) if trace_dir else math.inf
    t_span = w1 - trace_s
    annotate = jax.profiler.TraceAnnotation if trace_dir else \
        (lambda name: contextlib.nullcontext())
    # no Python tracer: it slows the host many times over, which would
    # show as device idle time
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0

    recs: Dict[int, Rec] = {}
    decode_polls: List[List[int]] = []
    traced = {"decodes": [], "iters": None}
    pending = sorted(traffic.open_loop(window_s), key=lambda s: s.due) \
        if open_loop else []
    stream = None if open_loop else traffic.stream()
    clients = 0 if open_loop else mix["arrivals"]["clients"]
    nxt = 0
    lateness: List[float] = []
    window_ids: List[int] = []
    counters = {}
    origin = time.monotonic()
    marks = {"origin": origin}
    profiling = False
    tracing = None

    def now():
        return time.monotonic() - origin

    def send(s: generator.Spec, due: float):
        t = now()
        with annotate("bench.submit"):
            rid = svc.submit(s.prompt, SamplingParams(
                temperature=0.0, max_new_tokens=s.max_new), arrival_time=due)
        in_win = w0 <= due < w1
        recs[rid] = Rec(rid, s, due, in_win)
        if in_win:
            window_ids.append(rid)
        if open_loop:
            lateness.append(t - due)

    def read_counters():
        """Every counter of the engine's telemetry registry, and the
        prefix cache's hit and lookup tokens."""
        m = engine.metrics
        out = {} if m is None else dict(m.counters)
        pc = engine.prefix_cache
        if pc is not None:
            out.update(prefix_hit_tokens=pc.hit_tokens,
                       prefix_lookup_tokens=pc.lookup_tokens)
        return out

    if not open_loop:
        for _ in range(clients):
            send(next(stream), 0.0)
    phase = "lead"
    while True:
        t = now()
        if phase == "lead" and t >= w0:
            phase = "window"
            marks["w0"] = time.monotonic()
            c0 = read_counters()
        if profiling is False and t >= t_start:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            profiling = True
        if phase == "window" and tracing is None and t >= t_span:
            tracing = annotate(tracefile.WINDOW_SPAN)
            tracing.__enter__()
            traced["iters"] = [engine.iterations, None]
        if phase == "window" and t >= w1:
            phase = "drain"
            marks["w1"] = time.monotonic()
            counters = {k: v - c0.get(k, 0) for k, v in
                        read_counters().items()}
            if tracing is not None:
                traced["iters"][1] = engine.iterations
                tracing.__exit__(None, None, None)
            if profiling:
                jax.profiler.stop_trace()
        if phase == "drain" and (t >= drain_end or all(
                recs[r].tokens for r in window_ids)):
            break
        while open_loop and nxt < len(pending) and pending[nxt].due <= t:
            send(pending[nxt], pending[nxt].due)
            nxt += 1
        if not engine.has_work and not svc.pending:
            gap = (pending[nxt].due - t) if nxt < len(pending) else 0.01
            with annotate(tracefile.WAIT_SPAN):
                time.sleep(max(0.0, min(gap, 0.05)))
            continue
        with annotate("bench.poll"):
            chunks = svc.poll(t)
        tr = now()
        ctxs = []
        for ch in chunks:
            r = recs[ch.request_id]
            for tok in ch.token_ids:
                if r.tokens:
                    ctxs.append(len(r.spec.prompt) + len(r.tokens))
                r.tokens.append(tok)
                r.times.append(tr)
            if ch.finished:
                r.finished = True
                if not open_loop and tr < w1:
                    send(next(stream), tr)
        if ctxs and phase == "window":
            decode_polls.append(ctxs)
        if ctxs and tracing is not None and phase == "window":
            traced["decodes"].append(ctxs)
    marks["end"] = time.monotonic()
    if lateness:
        log(f"generator: {len(lateness)} requests sent, lateness p50 "
            f"{percentile(lateness, 50):.6f} s, p99 "
            f"{percentile(lateness, 99):.6f} s, max {max(lateness):.6f} s")
    log(f"compilations: {compiles.between(marks['w0'], marks['w1'])} in the "
        f"window, {compiles.between(marks['w1'], marks['end'])} in the drain")
    return list(recs.values()), decode_polls, counters, marks, traced


# -- metrics and the check ---------------------------------------------------------


def end_to_end(recs: List[Rec], window_s: float, w0: float,
               w1: float) -> Dict[str, float]:
    """The client-side metrics over the window (harness clock, seconds
    after the traffic started)."""
    win = [r for r in recs if r.window]
    ttft = [r.times[0] - r.due for r in win if r.times]
    gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])
            if w0 <= b < w1]
    toks = sum(1 for r in recs for t in r.times if w0 <= t < w1)
    return {"ttft_p50_s": percentile(ttft, 50),
            "ttft_p90_s": percentile(ttft, 90),
            "itl_p99_ms": 1e3 * percentile(gaps, 99),
            "tokens_per_s": toks / window_s}


def sample(recs: List[Rec], seed: int, check: Dict) -> List[Rec]:
    """Finished requests the reference re-computes: the one with the most
    served tokens, then others drawn from the seed, until ``min_tokens``
    served tokens or ``max_requests`` requests."""
    done = [r for r in recs if r.finished and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    first = max(done, key=lambda r: len(r.tokens))
    rest = [r for r in done if r is not first]
    order = np.random.default_rng(seed ^ 0x5EED).permutation(len(rest))
    out, n = [first], len(first.tokens)
    for i in order:
        if n >= check["min_tokens"] or len(out) >= check["max_requests"]:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def compare(conf: Dict, seed: int, recs: List[Rec],
            control: bool = False) -> Dict[str, float]:
    """Widest gap, over every served token of ``recs``, between the
    reference's best logit and its logit of the served token. With
    ``control`` also the gap of the tokens that the reference computed at
    lower precision puts first at the same positions."""
    ref = importlib.import_module("perfbench.references." + conf["reference"])
    seqs = [np.asarray(r.spec.prompt + r.tokens[:-1], np.int32)
            for r in recs]
    firsts = [len(r.spec.prompt) - 1 for r in recs]
    rows = ref.logits(conf["model"], seed, seqs, firsts)
    out = {"widest_logit_gap": max(ref.widest_gaps(
        rows, [r.tokens for r in recs]))}
    if control:
        low = ref.logits(conf["model"], seed, seqs, firsts, control=True)
        out["control_gap"] = max(ref.widest_gaps(
            rows, [x.argmax(-1) for x in low]))
    return out


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(bench: spec.Bench, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, devices,
             control: bool = False) -> Dict:
    """One run; returns the result line's object. ``control`` adds the
    lower-precision control's reading (calibration only)."""
    import jax
    cell = bench.cell(cell_name)
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    limits = bench.limits(cell_name)
    model = conf["model"]
    pk = roofline.peaks(devices[0].device_kind)
    ctx_needed = generator.max_context(mix)
    if ctx_needed > conf["engine"]["max_context_len"]:
        raise ValueError(f"the mix needs {ctx_needed} tokens of context, the "
                         f"engine is sized for "
                         f"{conf['engine']['max_context_len']}")
    compiles = Compiles()

    engine, params = build(cell["config"], conf, seed, telemetry=trace)
    from repro.serving.api import LLMService
    svc = LLMService(engine)
    t = time.monotonic()
    n_warm = warm_up(svc, engine.ecfg, mix, model["vocab_size"])
    log(f"warm-up: {n_warm} requests, {time.monotonic() - t:.3f} s, "
        f"{len(compiles.times)} programs prepared since start")

    traffic = generator.Traffic(mix, seed, model["vocab_size"])
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace \
        else None
    recs, decode_polls, counters, marks, traced = drive(
        svc, engine, mix, traffic, seconds, trace_dir, compiles)
    origin = marks["origin"]
    w0 = marks["w0"] - origin
    w1 = marks["w1"] - origin
    e2e = end_to_end(recs, seconds, w0, w1)
    e2e["setup_s"] = marks["w0"] - t_start
    win = [r for r in recs if r.window]
    failed = sum(1 for r in win if not r.tokens)
    run = Run(model, pk, seconds, recs, decode_polls, counters,
              memory_peak(devices[:cell["chips"]]))
    if trace:
        run.traced_decodes = traced["decodes"]
        a, b = traced["iters"]
        for ev in engine.trace.events():
            if a <= ev.it < b:
                run.traced_events.append(ev)
                if ev.cat == "engine" and ev.name == "chunk":
                    run.traced_chunks.append((ev.args["start"],
                                              ev.args["length"]))
            if ev.cat == "sched" and ev.name == "admit" and \
                    ev.rid not in run.admits:
                run.admits[ev.rid] = ev.ts
        paths = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
                 for f in fs if f.endswith(".xplane.pb")]
        try:
            run.trace = tracefile.load(paths[0])
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    chosen = sample(recs, seed, mix["check"])
    # the program's state goes before the reference runs
    del svc, engine, params
    gc.collect()
    jax.clear_caches()
    gc.collect()
    t = time.monotonic()
    got = compare(conf, seed, chosen, control) if chosen \
        else {"widest_logit_gap": math.inf}
    gap = got["widest_logit_gap"]
    limit = float(limits["widest_logit_gap"])
    log(f"reference: {len(chosen)} requests, "
        f"{sum(len(r.tokens) for r in chosen)} served tokens compared, "
        f"{time.monotonic() - t:.3f} s")

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics(kind, cell_name):
        v = e2e.get(m["name"]) if not trace else bench.reader(m["name"])(run)
        if v is None or not math.isfinite(v):
            continue
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": bool(gap <= limit), "attempted": len(win),
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.top_ops()],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps()]}
    if control:
        out["control_gap"] = got.get("control_gap", math.inf)
    out["check"] = {"widest_logit_gap": {"value": gap, "limit": limit}}
    log(f"check: widest_logit_gap {gap!r} limit {limit!r}")
    return out
