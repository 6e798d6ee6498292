#!/usr/bin/env python
"""Validate an exported Chrome/Perfetto trace, and check the
tracer-disabled path really is free.

Usage:
  PYTHONPATH=src python tools/validate_trace.py out.json [more.json ...]
  PYTHONPATH=src python tools/validate_trace.py --check-disabled-overhead

Validation runs the structural schema checks shared with the exporter
tests (``repro.core.telemetry.validate_trace_events``): top-level shape,
required per-event fields, known phase codes, non-negative durations, and
balanced async begin/end spans — plus the host-swap invariant
(``repro.core.telemetry.validate_swap_balance``): per request,
``sched.swap_out``/``sched.swap_in`` instants must alternate with at most
one unmatched trailing swap_out. Exit status is non-zero on any problem.

``--check-disabled-overhead`` runs the chunked-prefill sim path twice —
telemetry off, then on — and a tiny CPU ``PagedEngine`` (prefix cache on)
through ``LLMService`` with telemetry off, and asserts with
``tracemalloc`` that the disabled runs allocate ZERO bytes attributable to
the telemetry module files: with the tracer ``None`` every emission site
is a single attribute test (a span site enters the shared ``NO_SPAN``),
so no Event object, args dict, or string may be constructed. (A
wall-clock <2% bound is reported for information but not enforced — CI
machines are too noisy to gate on sub-percent timing.)
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import tracemalloc


def validate_files(paths) -> int:
    from repro.core.telemetry import validate_swap_balance, \
        validate_trace_events
    bad = 0
    for path in paths:
        try:
            with open(path) as f:
                obj = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"{path}: unreadable ({e})")
            bad += 1
            continue
        errors = validate_trace_events(obj)
        # host-swap invariant: per request, swap_out/swap_in instants
        # alternate (at most one unmatched trailing swap_out)
        errors += validate_swap_balance(obj)
        n = len(obj.get("traceEvents", obj) if isinstance(obj, (dict, list))
                else [])
        if errors:
            bad += 1
            print(f"{path}: INVALID ({len(errors)} problems, {n} events)")
            for e in errors[:20]:
                print(f"  - {e}")
            if len(errors) > 20:
                print(f"  ... and {len(errors) - 20} more")
        else:
            print(f"{path}: OK ({n} events)")
    return bad


def _sim_run(trace: bool):
    from repro.serving.simulator import make_workload, simulate_paged
    reqs = make_workload(80, rate=40.0, seed=7, max_len=512)
    return simulate_paged(reqs, num_blocks=400, block_size=16,
                          max_tokens_per_iter=512, trace=trace)


def _engine_serve():
    """A tiny CPU engine with the prefix cache on and telemetry off, and a
    function serving requests that share a prefix through LLMService."""
    import dataclasses

    import jax
    import numpy as np
    from repro.configs import smoke_config
    from repro.models import Model
    from repro.serving.api import LLMService, SamplingParams
    from repro.serving.engine import EngineConfig, PagedEngine
    cfg = dataclasses.replace(smoke_config("h2o-danube-1.8b"),
                              sliding_window=None)
    params = Model(cfg, remat=False).init(jax.random.PRNGKey(0))
    eng = PagedEngine(cfg, params, EngineConfig(
        num_pages=64, page_size=8, max_slots=4, max_tokens_per_iter=32,
        enable_prefix_cache=True))

    def serve(seed):
        rng = np.random.default_rng(seed)
        shared = rng.integers(1, 200, 16).tolist()
        LLMService(eng).generate(
            [shared + rng.integers(1, 200, 5 + 3 * i).tolist()
             for i in range(3)], SamplingParams(max_new_tokens=3))
    return serve


def _telemetry_bytes(fn) -> int:
    """Bytes ``fn()`` allocates in the telemetry module files."""
    import repro.core.telemetry.tracer as tracer_mod
    import repro.core.telemetry.metrics as metrics_mod
    telemetry_files = (tracer_mod.__file__, metrics_mod.__file__)
    flt = [tracemalloc.Filter(True, f) for f in telemetry_files]
    tracemalloc.start(5)
    fn()
    snap = tracemalloc.take_snapshot().filter_traces(flt)
    tracemalloc.stop()
    return sum(st.size for st in snap.statistics("filename"))


def check_disabled_overhead() -> int:
    _sim_run(False)  # warm imports/caches outside the measured window
    telemetry_bytes = _telemetry_bytes(lambda: _sim_run(False))
    serve = _engine_serve()
    serve(0)  # compile every shape outside the measured window
    engine_bytes = _telemetry_bytes(lambda: serve(1))

    # time outside the tracemalloc window — it slows every allocation
    t0 = time.perf_counter()
    _sim_run(False)
    t_off = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_on = _sim_run(True)
    t_on = time.perf_counter() - t0

    print(f"tracer-disabled run: {telemetry_bytes} bytes allocated by "
          f"telemetry code (must be 0)")
    print(f"tracer-disabled engine run: {engine_bytes} bytes allocated by "
          f"telemetry code (must be 0)")
    print(f"wall time: disabled {t_off * 1e3:.1f}ms, enabled "
          f"{t_on * 1e3:.1f}ms ({len(res_on.events)} events) "
          f"[informational]")
    if telemetry_bytes or engine_bytes:
        print("FAIL: the disabled path constructed telemetry objects")
        return 1
    print("OK: disabled path allocates nothing in the telemetry layer")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("traces", nargs="*",
                    help="trace-event JSON files to validate")
    ap.add_argument("--check-disabled-overhead", action="store_true",
                    help="assert the tracer-disabled sim and engine paths "
                         "allocate nothing in the telemetry layer")
    args = ap.parse_args()
    if not args.traces and not args.check_disabled_overhead:
        ap.error("nothing to do: pass trace files and/or "
                 "--check-disabled-overhead")
    bad = validate_files(args.traces)
    if args.check_disabled_overhead:
        bad += check_disabled_overhead()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
