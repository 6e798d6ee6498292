"""Find the highest open-loop rate a cell sustains: one engine, one
warm-up, then the cell's traffic at each rate in turn (a lead-in, a
window and a drain each), printing what the client saw. Run once on the
chip to fix a cell's rate (about four fifths of the knee); the benchmark's
runs never search.

  python3 perfbench/sweep.py --workload danube-chat --seed 7 \
      --rates 0.5,1,1.5,2 --seconds 20

A rate is sustained where the window's tokens/s keeps up with the offered
load and the time to first token does not grow from the window's first
half to its second.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()

    from perfbench import driver, generator, spec
    if driver.start_jax()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    from perfbench.stats import percentile
    from repro.serving.api import LLMService
    bench = spec.Bench()
    cell = bench.cell(args.workload)
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    compiles = driver.Compiles()
    engine, _ = driver.build(cell["config"], conf, args.seed, False)
    svc = LLMService(engine)
    driver.warm_up(svc, engine.ecfg, mix, conf["model"]["vocab_size"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, arrivals={"kind": "poisson", "rate_per_s": rate})
        traffic = generator.Traffic(m, args.seed + i,
                                    conf["model"]["vocab_size"])
        recs, _, _, marks, _ = driver.drive(svc, engine, m, traffic,
                                            args.seconds, None, compiles)
        while svc.pending:
            svc.poll(time.monotonic() - marks["origin"])
        w0 = marks["w0"] - marks["origin"]
        w1 = marks["w1"] - marks["origin"]
        e2e = driver.end_to_end(recs, args.seconds, w0, w1)
        win = sorted((r for r in recs if r.window), key=lambda r: r.due)
        half = len(win) // 2
        ttft = [[r.times[0] - r.due for r in part if r.times]
                for part in (win[:half], win[half:])]
        offered = sum(r.spec.max_new for r in win) / args.seconds
        print(json.dumps({
            "rate_per_s": rate, "offered_tokens_per_s": offered,
            "tokens_per_s": e2e["tokens_per_s"],
            "ttft_p50_s": e2e["ttft_p50_s"], "ttft_p90_s": e2e["ttft_p90_s"],
            "itl_p99_ms": e2e["itl_p99_ms"],
            "ttft_p90_first_half_s": percentile(ttft[0], 90),
            "ttft_p90_second_half_s": percentile(ttft[1], 90),
            "unfinished": sum(1 for r in win if not r.finished)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
