"""Readings that a cell's correctness limit is set from: in one process, a
short run of the cell on each seed (the timed path at the cell's own
sizes and load), each followed by the reference and by the control, the
same reference with every linear layer's operands rounded to float8.

  python3 perfbench/calibrate.py --workload danube-chat \
      --seeds 11,12,13 --seconds 10

Prints one JSON line per seed, then the lower reading (the largest widest
gap of the program), the upper one (the smallest of the control) and
their ratio. The benchmark's own runs never compute the control.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    from perfbench import driver, spec
    devices = driver.start_jax()
    if devices[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    bench = spec.Bench()
    prog, ctl = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = driver.run_cell(bench, args.workload, seed, args.seconds,
                              False, time.monotonic(), devices,
                              control=True)
        g = out["check"]["widest_logit_gap"]["value"]
        prog.append(g)
        ctl.append(out["control_gap"])
        print(json.dumps({"seed": seed, "widest_logit_gap": g,
                          "control_gap": out["control_gap"],
                          "attempted": out["attempted"],
                          "failed": out["failed"]}), flush=True)
    lower, upper = max(prog), min(ctl)
    print(json.dumps({"lower": lower, "upper": upper,
                      "ratio": upper / lower if lower else None}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
