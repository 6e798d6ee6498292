"""Run one cell of the chip benchmark once.

  python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout, builds the cell's
configuration with weights drawn from ``--seed``, warms up every program
the cell's traffic reaches, serves a lead-in and then ``--seconds`` of the
traffic through ``LLMService``, drains, and re-computes a sample of the
served tokens with the plain reference. The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones
from a profiler trace of part of the window), ``device``, and last
``check``, the numbers compared with their limits. Without a TPU, or with
fewer chips than the cell asks for, it exits nonzero and prints no result.
JAX's compilation cache is kept in ``.jax_cache/`` of the checkout.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench import driver, spec
    bench = spec.Bench()
    cell = bench.cell(args.workload)
    devices = driver.start_jax()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices only",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    out = driver.run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START, devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
