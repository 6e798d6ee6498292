"""The serving sampler on one chip: ``sampling.sample_batch`` as the engine
calls it (one jitted program per batch of slots), at the benchmark's slots x
vocabulary shapes.

  python tools/bench_sampler.py [--out result.json]
  JAX_PLATFORMS=cpu python tools/bench_sampler.py --tiny

Two shapes, float32 logits: 128 x 102400 (DeepSeek-V2's stage: 128 slots,
its whole vocabulary) and 16 x 32768 (Mistral-Large-2407, 16 slots). Three
batches each: every row greedy (the benchmark's traffic), every row at
temperature 0.8 with ``top_p`` 0.9, and the two alternating. Each case
times ``--reps`` calls on the host clock, each ending in
``block_until_ready``, after a warm-up call, and prints the median and the
quartiles in milliseconds per call, and a digest of the tokens and
logprobs (equal digests: bit-identical outputs), one JSON object per line.
``--tiny`` runs 8 x 1024 to rehearse the script without a chip: its times
are the CPU's and mean nothing. Run a copy of this file from another
checkout's ``tools/`` to time that checkout's sampler.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.models import sampling  # noqa: E402

SHAPES = ((128, 102400), (16, 32768))
BATCHES = ("greedy", "top_p", "mixed")


def inputs(n: int, v: int, batch: str, seed: int = 0):
    rng = np.random.default_rng(seed)
    logits = jax.device_put(
        (rng.standard_normal((n, v), np.float32) * 3.0))
    temp = np.full(n, 0.8, np.float32)
    if batch == "greedy":
        temp[:] = 0.0
    elif batch == "mixed":
        temp[::2] = 0.0
    top_p = np.full(n, 0.9, np.float32)
    seeds = np.arange(n, dtype=np.int32) + 1000
    steps = np.arange(n, dtype=np.int32) * 7
    return (logits, jnp.asarray(seeds), jnp.asarray(steps), jnp.asarray(temp),
            jnp.zeros(n, jnp.int32), jnp.asarray(top_p))


def time_case(fn, args, reps: int) -> dict:
    toks, lps = jax.block_until_ready(fn(*args))  # compile and warm
    digest = hashlib.sha1(np.asarray(toks).tobytes()
                          + np.asarray(lps).tobytes()).hexdigest()[:16]
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t))
    q1, med, q3 = statistics.quantiles(times, n=4)
    return {"ms_median": med, "ms_q1": q1, "ms_q3": q3, "reps": reps,
            "out_digest": digest}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    shapes = ((8, 1024),) if args.tiny else SHAPES
    dev = jax.devices()[0]
    fn = jax.jit(sampling.sample_batch)
    rows = []
    for n, v in shapes:
        for batch in BATCHES:
            row = {"n": n, "v": v, "batch": batch, "device": dev.device_kind,
                   **time_case(fn, inputs(n, v, batch), args.reps)}
            print(json.dumps(row), flush=True)
            rows.append(row)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
