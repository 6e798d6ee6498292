"""Paged decode attention on one chip: the Pallas kernel against the XLA
gather (``paged_decode_attention``), at the benchmark's widths.

  python tools/bench_paged_attention.py [--out result.json]
  JAX_PLATFORMS=cpu python tools/bench_paged_attention.py --tiny

Three cases, bf16, 16-token pages: h2o-danube-1.8b (32/8 heads, head_dim
80, 4097 pool pages, 1024-page tables, its 4096-token window) with ten chat
contexts around 1.2k tokens and six empty slots, then with every slot at
16k; Mistral-Large-2407's attention (96/8 heads, head_dim 128, 8193 pool
pages, 512-page tables) with sixteen contexts around 2.3k. Each case checks
the kernel against the reference, then times 24 chained calls (one per
layer) on the host clock, ending in ``block_until_ready``, and prints
milliseconds per call and the live K+V bytes read per second (the data,
not the HBM tiles' lane padding). ``--tiny`` runs one small case in
interpret mode, to rehearse the script without a chip: its times are the
CPU's and mean nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ref  # noqa: E402
from repro.kernels.paged_attention import live_pages, paged_attention  # noqa: E402
from repro.models.attention import paged_decode_attention  # noqa: E402

PAGE = 16
LAYERS = 24


def per_call_ms(fn, args, reps: int = 20) -> float:
    """Median host milliseconds per call over ``reps`` runs of ``LAYERS``
    chained calls (each call's query depends on the previous output)."""
    @jax.jit
    def run(q, *rest):
        def body(_, acc):
            qi = (q.astype(jnp.float32) + 1e-3 * acc).astype(q.dtype)
            return acc + fn(qi, *rest).astype(jnp.float32)
        return jax.lax.fori_loop(0, LAYERS, body,
                                 jnp.zeros(q.shape, jnp.float32))

    run(*args).block_until_ready()
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        run(*args).block_until_ready()
        times.append(time.perf_counter() - t)
    return 1e3 * float(np.median(times)) / LAYERS


def case(name, heads, kv_heads, head_dim, pool_pages, pages_per_seq, window,
         lens, interpret, out):
    b = len(lens)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, heads, head_dim), jnp.bfloat16)
    shape = (pool_pages, PAGE, kv_heads, head_dim)
    kp = jax.random.normal(ks[1], shape, jnp.bfloat16)
    vp = jax.random.normal(ks[2], shape, jnp.bfloat16)
    bt = jnp.asarray(np.random.default_rng(0).integers(
        0, pool_pages, (b, pages_per_seq)), jnp.int32)
    lens = np.asarray(lens, np.int32)
    _, count = live_pages(lens, PAGE, window)
    live_mb = int(count.sum()) * PAGE * kv_heads * head_dim * 2 * 2 / 1e6
    args = (q, kp, vp, bt, jnp.asarray(lens))

    def kernel(*a):
        return paged_attention(*a, page_size=PAGE, window=window,
                               interpret=interpret)

    def xla(*a):
        return paged_decode_attention(*a, page_size=PAGE, window=window)

    act = lens > 0
    err = float(np.abs(np.asarray(kernel(*args), np.float32) - np.asarray(
        ref.paged_attention_ref(*args, page_size=PAGE, window=window),
        np.float32))[act].max())
    k_ms, x_ms = per_call_ms(kernel, args), per_call_ms(xla, args)
    out[name] = dict(live_pages=int(count.sum()), live_kv_mb=live_mb,
                     kernel_err=err, kernel_ms=k_ms, xla_ms=x_ms)
    print(f"{name}: {int(count.sum())} live pages ({live_mb:.2f} MB of K+V);"
          f" kernel off the reference by {err!r}; kernel {k_ms:.4f} ms "
          f"({live_mb / k_ms:.1f} GB/s), XLA gather {x_ms:.4f} ms per call",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="one small case in interpret mode (no chip)")
    ap.add_argument("--out", help="write the numbers here as JSON")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if not args.tiny and dev.platform != "tpu":
        print("no TPU: pass --tiny to rehearse on the CPU", file=sys.stderr)
        return 1
    out = {"device": dev.device_kind}
    if args.tiny:
        case("tiny", 8, 2, 32, 65, 16, 40, [0, 17, 100, 256], True, out)
    else:
        rng = np.random.default_rng(1)
        chat = np.clip(np.round(np.exp(rng.normal(np.log(1200), 0.6, 16))),
                       64, 8192).astype(int)
        chat[10:] = 0
        case("danube.chat", 32, 8, 80, 4097, 1024, 4096, chat, False, out)
        case("danube.full", 32, 8, 80, 4097, 1024, 4096, [16384] * 16, False,
             out)
        batch = np.clip(np.round(np.exp(rng.normal(np.log(2300), 0.4, 16))),
                        600, 5000).astype(int)
        case("mistral.batch", 96, 8, 128, 8193, 512, None, batch, False, out)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
