"""Chip smoke test: the serving main path at full width on a TPU.

Serves h2o-danube-1.8b at its published width and depth (24 layers,
d_model 2560, 32 heads / 8 KV heads, head_dim 80, random weights drawn from
``--seed``) through ``LLMService`` -> ``PagedEngine``, built by
``repro.launch.serve.build_engine``, and checks what comes out. Everything
runs in this one process, which holds every chip it uses.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # four replicas, one per chip

One chip: the Pallas decode kernel against the pure-jnp reference at the
model's decode shapes, then eight requests (prompts of 128-3000 tokens, so
chunked prefill runs past the 2048-token budget; one prompt shares a
mid-page prefix with an earlier one, so the radix cache hits and the shared
page is copied on write), greedy, 32 new tokens each, once on the reference
decode path and once on the kernel path. Four chips: the same requests,
one at a time, on one engine on one chip and then on four replicas behind
``RouterBackend(policy="prefix_affinity", prefix_share=True)``; every
request's tokens must match.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or when any check fails, the script exits nonzero and prints
no such line. Times printed on the way are host wall seconds, compilation
included; none is a device measurement.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "h2o-danube-1.8b"
PAGE_SIZE = 16
NUM_PAGES = 2048      # 2.0 GB of bf16 KV per engine at full width
MAX_SLOTS = 8
MAX_NEW = 32
WAVE_1 = (3000, 1800, 600, 128)
WAVE_2 = (2600, 2300, 900, 256)   # the 2600 starts with WAVE_1[0][:2005]
SHARED = 2005                      # mid-page: 125 full pages + 5 tokens
# kernel vs reference, per output element in units of sum_i p_i |v_i| (the
# attention-weighted mean of |v|): the chip's default matmul precision
# rounds each side's softmax weights p_i to bf16 (2^-9 relative, so at most
# 2^-9 of that sum each), and each side rounds its output o to bf16, which
# two near-equal values can straddle (one ulp, at most 2^-7 |o|, and |o| is
# at most that sum): 2^-8 + 2^-7 in all. A wrong page, mask or window is
# off by the output's own scale, i.e. by ~1
KERNEL_TOL = 2.0 ** -6


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")


def make_prompts(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    wave1 = [rng.integers(0, vocab, n).tolist() for n in WAVE_1]
    wave2 = [rng.integers(0, vocab, n).tolist() for n in WAVE_2]
    wave2[0][:SHARED] = wave1[0][:SHARED]
    return wave1, wave2


def engine_config(use_kernel: bool):
    from repro.serving.engine import EngineConfig
    return EngineConfig(num_pages=NUM_PAGES, page_size=PAGE_SIZE,
                        max_slots=MAX_SLOTS, use_kernel=use_kernel,
                        enable_prefix_cache=True)


def check_outputs(outs, prompts, vocab: int, label: str) -> None:
    for out, prompt in zip(outs, prompts):
        where = f"{label} request {out.request_id} ({len(prompt)} tokens)"
        check(out.finish_reason in ("length", "stop"),
              f"{where} finished with {out.finish_reason!r}")
        check(out.finish_reason != "length" or out.n_generated == MAX_NEW,
              f"{where} stopped for length after {out.n_generated} tokens")
        check(all(0 <= t < vocab for t in out.token_ids),
              f"{where} produced a token outside the vocabulary")
        lps = out.samples[0].token_logprobs
        check(lps is not None and len(lps) == out.n_generated
              and bool(np.all(np.isfinite(lps))),
              f"{where} has missing or non-finite logprobs")


def release() -> None:
    """Free the engines of a finished phase: the jitted steps take the
    engine as a static argument, so JAX's caches keep it (and its HBM)
    alive until they are cleared."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


def kernel_vs_reference(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    b, h, hkv, dh = MAX_SLOTS, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pages_per_seq = cfg.max_seq_len // PAGE_SIZE
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, h, dh), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (NUM_PAGES + 1, PAGE_SIZE, hkv, dh),
                           jnp.bfloat16)
    vp = jax.random.normal(ks[2], kp.shape, jnp.bfloat16)
    bt = jax.random.randint(ks[3], (b, pages_per_seq), 0, NUM_PAGES + 1)
    lens = jax.random.randint(ks[4], (b,), 1, pages_per_seq * PAGE_SIZE + 1)
    for window in (None, cfg.sliding_window):
        got = np.asarray(ops.paged_attention(
            q, kp, vp, bt, lens, page_size=PAGE_SIZE, window=window),
            np.float32)
        want, scale = (np.asarray(ref.paged_attention_ref(
            q, kp, v, bt, lens, page_size=PAGE_SIZE, window=window),
            np.float32) for v in (vp, jnp.abs(vp)))
        diff = np.abs(got - want)
        rel = float(np.max(diff / scale))
        print(f"kernel vs reference (window={window}): max abs err "
              f"{float(diff.max())!r}, scaled err {rel!r} "
              f"(bound {KERNEL_TOL!r})", flush=True)
        check(rel <= KERNEL_TOL,
              f"paged_attention kernel off the reference by {rel} "
              f"(window={window})")


def serve_one_chip(cfg, use_kernel: bool, seed: int, wave1, wave2):
    from repro.launch.serve import build_engine
    from repro.serving.api import LLMService, SamplingParams
    from repro.serving.engine import PagedEngine
    label = "kernel" if use_kernel else "reference"
    t0 = time.monotonic()
    eng = build_engine(cfg, engine_config(use_kernel), seed=seed)
    svc = LLMService(eng)
    sp = SamplingParams(temperature=0.0, max_new_tokens=MAX_NEW)
    outs = svc.generate(wave1, sp) + svc.generate(wave2, sp)
    prompts = wave1 + wave2
    check_outputs(outs, prompts, cfg.vocab_size, label)
    shared = outs[len(wave1)]
    check(shared.metrics.num_cached_tokens >= SHARED - SHARED % PAGE_SIZE,
          f"{label}: the shared-prefix prompt reused only "
          f"{shared.metrics.num_cached_tokens} cached tokens")
    for out, prompt in zip(outs, prompts):
        print(f"  {label} request {out.request_id}: prompt {len(prompt)}, "
              f"cached {out.metrics.num_cached_tokens}, "
              f"{out.n_generated} tokens ({out.finish_reason}), "
              f"first {out.token_ids[:6]}", flush=True)
    print(f"{label} decode path: {len(outs)} requests served, "
          f"{eng.iterations} iterations, prefix-cache hit rate "
          f"{eng.prefix_cache.hit_rate!r}, "
          f"{time.monotonic() - t0:.1f} s wall", flush=True)
    if use_kernel:
        n = MAX_SLOTS
        zeros = np.zeros(n, np.int32)
        hlo = PagedEngine._decode_fn.lower(
            eng, eng.params, eng.k_pages, eng.v_pages, zeros, zeros,
            np.zeros((n, eng.max_pages_per_seq), np.int32),
            zeros).compile().as_text()
        check("tpu_custom_call" in hlo,
              "the compiled kernel decode step holds no Mosaic call")
        print("kernel decode step: compiled with a Mosaic tpu_custom_call",
              flush=True)
    return [o.token_ids for o in outs]


def serve_sequentially(svc, prompts):
    from repro.serving.api import SamplingParams
    sp = SamplingParams(temperature=0.0, max_new_tokens=MAX_NEW)
    return [svc.generate([p], sp)[0] for p in prompts]


def one_chip(cfg, seed: int) -> None:
    kernel_vs_reference(cfg, seed)
    wave1, wave2 = make_prompts(cfg.vocab_size, seed)
    ref_tokens = serve_one_chip(cfg, False, seed, wave1, wave2)
    release()
    kernel_tokens = serve_one_chip(cfg, True, seed, wave1, wave2)
    same = sum(a == b for a, b in zip(ref_tokens, kernel_tokens))
    print(f"reference and kernel decode paths: {same}/{len(ref_tokens)} "
          f"requests token-identical (greedy; bf16 near-ties may differ)",
          flush=True)


def four_chips(cfg, seed: int, devices) -> None:
    from repro.launch.serve import build_engine
    from repro.serving.api import LLMService
    from repro.serving.router import RouterBackend
    wave1, wave2 = make_prompts(cfg.vocab_size, seed)
    prompts = wave1 + wave2
    t0 = time.monotonic()
    single = LLMService(build_engine(cfg, engine_config(False), seed=seed,
                                     device=devices[0]))
    want = serve_sequentially(single, prompts)
    check_outputs(want, prompts, cfg.vocab_size, "one engine")
    print(f"one engine on {devices[0]}: {len(want)} requests, "
          f"{time.monotonic() - t0:.1f} s wall", flush=True)
    del single
    release()

    t0 = time.monotonic()
    replicas = [build_engine(cfg, engine_config(False), seed=seed, device=d)
                for d in devices[:4]]
    pool_devices = [e.k_pages.devices() | e.v_pages.devices()
                    for e in replicas]
    print(f"replica pool devices: {pool_devices}", flush=True)
    check(all(len(d) == 1 for d in pool_devices)
          and len(set().union(*pool_devices)) == 4,
          "the four replicas' pools are not on four distinct devices")
    router = RouterBackend(replicas, policy="prefix_affinity",
                           prefix_share=True, share_mode="copy")
    got = serve_sequentially(LLMService(router), prompts)
    check_outputs(got, prompts, cfg.vocab_size, "router")
    for g, w, p in zip(got, want, prompts):
        print(f"  request {g.request_id}: prompt {len(p)} on instance "
              f"{g.metrics.instance_id}, cached "
              f"{g.metrics.num_cached_tokens}, identical "
              f"{g.token_ids == w.token_ids}", flush=True)
        check(g.token_ids == w.token_ids,
              f"router request {g.request_id} differs from one engine")
    print(f"router over 4 replicas: {len(got)} requests token-identical to "
          f"one engine, placed {router.requests_placed}, "
          f"{time.monotonic() - t0:.1f} s wall", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel check + both decode paths on one chip; "
                         "4: four replicas behind the router vs one engine")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    print(f"devices: {devices}", flush=True)
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices only",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    t0 = time.monotonic()
    if args.chips == 1:
        one_chip(cfg, args.seed)
    else:
        four_chips(cfg, args.seed, devices)
    print(f"all checks passed in {time.monotonic() - t0:.1f} s wall",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
