"""End-to-end serving engine: continuous batching on a real model must match
per-request sequential decoding exactly (greedy)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.scheduling.request import Request
from repro.models import Model
from repro.serving.api import SamplingParams
from repro.serving.engine import EngineConfig, PagedEngine


@pytest.fixture(scope="module")
def model_setup():
    cfg = smoke_config("h2o-danube-1.8b")
    cfg = dataclasses.replace(cfg, sliding_window=None)
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def model_setup_f32():
    """Float32 everywhere (params, KV pages, logits): the engine and the
    ring-cache oracle agree bit-for-bit well past argmax resolution, so
    greedy comparisons are exact instead of flaky on bf16 near-ties."""
    cfg = smoke_config("h2o-danube-1.8b")
    cfg = dataclasses.replace(cfg, sliding_window=None, dtype="float32",
                              logits_fp32=True)
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _oracle(model, params, cfg, prompt, n):
    tokens = jnp.asarray(prompt, jnp.int32)[None]
    logits, caches = model.prefill(params, tokens, seq_capacity=128)
    tok = int(jnp.argmax(logits[0]))
    out = [tok]
    pos = len(prompt)
    while len(out) < n:
        lg, caches = model.decode_step(params, jnp.array([[tok]], jnp.int32),
                                       jnp.array([pos], jnp.int32), caches)
        tok = int(jnp.argmax(lg[0]))
        out.append(tok)
        pos += 1
    return out


def _oracle_next_logits(model, params, tokens):
    """Next-token logits after feeding ``tokens`` (prefill last position)."""
    logits, _ = model.prefill(params, jnp.asarray(tokens, jnp.int32)[None],
                              seq_capacity=128)
    return np.asarray(logits[0])


def test_engine_matches_sequential_oracle(model_setup):
    cfg, model, params = model_setup
    eng = PagedEngine(cfg, params, EngineConfig(num_pages=64, page_size=8,
                                                max_slots=4))
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(5):
        plen = int(rng.integers(3, 12))
        reqs.append(Request(i, 0.0,
                            rng.integers(0, cfg.vocab_size, plen).tolist(),
                            max_new_tokens=int(rng.integers(2, 7))))
        eng.add_request(reqs[-1])
    eng.run_to_completion()
    for r in reqs:
        want = _oracle(model, params, cfg, r.prompt, len(r.full_output))
        assert r.full_output == want, f"req {r.request_id}"


def test_bf16_divergence_is_argmax_tie_artifact(model_setup, model_setup_f32):
    """ROADMAP follow-up: the rare engine-vs-oracle greedy divergence under
    bf16 is an argmax (near-)tie artifact, not a numerics bug.

    Short (3-token) prompts are replayed on the bf16 engine and the bf16
    oracle. Wherever the two streams first disagree, the oracle's own bf16
    logits at that step must rate the two winners within ONE bf16 ulp —
    i.e. the candidates are indistinguishable at bf16 resolution, and the
    two (both correct) implementations merely resolve the tie through
    different accumulation orders. With float32 compute the same prompts
    must match token-for-token (see model_setup_f32)."""
    cfg, model, params = model_setup
    cfg32, model32, params32 = model_setup_f32
    rng = np.random.default_rng(42)
    prompts = [rng.integers(0, cfg.vocab_size, 3).tolist() for _ in range(8)]
    n_new = 6

    eng = PagedEngine(cfg, params, EngineConfig(num_pages=64, page_size=8,
                                                max_slots=4))
    reqs = [Request(i, 0.0, list(p), max_new_tokens=n_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    eng.run_to_completion()

    for r in reqs:
        got = r.full_output
        want = _oracle(model, params, cfg, r.prompt, n_new)
        if got == want:
            continue
        i = next(k for k, (a, b) in enumerate(zip(got, want)) if a != b)
        # both streams share the context up to the divergence point; the
        # bf16 logits there must rate the winners within one ulp (bf16 has
        # 8 mantissa bits -> ulp ~= magnitude * 2^-8; allow 2^-7 for the
        # boundary case spanning an exponent step)
        ctx = r.prompt + want[:i]
        lg = _oracle_next_logits(model, params, ctx)
        gap = abs(float(lg[got[i]]) - float(lg[want[i]]))
        ulp = float(np.abs(lg).max()) * 2.0 ** -7
        assert gap <= ulp, (
            f"req {r.request_id}: bf16 divergence at step {i} is NOT a "
            f"near-tie (logit gap {gap} > one bf16 ulp {ulp}) — real "
            f"numerics bug, not a tie artifact")

    # float32: tie-free at argmax resolution — same prompts, exact match
    eng32 = PagedEngine(cfg32, params32, EngineConfig(num_pages=64,
                                                      page_size=8,
                                                      max_slots=4))
    reqs32 = [Request(i, 0.0, list(p), max_new_tokens=n_new)
              for i, p in enumerate(prompts)]
    for r in reqs32:
        eng32.add_request(r)
    eng32.run_to_completion()
    for r in reqs32:
        want = _oracle(model32, params32, cfg32, r.prompt, n_new)
        assert r.full_output == want, f"req {r.request_id} (float32)"
    # (bf16 divergence is rare: zero diverging prompts in this sample is
    # fine — the float32 half still proves the comparison is exact)


@pytest.mark.parametrize("window,prompts,max_new", [
    # global attention: one request, the other slot never active
    (None, [[5, 9, 2, 7]], [3]),
    # the 64-token sliding window with the context past it; the second
    # request finishes first, so its slot sits inactive in later steps
    (64, [list(range(3, 73)), [11, 4, 6]], [5, 1]),
])
def test_engine_pallas_kernel_path(model_setup_f32, window, prompts,
                                   max_new):
    """Same engine with the Pallas paged-attention kernel (interpret)."""
    cfg, model, params = model_setup_f32
    if window is not None:
        cfg = dataclasses.replace(cfg, sliding_window=window)
        model = Model(cfg, remat=False)
    eng = PagedEngine(cfg, params, EngineConfig(num_pages=32, page_size=8,
                                                max_slots=2, use_kernel=True))
    reqs = [Request(i, 0.0, p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, max_new))]
    for r in reqs:
        eng.add_request(r)
    eng.run_to_completion()
    for r in reqs:
        assert r.full_output == _oracle(model, params, cfg, r.prompt,
                                        r.max_new_tokens)


def test_engine_kernel_default_follows_device(model_setup):
    """``use_kernel=None`` takes the kernel only where the pools live on a
    TPU: on a CPU device it resolves to the XLA gather."""
    cfg, model, params = model_setup
    eng = PagedEngine(cfg, params, EngineConfig(num_pages=16, page_size=8,
                                                max_slots=2))
    assert eng.device.platform == "cpu"
    assert eng.use_kernel is False
    forced = PagedEngine(cfg, params, EngineConfig(
        num_pages=16, page_size=8, max_slots=2, use_kernel=True))
    assert forced.use_kernel is True


def test_engine_counts_live_decode_pages(model_setup_f32):
    """With telemetry on, the engine counts the pages each decode step's
    active slots hold inside their context and window, against the block
    table's width."""
    cfg, model, params = model_setup_f32
    cfg = dataclasses.replace(cfg, sliding_window=64)
    eng = PagedEngine(cfg, params, EngineConfig(
        num_pages=32, page_size=8, max_slots=2, enable_telemetry=True))
    assert eng.max_pages_per_seq == 32
    eng.add_request(Request(0, 0.0, list(range(3, 73)), max_new_tokens=4))
    eng.add_request(Request(1, 0.0, [4, 5, 6], max_new_tokens=2))
    eng.run_to_completion()
    # decode contexts: request 0 at 71, 72, 73 tokens (window 64: pages
    # 0-8, 1-8, 1-9 -> 9, 8, 9 live); request 1 at 4 tokens in the first
    # step only (page 0 -> 1 live)
    c = eng.metrics.counters
    assert c["decode_live_pages"] == 9 + 1 + 8 + 9
    assert c["decode_table_pages"] == (2 + 1 + 1) * 32


def test_engine_counts_sampler_calls_that_sort(model_setup):
    """``sample_calls`` counts every sampler call; ``sample_sorted_calls``
    only those in which some row samples, the calls that take the filter's
    sort. Greedy traffic leaves the second at zero."""
    cfg, model, params = model_setup

    def run(sampled):
        eng = PagedEngine(cfg, params, EngineConfig(
            num_pages=32, page_size=8, max_slots=2, enable_telemetry=True))
        eng.add_request(Request(0, 0.0, [3, 4, 5, 6, 7], max_new_tokens=5))
        eng.add_request(Request(1, 0.0, [8, 9, 10], max_new_tokens=2,
                                sampling=sampled))
        eng.run_to_completion()
        return eng.metrics.counters

    greedy = run(None)
    assert greedy["sample_calls"] > 0
    assert greedy["sample_sorted_calls"] == 0
    mixed = run(SamplingParams(temperature=0.8, top_p=0.9, seed=5,
                               max_new_tokens=2))
    assert mixed["sample_calls"] == greedy["sample_calls"]
    # request 1's prefill and decode sort; request 0's later decodes do not
    assert 0 < mixed["sample_sorted_calls"] < mixed["sample_calls"]


def test_engine_swa_arch(model_setup):
    cfg = smoke_config("h2o-danube-1.8b")  # window=64 active
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(1))
    eng = PagedEngine(cfg, params, EngineConfig(num_pages=64, page_size=8,
                                                max_slots=2))
    r = Request(0, 0.0, list(np.random.default_rng(2).integers(
        0, cfg.vocab_size, 10)), max_new_tokens=4)
    eng.add_request(r)
    eng.run_to_completion()
    want = _oracle(model, params, cfg, r.prompt, 4)
    assert r.full_output == want


def test_engine_continuous_batching_admits_late_request(model_setup_f32):
    # float32 compute: this test's 3-token prompt sits exactly on a bf16
    # argmax near-tie (top-2 logits one bf16 ulp apart), which the engine
    # and the ring-cache oracle legitimately break differently — the
    # pre-existing tier-1 flake recorded in ROADMAP, dissected by
    # test_bf16_divergence_is_argmax_tie_artifact. In float32 the
    # comparison is exact and the continuous-batching property under test
    # (late joiners don't perturb running requests) is checked tightly.
    cfg, model, params = model_setup_f32
    eng = PagedEngine(cfg, params, EngineConfig(num_pages=64, page_size=8,
                                                max_slots=4))
    r1 = Request(0, 0.0, [1, 2, 3], max_new_tokens=6)
    eng.add_request(r1)
    eng.step()  # r1 prefilled
    r2 = Request(1, 0.0, [4, 5], max_new_tokens=2)
    eng.add_request(r2)  # joins while r1 decodes
    eng.run_to_completion()
    assert r1.full_output == _oracle(model, params, cfg, r1.prompt, 6)
    assert r2.full_output == _oracle(model, params, cfg, r2.prompt, 2)


def test_engine_kv_utilization_reported(model_setup):
    cfg, model, params = model_setup
    eng = PagedEngine(cfg, params, EngineConfig(num_pages=64, page_size=8,
                                                max_slots=4))
    eng.add_request(Request(0, 0.0, [1] * 9, max_new_tokens=3))
    eng.step()
    util = eng.kv_utilization()
    assert 0.5 <= util <= 1.0  # 9 tokens in 2 pages of 8 = 0.5625


@pytest.mark.parametrize("lease", [False, True])
@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "deepseek-v2-236b"])
def test_step_programs_keep_their_names(arch, lease):
    """Both step programs lower in either attention flavor, with and
    without a zero-copy lease, as the modules ``jit__prefill_chunk_fn``
    and ``jit__decode_fn``: the benchmark finds the programs in a device
    trace by these names."""
    cfg = dataclasses.replace(smoke_config(arch), sliding_window=None)
    params = Model(cfg, remat=False).init(jax.random.PRNGKey(0))
    eng = PagedEngine(cfg, params, EngineConfig(num_pages=16, page_size=8,
                                                max_slots=4))
    n, r = eng.ecfg.max_slots, 8  # slots, borrowed tokens
    i32 = np.zeros(n, np.int32)
    remote_c = remote_d = None
    if lease:
        remote_c = (jnp.int32(r), *(jnp.zeros((cfg.num_layers, r)
                                              + p.shape[3:], p.dtype)
                                    for p in eng.pools))
        remote_d = (jnp.full(n, r, jnp.int32),
                    *(jnp.zeros((cfg.num_layers, n, r) + p.shape[3:],
                                p.dtype) for p in eng.pools))
    chunk = PagedEngine._prefill_chunk_fn.lower(
        eng, eng.params, eng.pools, jnp.zeros((1, 16), jnp.int32),
        jnp.zeros(2, jnp.int32), jnp.int32(0), jnp.int32(16), remote_c)
    decode = PagedEngine._decode_fn.lower(
        eng, eng.params, eng.pools, i32, i32,
        np.zeros((n, eng.max_pages_per_seq), np.int32), i32, remote_d)
    for low, name in ((chunk, "jit__prefill_chunk_fn"),
                      (decode, "jit__decode_fn")):
        assert low.as_text().startswith(f"module @{name} ")
