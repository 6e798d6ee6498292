"""Chunked prefill as a first-class scheduler mode.

Covers the PR's acceptance criteria: token-identity of chunked vs monolithic
prefill on both backends (same first sampled token AND same KV state),
chunked *suffix* prefill after a radix-cache hit, token-level (mid-page)
cache hits through the partial-page COW, preemption mid-prefill resuming
cleanly, and the budget invariant (no iteration exceeds
``max_tokens_per_iter`` under the chunking policies).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.paging import BlockAllocator
from repro.core.prefixcache import PrefixCache
from repro.core.scheduling import (CHUNK_POLICIES, IterationScheduler, Phase,
                                   Request)
from repro.serving.engine import EngineConfig, PagedEngine
from repro.serving.simulator import SimBackend, make_workload, simulate_paged

PS = 8  # page size used throughout


def _drive(s, *reqs, max_iters=500, start_it=0.0):
    for r in reqs:
        s.add_request(r)
    it = start_it
    for _ in range(max_iters):
        plan = s.schedule()
        if plan.empty and not s.waiting:
            return it
        for r in plan.prefill + plan.decode:
            r.output.append(0)
        s.complete_iteration(plan, it)
        it += 1.0
    raise AssertionError("scheduler did not drain")


# -- scheduler: chunk composition ----------------------------------------------

def test_long_prompt_chunks_across_iterations():
    a = BlockAllocator(64, PS)
    s = IterationScheduler(a, max_tokens_per_iter=16)
    r = Request(0, 0.0, list(range(40)), max_new_tokens=2)
    s.add_request(r)

    plan = s.schedule()  # admission: first chunk
    assert [(c.start, c.length) for c in plan.chunks] == [(0, 16)]
    assert not plan.prefill and not plan.decode
    assert r.prefilled_len == 16 and r.phase == Phase.INITIATION
    s.complete_iteration(plan, 0.0)
    assert r.first_token_time is None, "TTFT must span all chunks"

    plan = s.schedule()  # continuation
    assert [(c.start, c.length) for c in plan.chunks] == [(16, 16)]
    assert not plan.prefill and not plan.decode
    s.complete_iteration(plan, 1.0)

    plan = s.schedule()  # final chunk: the request samples its first token
    assert [(c.start, c.length) for c in plan.chunks] == [(32, 8)]
    assert plan.prefill == [r]
    r.output.append(0)
    s.complete_iteration(plan, 2.0)
    assert r.first_token_time == 2.0
    assert r.phase == Phase.INCREMENT

    plan = s.schedule()  # now it decodes
    assert plan.decode == [r] and not plan.chunks


def test_decode_first_piggybacks_decodes_with_chunks():
    """Sarathi stall-free: the running decode gets its token EVERY iteration
    while the long prompt prefills in leftover-budget chunks."""
    a = BlockAllocator(64, PS)
    s = IterationScheduler(a, max_tokens_per_iter=16,
                           chunk_policy="decode_first")
    short = Request(0, 0.0, list(range(4)), max_new_tokens=8)
    s.add_request(short)
    plan = s.schedule()
    short.output.append(0)
    s.complete_iteration(plan, 0.0)

    long = Request(1, 0.0, list(range(100, 145)), max_new_tokens=2)
    s.add_request(long)
    it = 1.0
    while long.prefilled_len < long.prompt_len:
        plan = s.schedule()
        assert short in plan.decode, \
            "decode must never stall behind the chunked prefill"
        assert plan.token_count() <= 16
        for r in plan.prefill + plan.decode:
            r.output.append(0)
        s.complete_iteration(plan, it)
        it += 1.0
        if short.phase == Phase.FINISHED:
            break
    # 45 tokens at 15/iter (budget 16 - 1 decode) = 3 iterations
    assert long.prefilled_len == long.prompt_len


def test_prefill_first_gives_budget_to_chunks():
    a = BlockAllocator(64, PS)
    s = IterationScheduler(a, max_tokens_per_iter=16,
                           chunk_policy="prefill_first")
    short = Request(0, 0.0, list(range(4)), max_new_tokens=8)
    s.add_request(short)
    plan = s.schedule()
    short.output.append(0)
    s.complete_iteration(plan, 0.0)

    long = Request(1, 0.0, list(range(100, 164)), max_new_tokens=2)
    s.add_request(long)
    plan = s.schedule()
    # the chunk takes the whole budget; the decode stalls this iteration
    assert [(c.start, c.length) for c in plan.chunks] == [(0, 16)]
    assert short not in plan.decode
    assert plan.token_count() == 16


def test_prefill_first_no_decode_in_final_chunk_iteration():
    """Under prefill_first the decode planner runs AFTER the chunk
    planners: a request whose final chunk runs this iteration must not be
    granted a decode token too (it samples its first token from the
    prefill logits and decodes NEXT iteration) — else max_new_tokens=1
    would emit two tokens at once."""
    a = BlockAllocator(64, PS)
    s = IterationScheduler(a, max_tokens_per_iter=32,
                           chunk_policy="prefill_first")
    r = Request(0, 0.0, list(range(8)), max_new_tokens=1)
    s.add_request(r)
    plan = s.schedule()
    assert plan.prefill == [r]
    assert r not in plan.decode, \
        "final-chunk request must not decode in the same iteration"
    # end to end on the sim: exactly one token comes out
    backend = SimBackend(num_blocks=100, block_size=PS,
                         chunk_policy="prefill_first")
    from repro.serving.api import LLMService
    svc = LLMService(backend)
    one = Request(0, 0.0, [], max_new_tokens=1, prompt_len=8)
    svc.submit_request(one)
    svc.drain()
    assert one.total_generated == 1


def test_monolithic_admits_over_budget_next_to_decodes():
    a = BlockAllocator(64, PS)
    s = IterationScheduler(a, max_tokens_per_iter=16,
                           chunk_policy="monolithic")
    short = Request(0, 0.0, list(range(4)), max_new_tokens=8)
    s.add_request(short)
    plan = s.schedule()
    short.output.append(0)
    s.complete_iteration(plan, 0.0)

    long = Request(1, 0.0, list(range(100, 140)), max_new_tokens=2)
    s.add_request(long)
    plan = s.schedule()
    # one giant prefill right next to the decode (the stall baseline)
    assert short in plan.decode
    assert [(c.start, c.length) for c in plan.chunks] == [(0, 40)]
    assert plan.prefill == [long]


def test_solo_waits_for_idle_engine():
    a = BlockAllocator(64, PS)
    s = IterationScheduler(a, max_tokens_per_iter=16, chunk_policy="solo")
    short = Request(0, 0.0, list(range(4)), max_new_tokens=3)
    s.add_request(short)
    plan = s.schedule()
    short.output.append(0)
    s.complete_iteration(plan, 0.0)

    long = Request(1, 0.0, list(range(100, 140)), max_new_tokens=2)
    s.add_request(long)
    it = 1.0
    while short.phase != Phase.FINISHED:
        plan = s.schedule()
        assert not plan.chunks, "legacy solo must wait for an idle engine"
        for r in plan.prefill + plan.decode:
            r.output.append(0)
        s.complete_iteration(plan, it)
        it += 1.0
    plan = s.schedule()  # idle now: the whole prompt runs alone
    assert [(c.start, c.length) for c in plan.chunks] == [(0, 40)]


def test_preempt_resets_prefill_progress():
    """The recompute policy restarts chunked prefill from the front: a
    preempted mid-prefill request re-enters waiting with zero progress."""
    a = BlockAllocator(64, PS)
    s = IterationScheduler(a, max_tokens_per_iter=8)
    long = Request(0, 0.0, list(range(40)), max_new_tokens=2)
    s.add_request(long)
    plan = s.schedule()
    s.complete_iteration(plan, 0.0)
    assert long.prefilled_len == 8  # one chunk in
    s._preempt(long)
    assert long.prefilled_len == 0
    assert long in s.waiting and long not in s.running
    assert a.num_free == 64 and not a.refcount


def test_preemption_mid_prefill_resumes_and_completes():
    """Engineered crunch: a decode needs a page while a long prompt is one
    token short of finishing its chunked prefill — the mid-prefill request
    is the victim, restarts from the front on re-admission, and still
    completes with no block leak."""
    # pool 11 pages x 8; budget 8; chunk_min 4 so the long prompt chunks at
    # 7 tokens/iter next to the short request's decode
    a = BlockAllocator(11, PS)
    s = IterationScheduler(a, max_tokens_per_iter=8, max_running=4,
                           prefill_chunk_min=4)
    short = Request(0, 0.0, list(range(14)), max_new_tokens=30)
    s.add_request(short)
    for it in range(3):  # chunks (0,8),(8,6) -> first token; then decode
        plan = s.schedule()
        for r in plan.prefill + plan.decode:
            r.output.append(0)
        s.complete_iteration(plan, float(it))
    assert short.phase == Phase.INCREMENT
    long = Request(1, 0.0, list(range(100, 164)), max_new_tokens=2)
    s.add_request(long)
    preempted_mid_prefill = False
    it = 100.0
    for _ in range(300):
        plan = s.schedule()
        if plan.empty and not s.waiting:
            break
        if long in plan.preempted and \
                long.prefilled_len < long.prompt_len:
            preempted_mid_prefill = True
        for r in plan.prefill + plan.decode:
            r.output.append(0)
        s.complete_iteration(plan, it)
        it += 1.0
    assert preempted_mid_prefill, "scenario must preempt the mid-prefill req"
    assert long.preemptions >= 1
    assert short.phase == Phase.FINISHED and long.phase == Phase.FINISHED
    assert short.total_generated == 30 and long.total_generated == 2
    assert a.num_free == 11 and not a.refcount


def test_prefill_backlog_tokens():
    a = BlockAllocator(64, PS)
    s = IterationScheduler(a, max_tokens_per_iter=16)
    s.add_request(Request(0, 0.0, list(range(40)), max_new_tokens=2))
    s.add_request(Request(1, 0.0, list(range(24)), max_new_tokens=2))
    assert s.prefill_backlog_tokens() == 64  # both queued
    plan = s.schedule()  # req 0 admitted, 16/40 prefilled; req 1 queued
    s.complete_iteration(plan, 0.0)
    assert s.prefill_backlog_tokens() == (40 - 16) + 24


def test_bad_chunk_policy_rejected():
    a = BlockAllocator(8, PS)
    with pytest.raises(ValueError, match="chunk_policy"):
        IterationScheduler(a, chunk_policy="nope")


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000),
       st.sampled_from(["decode_first", "prefill_first"]))
def test_budget_never_exceeded_property(seed, policy):
    """Property: under the chunking policies no iteration plans more than
    ``max_tokens_per_iter`` flattened tokens, and everything still drains."""
    rng = np.random.default_rng(seed)
    a = BlockAllocator(64, PS)
    budget = int(rng.integers(8, 40))
    s = IterationScheduler(a, max_running=6, max_tokens_per_iter=budget,
                           chunk_policy=policy)
    reqs = [Request(i, 0.0, list(range(int(rng.integers(1, 90)))),
                    max_new_tokens=int(rng.integers(1, 12)))
            for i in range(5)]
    for r in reqs:
        s.add_request(r)
    for it in range(800):
        plan = s.schedule()
        if plan.empty and not s.waiting:
            break
        assert plan.token_count() <= budget, \
            f"iteration exceeded the token budget under {policy}"
        for r in plan.prefill + plan.decode:
            r.output.append(0)
        s.complete_iteration(plan, float(it))
    assert all(r.phase == Phase.FINISHED for r in reqs)
    assert a.num_free == 64 and not a.refcount


# -- token-level (mid-page) radix hits -----------------------------------------

def test_match_partial_frontier():
    a = BlockAllocator(16, PS)
    c = PrefixCache(a)
    toks = list(range(24))  # 3 full pages
    from repro.core.paging import BlockTable
    t = BlockTable()
    a.append_tokens(t, 24)
    t_blocks = list(t.blocks)
    c.insert(toks, t.blocks)
    # diverges 4 tokens into page 3: full match 2 pages + partial run of 4
    probe = toks[:20] + [777, 778]
    path = c.match(probe, max_tokens=len(probe) - 1)
    assert len(path) == 2
    partial = c.match_partial(probe, path, max_tokens=len(probe) - 1)
    assert partial is not None
    node, run = partial
    assert run == 4 and node.block == t_blocks[2]
    # page-aligned divergence -> no partial
    probe2 = toks[:16] + [888] * 8
    path2 = c.match(probe2)
    assert c.match_partial(probe2, path2) is None
    # token_level=False restores page-aligned-only behavior
    c2 = PrefixCache(a, token_level=False)
    assert c2.match_partial(probe, path) is None
    a.free_table(t)


def test_scheduler_token_level_hit_cows_boundary_page():
    """Admission with a mid-page hit locks the boundary node and the first
    suffix write COWs it — the tree's page is untouched, the request gets
    its own copy, and nothing leaks."""
    a = BlockAllocator(64, PS)
    c = PrefixCache(a)
    s = IterationScheduler(a, prefix_cache=c, max_tokens_per_iter=999)
    r1 = Request(0, 0.0, list(range(24)), max_new_tokens=2)
    _drive(s, r1)
    tree_path = c.match(list(range(24)))
    boundary_block = tree_path[2].block

    r2 = Request(1, 0.0, list(range(20)) + [777] * 12, max_new_tokens=2)
    s.add_request(r2)
    plan = s.schedule()
    assert r2.num_cached_tokens == 20, \
        "token-level match must recover the 4 mid-page tokens"
    assert [(ch.start, ch.length) for ch in plan.chunks] == [(20, 12)]
    table = s.tables[r2.request_id]
    cow_copy = table.blocks[2]  # (free_table clears the list at finish)
    # the boundary page was COW-copied for r2's divergent suffix
    assert (boundary_block, cow_copy) in plan.cow
    assert cow_copy != boundary_block
    assert c.match(list(range(24)))[2].block == boundary_block, \
        "the tree's own branch must keep its original page"
    r2.output.append(0)
    s.complete_iteration(plan, 10.0)
    _drive(s, max_iters=50, start_it=11.0)
    assert r2.phase == Phase.FINISHED
    # both divergent boundary pages are now cached (post-split siblings)
    assert c.match(list(range(20)) + [777] * 4)[2].block == cow_copy
    c.clear()
    assert a.num_free == 64 and not a.refcount


def test_rescinded_victim_leaves_no_stale_cow_pairs():
    """A request admitted with a partial-page COW and preempted later in
    the SAME schedule() call must take its pending COW pair out of the
    plan: its fresh target block is freed and may be reallocated before
    the engine applies plan.cow — a stale copy would clobber the new
    owner's page."""
    # decode_reserve=False: the reserve (PR 5) forecloses exactly this
    # admit-then-preempt-same-iteration scenario; disable it so the rescind
    # machinery (which still guards decode-vs-decode preemptions and COW
    # shortfalls) keeps its regression coverage
    a = BlockAllocator(10, PS)
    c = PrefixCache(a)
    s = IterationScheduler(a, prefix_cache=c, max_tokens_per_iter=8192,
                           chunk_policy="prefill_first",
                           decode_reserve=False)
    r0 = Request(0, 0.0, list(range(24)), max_new_tokens=2)
    _drive(s, r0)  # seeds the tree with 3 pages
    r1 = Request(1, 0.0, list(range(1000, 1006)), max_new_tokens=20)
    r3 = Request(3, 0.0, list(range(2000, 2006)), max_new_tokens=20)
    s.add_request(r1)
    s.add_request(r3)
    it = 10.0
    # lockstep decode until each table stores exactly 16 tokens (the first
    # output token comes from prefill logits without a KV append, so stored
    # tokens lag n_generated by one): the NEXT decode needs a third page
    while True:
        plan = s.schedule()
        for r in plan.prefill + plan.decode:
            r.output.append(0)
        s.complete_iteration(plan, it)
        it += 1.0
        if s.tables[1].num_tokens >= 16:
            break
    # r2: token-level hit (2 full pages + 4 mid-page tokens) -> its
    # admission generates a partial-page COW pair. The same iteration, both
    # decoders cross a page boundary; the second finds no free page and
    # preempts the just-admitted r2.
    r2 = Request(2, 0.0, list(range(20)) + [777] * 8, max_new_tokens=2)
    s.add_request(r2)
    plan = s.schedule()
    assert r2 in plan.preempted and r2 in s.waiting
    assert r1 in plan.decode and r3 in plan.decode
    assert r2 not in plan.prefill and not plan.chunks
    assert plan.cow == [], \
        "rescinded victim's pending COW pair must not reach the engine"
    for r in plan.prefill + plan.decode:
        r.output.append(0)
    s.complete_iteration(plan, it)
    _drive(s, max_iters=200, start_it=it + 1)
    assert all(r.phase == Phase.FINISHED for r in (r1, r2, r3))
    c.clear()
    assert a.num_free == 10 and not a.refcount


def test_partial_hit_rollback_under_memory_pressure():
    """If admission cannot get the pages it needs, a locked partial path
    (including the boundary node) unwinds cleanly."""
    a = BlockAllocator(4, PS)
    c = PrefixCache(a)
    s = IterationScheduler(a, prefix_cache=c, max_tokens_per_iter=999,
                           watermark=0.0)
    r1 = Request(0, 0.0, list(range(20)), max_new_tokens=2)
    _drive(s, r1)  # 3 pages; all stay in the tree (2 full inserted + tail)
    # a huge prompt sharing 20 tokens: partial hit, but the 6 pages it needs
    # cannot be found even after eviction of unpinned pages
    r2 = Request(1, 0.0, list(range(20)) + [5] * 28, max_new_tokens=2)
    s.add_request(r2)
    s.schedule()
    # r2 was not admitted and its locks unwound: every page either free or
    # exclusively tree-owned
    assert r2.request_id not in s.tables
    for node in c.match(list(range(16))):
        assert node.pin_count == 0
    c.clear()
    assert a.num_free == 4 and not a.refcount


# -- engine: token identity (acceptance) ---------------------------------------

@pytest.fixture(scope="module")
def model_setup_f32():
    from repro.configs import smoke_config
    from repro.models import Model
    cfg = smoke_config("h2o-danube-1.8b")
    cfg = dataclasses.replace(cfg, sliding_window=None, dtype="float32",
                              logits_fp32=True)
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _gathered_prompt_kv(eng, rid, plen):
    """(L, plen, Hkv, Dh) K/V actually stored for the request's prompt."""
    table = eng.scheduler.tables[rid]
    npg = -(-plen // eng.ecfg.page_size)
    idx = jnp.asarray(table.blocks[:npg], jnp.int32)
    L = eng.cfg.num_layers
    k = np.asarray(eng.k_pages[:, idx]).reshape(L, -1, eng.cfg.num_kv_heads,
                                                eng.cfg.head_dim)[:, :plen]
    v = np.asarray(eng.v_pages[:, idx]).reshape(L, -1, eng.cfg.num_kv_heads,
                                                eng.cfg.head_dim)[:, :plen]
    return k, v


def test_engine_chunked_equals_monolithic(model_setup_f32):
    """ACCEPTANCE: a chunked prefill produces exactly the same first sampled
    token and KV state as a monolithic prefill (float32: comparisons are
    exact at argmax resolution), and the full decode matches."""
    cfg, model, params = model_setup_f32
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 42).tolist()

    def build(budget):
        eng = PagedEngine(cfg, params, EngineConfig(
            num_pages=64, page_size=PS, max_slots=4,
            max_tokens_per_iter=budget))
        r = Request(0, 0.0, list(prompt), max_new_tokens=5)
        eng.add_request(r)
        # step until the first token exists (the final chunk's iteration)
        iters = 0
        while not r.output:
            eng.step()
            iters += 1
        return eng, r, iters

    mono_eng, mono_r, mono_iters = build(budget=1000)
    chunk_eng, chunk_r, chunk_iters = build(budget=16)
    assert mono_iters == 1 and chunk_iters == 3  # ceil(42/16) chunks

    # same first sampled token...
    assert chunk_r.output[0] == mono_r.output[0]
    # ...and the same prompt KV state, page layout aside
    km, vm = _gathered_prompt_kv(mono_eng, 0, len(prompt))
    kc, vc = _gathered_prompt_kv(chunk_eng, 0, len(prompt))
    # Bound for a float32 model: the two prefills sum attention over
    # different padded key lengths, so their f32 sums differ in the last
    # bits (4.3e-6 measured with f32 attention operands). The models'
    # attention rounds q, k and the softmax weights to bf16 (2^-8 steps),
    # and such a last-bit difference flips some of those roundings, which
    # the next layer's K/V inherit: 6.4e-5 measured. A wrong position, page
    # or mask moves K/V by O(0.1).
    np.testing.assert_allclose(kc, km, rtol=1e-5, atol=5e-4)
    np.testing.assert_allclose(vc, vm, rtol=1e-5, atol=5e-4)

    # the remaining decode is token-identical too
    mono_eng.run_to_completion()
    chunk_eng.run_to_completion()
    assert chunk_r.full_output == mono_r.full_output


def test_engine_chunked_suffix_after_prefix_hit(model_setup_f32):
    """A radix-cache hit followed by a long suffix: the suffix itself is
    chunked across iterations and the output matches a cold engine."""
    cfg, model, params = model_setup_f32
    rng = np.random.default_rng(12)
    shared = rng.integers(0, cfg.vocab_size, 2 * PS).tolist()
    suffix = rng.integers(0, cfg.vocab_size, 36).tolist()
    prompt2 = shared + suffix

    cold = PagedEngine(cfg, params, EngineConfig(
        num_pages=64, page_size=PS, max_slots=4, max_tokens_per_iter=1000))
    rc = Request(0, 0.0, list(prompt2), max_new_tokens=4)
    cold.add_request(rc)
    cold.run_to_completion()

    warm = PagedEngine(cfg, params, EngineConfig(
        num_pages=64, page_size=PS, max_slots=4, max_tokens_per_iter=16,
        enable_prefix_cache=True))
    r1 = Request(0, 0.0, list(shared), max_new_tokens=1)
    warm.add_request(r1)
    warm.run_to_completion()  # seeds the tree with the shared pages
    r2 = Request(1, 0.0, list(prompt2), max_new_tokens=4)
    warm.add_request(r2)
    iters_before = warm.iterations
    warm.run_to_completion()
    assert r2.num_cached_tokens >= 2 * PS
    # 36 uncached tokens at budget 16 = 3 chunk iterations minimum
    assert warm.iterations - iters_before >= 3
    assert r2.full_output == rc.full_output, \
        "chunked suffix after a cache hit must be a pure optimization"


def test_engine_token_level_partial_hit_identity(model_setup_f32):
    """Two prompts diverging mid-page: the second request's token-level hit
    resumes prefill at an UNALIGNED boundary from a COW'd page — and still
    decodes token-identically to a cold engine."""
    cfg, model, params = model_setup_f32
    rng = np.random.default_rng(13)
    common = rng.integers(0, cfg.vocab_size, 20).tolist()  # 2.5 pages
    sufa = rng.integers(0, cfg.vocab_size, 6).tolist()
    sufb = rng.integers(0, cfg.vocab_size, 9).tolist()

    cold = PagedEngine(cfg, params, EngineConfig(
        num_pages=64, page_size=PS, max_slots=4))
    rb_cold = Request(0, 0.0, common + sufb, max_new_tokens=4)
    cold.add_request(rb_cold)
    cold.run_to_completion()

    warm = PagedEngine(cfg, params, EngineConfig(
        num_pages=64, page_size=PS, max_slots=4, enable_prefix_cache=True))
    ra = Request(0, 0.0, common + sufa, max_new_tokens=2)
    warm.add_request(ra)
    warm.run_to_completion()
    rb = Request(1, 0.0, common + sufb, max_new_tokens=4)
    warm.add_request(rb)
    warm.run_to_completion()
    assert rb.num_cached_tokens == 20, \
        "mid-page divergence must still hit 2 pages + 4 partial tokens"
    assert rb.full_output == rb_cold.full_output


# -- simulator: chunked vs monolithic ------------------------------------------

def test_sim_chunked_matches_monolithic_and_bounds_stall():
    wl = lambda: make_workload(80, rate=20.0, seed=2, max_len=512,
                               long_frac=0.1, long_len=6000)
    mono = simulate_paged(wl(), num_blocks=3000, max_tokens_per_iter=1024,
                          chunk_policy="monolithic")
    chunked = simulate_paged(wl(), num_blocks=3000, max_tokens_per_iter=1024,
                             chunk_policy="decode_first")
    assert mono.completed_frac == 1.0 and chunked.completed_frac == 1.0
    for rm, rc in zip(mono.requests, chunked.requests):
        assert rm.total_generated == rc.total_generated, \
            "chunked prefill must not change what gets generated"
    # the decode-stall tail shrinks; total work is the same
    assert chunked.p99_tbt < mono.p99_tbt
    assert chunked.throughput_tokens_per_s >= \
        0.95 * mono.throughput_tokens_per_s


def test_sim_ttft_spans_chunks():
    """A long prompt's first token arrives only after its LAST chunk: TTFT
    covers the whole chunked prefill, and prefill_time is multi-iteration."""
    backend = SimBackend(num_blocks=2000, max_tokens_per_iter=512,
                         chunk_policy="decode_first")
    from repro.serving.api import LLMService
    svc = LLMService(backend)
    long = Request(0, 0.0, [], max_new_tokens=4, prompt_len=2000)
    svc.submit_request(long)
    svc.drain()
    assert long.first_token_time is not None
    # 2000 tokens at 512/iter = 4 chunk iterations before the first token
    assert long.first_token_time - long.scheduled_time > \
        3 * backend.cost.t_fixed
    stats = svc.stats()
    assert stats.n_finished == 1
    assert stats.per_instance is None  # single backend: no router breakdown


def test_service_stats_stall_metrics():
    wl = lambda: make_workload(60, rate=25.0, seed=4, max_len=512,
                               long_frac=0.15, long_len=5000)
    mono = simulate_paged(wl(), num_blocks=3000, max_tokens_per_iter=1024,
                          chunk_policy="monolithic")
    chunked = simulate_paged(wl(), num_blocks=3000, max_tokens_per_iter=1024,
                             chunk_policy="decode_first")
    # SimResult-level: per-request worst gaps are recorded
    assert len(chunked.max_tbts) > 0
    assert chunked.p99_tbt < mono.p99_tbt


# -- logprob streaming ---------------------------------------------------------

def test_engine_streams_logprobs(model_setup_f32):
    from repro.serving.api import LLMService, SamplingParams
    cfg, model, params = model_setup_f32
    eng = PagedEngine(cfg, params, EngineConfig(num_pages=32, page_size=PS,
                                                max_slots=2))
    svc = LLMService(eng)
    rng = np.random.default_rng(3)
    svc.submit(rng.integers(0, cfg.vocab_size, 6).tolist(),
               SamplingParams(max_new_tokens=4))
    got_tokens, got_lps = [], []
    while svc.pending:
        for ch in svc.poll():
            assert ch.logprobs is not None, \
                "engine chunks must stream per-token logprobs"
            assert len(ch.logprobs) == len(ch.token_ids)
            got_tokens += ch.token_ids
            got_lps += ch.logprobs
    assert len(got_lps) == 4
    assert all(lp <= 0.0 for lp in got_lps), "log-probabilities are <= 0"
    out = svc._results[0]
    assert out.samples[0].token_logprobs is not None
    assert out.cumulative_logprob == pytest.approx(sum(got_lps), rel=1e-5)


def test_sim_streams_no_logprobs():
    from repro.serving.api import LLMService, SamplingParams
    svc = LLMService(SimBackend(num_blocks=100, block_size=PS))
    svc.submit([1, 2, 3], SamplingParams(max_new_tokens=3))
    chunks = []
    while svc.pending:
        chunks += svc.poll()
    assert chunks and all(ch.logprobs is None for ch in chunks), \
        "the cost-model sim does not score tokens"


# -- router: prefill tokens count as load --------------------------------------

def test_least_loaded_counts_prefill_backlog():
    from repro.serving.router import LeastLoadedPolicy
    heavy = SimBackend(num_blocks=2000, max_tokens_per_iter=256)
    light = SimBackend(num_blocks=2000, max_tokens_per_iter=256)
    # same request COUNT on both; instance 0 carries a 4000-token in-flight
    # prefill, instance 1 a short chat
    heavy.add_request(Request(0, 0.0, [], max_new_tokens=4, prompt_len=4000))
    light.add_request(Request(1, 0.0, [], max_new_tokens=4, prompt_len=8))
    heavy.step()
    light.step()
    assert heavy.scheduler.prefill_backlog_tokens() > 0
    pol = LeastLoadedPolicy()
    probe = Request(2, 0.0, [], max_new_tokens=4, prompt_len=8)
    assert pol.choose(probe, [heavy, light]) == 1, \
        "in-flight prefill tokens must count as load"
