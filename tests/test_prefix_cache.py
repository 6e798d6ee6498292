"""Radix-tree prefix KV cache: tree unit tests, scheduler integration, and
engine equivalence (cached-prefix prefill must be a pure optimization)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.paging import BlockAllocator, BlockTable
from repro.core.prefixcache import PrefixCache
from repro.core.scheduling import IterationScheduler, Phase, Request
from repro.serving.engine import EngineConfig, PagedEngine


PS = 8  # page size used throughout


def _table_for(alloc, tokens):
    t = BlockTable()
    alloc.append_tokens(t, len(tokens))
    return t


# -- radix tree unit tests -----------------------------------------------------

def test_match_insert_roundtrip():
    a = BlockAllocator(16, PS)
    c = PrefixCache(a)
    toks = list(range(20))  # 2 full pages + partial
    t = _table_for(a, toks)
    blocks = list(t.blocks)
    assert c.insert(toks, t.blocks) == 2  # partial page 3 not insertable
    a.free_table(t)
    # tree's refs keep both full pages alive
    assert a.num_free == 16 - 2
    path = c.match(toks)
    assert [n.block for n in path] == blocks[:2]
    assert all(a.refcount_of(n.block) == 1 for n in path)


def test_match_is_page_aligned_and_capped():
    a = BlockAllocator(16, PS)
    c = PrefixCache(a)
    toks = list(range(PS * 3))
    t = _table_for(a, toks)
    c.insert(toks, t.blocks)
    # divergence in the middle of page 2 stops the walk after page 1
    other = toks[:PS] + [999] + toks[PS + 1:]
    assert len(c.match(other)) == 1
    # a fully-cached prompt capped at len-1 leaves the last page unmatched
    assert len(c.match(toks, max_tokens=len(toks) - 1)) == 2
    assert len(c.match(toks)) == 3
    a.free_table(t)


def test_insert_existing_pages_skipped():
    a = BlockAllocator(16, PS)
    c = PrefixCache(a)
    toks = list(range(PS * 2))
    t1 = _table_for(a, toks)
    assert c.insert(toks, t1.blocks) == 2
    t2 = _table_for(a, toks)  # same tokens, different physical pages
    assert c.insert(toks, t2.blocks) == 0, "duplicate pages are not adopted"
    a.free_table(t1)
    a.free_table(t2)
    assert a.num_free == 16 - 2  # only the first copy is retained


def test_lock_increfs_into_block_table():
    a = BlockAllocator(16, PS)
    c = PrefixCache(a)
    toks = list(range(PS * 2))
    t = _table_for(a, toks)
    c.insert(toks, t.blocks)
    a.free_table(t)
    path = c.match(toks)
    blocks = c.lock(path)
    assert all(a.refcount_of(b) == 2 for b in blocks)  # tree + request
    shared = BlockTable(blocks=list(blocks), num_tokens=PS * 2)
    a.free_table(shared)
    c.release(path)
    assert all(a.refcount_of(b) == 1 for b in blocks)  # tree ref remains


def test_evict_lru_order_and_pinning():
    a = BlockAllocator(16, PS)
    c = PrefixCache(a)
    old = list(range(PS))
    new = list(range(100, 100 + PS))
    t1, t2 = _table_for(a, old), _table_for(a, new)
    c.insert(old, t1.blocks)
    c.insert(new, t2.blocks)
    a.free_table(t1)
    a.free_table(t2)
    c.match(new)  # touch "new" so "old" is LRU
    pinned = c.match(old)
    c.lock(pinned)  # a running request holds "old"
    # eviction must take the unpinned leaf even though it is more recent
    assert c.evict(1) == 1
    assert len(c.match(new)) == 0, "unpinned page was evicted"
    assert len(c.match(old)) == 1, "pinned page survived"
    # and with only pinned leaves left, eviction gives up rather than free
    # a referenced page
    free_before = a.num_free
    assert c.evict(5) == 0
    assert a.num_free == free_before


def test_evict_never_frees_referenced_page():
    a = BlockAllocator(16, PS)
    c = PrefixCache(a)
    toks = list(range(PS))
    t = _table_for(a, toks)
    c.insert(toks, t.blocks)
    block = t.blocks[0]
    # request still holds its own ref (table not freed), node unpinned:
    # the page is not an eviction candidate at all — freeing it is
    # impossible and forgetting it would lose cache for nothing
    assert c.evict(1) == 0
    assert block not in a.free_list
    assert a.refcount_of(block) == 2 and c.num_pages == 1
    a.free_table(t)
    # now exclusively tree-owned -> evictable, page really freed
    assert c.evict(1) == 1
    assert a.num_free == 16


def test_evict_walks_the_tree_once_per_call(monkeypatch):
    """Evicting a chain's tail exposes its parent for the next pass; the
    passes after the first take those parents, oldest first, without
    walking the tree again. A pinned parent is never exposed."""
    a = BlockAllocator(16, PS)
    c = PrefixCache(a)
    old = list(range(PS * 3))
    new = list(range(100, 100 + PS * 3))
    t1, t2 = _table_for(a, old), _table_for(a, new)
    c.insert(old, t1.blocks)
    c.insert(new, t2.blocks)
    a.free_table(t1)
    a.free_table(t2)
    walks = []
    lru = PrefixCache._lru_leaves
    monkeypatch.setattr(PrefixCache, "_lru_leaves",
                        lambda self: walks.append(1) or lru(self))
    # pass 1 takes both tails, pass 2 the older chain's middle
    assert c.evict(3) == 3 and len(walks) == 1
    assert len(c.match(old)) == 1 and len(c.match(new)) == 2
    t3 = _table_for(a, old)
    assert c.insert(old, t3.blocks) == 2  # the first page is there
    a.free_table(t3)
    pinned = c.match(old[:PS])
    c.lock(pinned)
    # "old"'s last two pages go, its pinned first page stays; "new" goes
    assert c.evict(10) == 4 and len(walks) == 2
    assert c.num_pages == 1 and len(c.match(old)) == 1


def test_hit_rate_stats():
    a = BlockAllocator(16, PS)
    c = PrefixCache(a)
    c.record_admission(20, 0)
    c.record_admission(20, 16)
    assert c.hit_rate == pytest.approx(16 / 40)
    assert c.stats()["admissions"] == 2


# -- scheduler integration -----------------------------------------------------

def _sched(num_blocks=64, **kw):
    a = BlockAllocator(num_blocks, PS)
    c = PrefixCache(a)
    s = IterationScheduler(a, prefix_cache=c, **kw)
    return a, c, s


def _drain(s, *reqs, max_iters=300):
    for r in reqs:
        s.add_request(r)
    for it in range(max_iters):
        plan = s.schedule()
        if plan.empty and not s.waiting:
            return
        for r in plan.prefill + plan.decode:
            r.output.append(0)
        s.complete_iteration(plan, float(it))


def test_scheduler_second_request_hits_cache():
    a, c, s = _sched(max_tokens_per_iter=64)
    shared = list(range(PS * 2))
    r1 = Request(0, 0.0, shared + [7, 8], max_new_tokens=2)
    _drain(s, r1)
    r2 = Request(1, 0.0, shared + [9, 10], max_new_tokens=2)
    s.add_request(r2)
    plan = s.schedule()
    assert plan.prefill == [r2]
    assert r2.num_cached_tokens == PS * 2
    # budget was charged for the suffix only
    assert plan.token_count() == r2.prompt_len - PS * 2
    # the shared pages are physically shared (tree + r2's table)
    t2 = s.tables[r2.request_id]
    assert all(a.refcount_of(b) == 2 for b in t2.blocks[:2])


def test_insert_at_prefill_not_at_finish():
    """A follow-up sharing the prefix hits while the first request is still
    decoding — pages are adopted at prefill completion, so a same-prefix
    burst doesn't recompute the prefix once per member."""
    a, c, s = _sched(max_tokens_per_iter=20)
    shared = list(range(PS * 2))
    r0 = Request(0, 0.0, shared + [1, 2], max_new_tokens=5)
    r1 = Request(1, 0.0, shared + [3, 4], max_new_tokens=5)
    s.add_request(r0)
    s.add_request(r1)
    plan = s.schedule()  # budget 20 admits only r0 (prompt 18)
    assert plan.prefill == [r0] and not r1.num_cached_tokens
    r0.output.append(0)
    s.complete_iteration(plan, 0.0)
    plan = s.schedule()  # r0 decodes; r1 admitted against the warm tree
    assert r0 in plan.decode and r1 in plan.prefill
    assert r0.phase != Phase.FINISHED
    assert r1.num_cached_tokens == PS * 2


def test_scheduler_no_leak_with_cache():
    a, c, s = _sched(max_tokens_per_iter=128)
    shared = list(range(PS * 2))
    reqs = [Request(i, 0.0, shared + [100 + i], max_new_tokens=3)
            for i in range(6)]
    _drain(s, *reqs)
    assert all(r.phase == Phase.FINISHED for r in reqs)
    # only tree-held pages remain; clearing the cache frees everything
    c.clear()
    assert a.num_free == a.num_blocks and not a.refcount


def test_scheduler_evicts_cache_before_preempting():
    # 8 blocks x 8 = 64 slots. r1 fills + finishes, leaving cached pages;
    # r2 then needs the space back — eviction must free it without any
    # preemption.
    a, c, s = _sched(num_blocks=8, max_tokens_per_iter=999)
    r1 = Request(0, 0.0, list(range(40)), max_new_tokens=2)
    _drain(s, r1)
    assert c.num_pages == 5
    r2 = Request(1, 0.0, list(range(1000, 1040)), max_new_tokens=16)
    _drain(s, r2)
    assert r2.phase == Phase.FINISHED
    assert r2.preemptions == 0
    assert c.evicted_pages > 0


def test_evict_retry_after_preemption_saves_survivor():
    """A victim preempted straight after prefill with a page-aligned prompt
    frees ZERO blocks directly (all its pages live on as tree-held cache
    pages) — the decode loop must then evict those pages rather than
    self-preempt the request it was trying to grow."""
    a, c, s = _sched(num_blocks=5, max_tokens_per_iter=999)
    rb = Request(0, 0.0, list(range(PS)), max_new_tokens=20)
    s.add_request(rb)
    plan = s.schedule()  # rb prefills: 1 page-aligned block
    rb.output.append(0)
    s.complete_iteration(plan, 0.0)
    ra = Request(1, 0.0, list(range(100, 100 + 2 * PS)), max_new_tokens=8)
    s.add_request(ra)
    plan = s.schedule()  # rb decodes (block 2); ra prefills its 2 pages
    assert ra in plan.prefill
    for r in plan.prefill + plan.decode:
        r.output.append(0)
    s.complete_iteration(plan, 1.0)
    # budget 1: only rb (older) decodes; ra never gets a token, so its table
    # stays exactly its two tree-shared prompt pages
    s.max_tokens = 1
    it = 2.0
    while rb.phase != Phase.FINISHED:
        plan = s.schedule()
        assert ra not in plan.decode
        if ra in plan.preempted:
            # the crunch: rb needed a block, ra's preemption freed nothing
            # directly, and the retry-evict reclaimed ra's cached pages
            assert rb in plan.decode, \
                "survivor must not be self-preempted after the victim"
        for r in plan.prefill + plan.decode:
            r.output.append(0)
        s.complete_iteration(plan, it)
        it += 1.0
    assert rb.preemptions == 0 and ra.preemptions == 1
    # drain ra (already re-queued in waiting) with a full budget again
    s.max_tokens = 999
    for it2 in range(100):
        plan = s.schedule()
        if plan.empty and not s.waiting:
            break
        for r in plan.prefill + plan.decode:
            r.output.append(0)
        s.complete_iteration(plan, 100.0 + it2)
    assert ra.phase == Phase.FINISHED
    c.clear()
    assert a.num_free == a.num_blocks and not a.refcount


def test_preempted_request_releases_and_rematches():
    a, c, s = _sched(num_blocks=12, max_tokens_per_iter=999, max_running=4)
    shared = list(range(PS))
    r0 = Request(0, 0.0, shared + [5], max_new_tokens=2)
    _drain(s, r0)  # seeds the tree
    r1 = Request(1, 0.0, shared + [6], max_new_tokens=60)
    r2 = Request(2, 0.0, shared + [7], max_new_tokens=60)
    _drain(s, r1, r2)
    assert r1.phase == Phase.FINISHED and r2.phase == Phase.FINISHED
    c.clear()
    assert a.num_free == a.num_blocks, "locks must unwind through preemption"


# -- simulator mode ------------------------------------------------------------

def test_simulator_prefix_cache_mode():
    from repro.serving.simulator import (make_shared_prefix_workload,
                                         make_workload, simulate_paged)

    def shared():
        # staggered arrivals: early finishers seed the tree for later ones
        return make_shared_prefix_workload(120, rate=40.0, seed=3)

    base = simulate_paged(shared(), num_blocks=3000)
    pc = simulate_paged(shared(), num_blocks=3000, prefix_cache=True)
    assert base.prefix_hit_rate is None
    assert pc.prefix_hit_rate > 0.5
    assert pc.completed_frac == 1.0
    assert pc.throughput_tokens_per_s > base.throughput_tokens_per_s
    assert pc.mean_ttft <= base.mean_ttft

    def unique():
        return make_workload(60, rate=30.0, seed=3, materialize_tokens=True)

    u_base = simulate_paged(unique(), num_blocks=2000)
    u_pc = simulate_paged(unique(), num_blocks=2000, prefix_cache=True)
    assert u_pc.prefix_hit_rate == 0.0
    assert u_pc.throughput_tokens_per_s >= \
        0.98 * u_base.throughput_tokens_per_s


# -- engine equivalence (acceptance criterion) ---------------------------------

@pytest.fixture(scope="module")
def model_setup():
    from repro.configs import smoke_config
    from repro.models import Model
    cfg = smoke_config("h2o-danube-1.8b")
    cfg = dataclasses.replace(cfg, sliding_window=None)
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_engine_shared_prefix_equivalence_and_hit_rate(model_setup):
    """Shared 2-page system prompt across 8 requests: every request after the
    first prefills only its suffix, outputs match the no-cache engine, and
    the prompt-token hit rate clears 50%."""
    cfg, model, params = model_setup
    rng = np.random.default_rng(5)
    system = rng.integers(0, cfg.vocab_size, 2 * PS).tolist()
    prompts = [system + rng.integers(0, cfg.vocab_size, 6).tolist()
               for _ in range(8)]

    def run(enable):
        eng = PagedEngine(cfg, params, EngineConfig(
            num_pages=64, page_size=PS, max_slots=4,
            enable_prefix_cache=enable))
        outs = []
        for i, p in enumerate(prompts):
            r = Request(i, 0.0, list(p), max_new_tokens=4)
            eng.add_request(r)
            eng.run_to_completion()
            outs.append((r.full_output, r.num_cached_tokens))
        return outs, eng

    base, _ = run(False)
    cached, eng = run(True)
    assert [o for o, _ in base] == [o for o, _ in cached]
    assert all(nc == 0 for _, nc in base)
    assert all(nc == 2 * PS for _, nc in cached[1:]), \
        "every follow-up request must reuse the system-prompt pages"
    stats = eng.prefix_cache_stats()
    assert stats["hit_rate"] >= 0.5


def test_suffix_prefill_logits_match_full_prefill(model_setup):
    """The cached-suffix prefill computes the same first-token logits as the
    full prefill, within fp tolerance."""
    cfg, model, params = model_setup
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, 2 * PS + 5).tolist()

    eng = PagedEngine(cfg, params, EngineConfig(
        num_pages=32, page_size=PS, max_slots=2, enable_prefix_cache=True))
    r1 = Request(0, 0.0, list(prompt), max_new_tokens=1)
    eng.add_request(r1)
    eng.run_to_completion()  # seeds the radix tree with 2 prompt pages

    # full-prefill logits for the same prompt, computed directly
    full_logits = model.prefill(params, jnp.asarray(prompt, jnp.int32)[None],
                                seq_capacity=64)[0][0]
    # admit an identical prompt: the engine takes the suffix path
    r2 = Request(1, 0.0, list(prompt), max_new_tokens=1)
    eng.add_request(r2)
    plan = eng.scheduler.schedule()
    assert plan.prefill == [r2]
    assert r2.num_cached_tokens == 2 * PS
    table = eng.scheduler.tables[r2.request_id]
    cached = r2.num_cached_tokens
    # build the pow2-bucketed chunk inputs the engine's step() would:
    # tokens pad with zeros (masked via `length`), page_ids pad with a real
    # page (causally masked), no remote (no zero-copy lease here)
    from repro.serving.engine import _pow2_bucket
    suffix = prompt[cached:]
    tok = np.zeros(_pow2_bucket(len(suffix)), np.int32)
    tok[:len(suffix)] = suffix
    pages = np.full(_pow2_bucket(len(table.blocks), 1), table.blocks[0],
                    np.int32)
    pages[:len(table.blocks)] = table.blocks
    suffix_logits, _, _ = eng._prefill_chunk_fn(
        eng.params, eng.pools, jnp.asarray(tok)[None], jnp.asarray(pages),
        jnp.int32(cached), jnp.int32(len(suffix)))
    np.testing.assert_allclose(np.asarray(suffix_logits),
                               np.asarray(full_logits), rtol=2e-4, atol=2e-4)


def test_engine_swa_prefix_cache(model_setup):
    """Sliding-window arch through the cached-suffix path (window masks the
    gathered prefix pages)."""
    from repro.configs import smoke_config
    from repro.models import Model
    cfg = smoke_config("h2o-danube-1.8b")  # window=64 active
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, 2 * PS).tolist()

    def run(enable):
        eng = PagedEngine(cfg, params, EngineConfig(
            num_pages=64, page_size=PS, max_slots=2,
            enable_prefix_cache=enable))
        outs = []
        for i in range(3):
            r = Request(i, 0.0, shared + [int(100 + i)], max_new_tokens=3)
            eng.add_request(r)
            eng.run_to_completion()
            outs.append(r.full_output)
        return outs

    assert run(False) == run(True)


# -- generated-token caching (multi-turn) --------------------------------------

def test_scheduler_inserts_generated_tokens_at_finish():
    """Finishing a request adopts its *generated* full pages too (KV exists
    for all but the final sampled token), so a follow-up that resends the
    reply as history hits past the prompt."""
    a, c, s = _sched(max_tokens_per_iter=999)
    r1 = Request(0, 0.0, list(range(PS * 2)), max_new_tokens=PS + 1)
    s.add_request(r1)
    it = 0.0
    toks = iter(range(1000, 2000))
    while r1.phase != Phase.FINISHED:
        plan = s.schedule()
        for r in plan.prefill + plan.decode:
            r.output.append(next(toks))  # distinct "real" generated ids
        s.complete_iteration(plan, it)
        it += 1.0
    # prompt pages (2) + one full generated page (PS of PS+1 tokens; the
    # final sampled token has no KV and its page is partial)
    assert c.num_pages == 3
    history = r1.prompt + r1.output  # what a client resends next turn
    r2 = Request(1, 0.0, history + [7, 8], max_new_tokens=2)
    s.add_request(r2)
    plan = s.schedule()
    assert plan.prefill == [r2]
    assert r2.num_cached_tokens == PS * 3, \
        "multi-turn reuse must cover the generated reply, not just the prompt"


def test_scheduler_cache_generated_opt_out():
    """cache_generated=False (the simulator: outputs are placeholder ids)
    keeps the old prompt-only insertion behavior."""
    alloc = BlockAllocator(64, PS)
    cache = PrefixCache(alloc)
    s = IterationScheduler(alloc, prefix_cache=cache, cache_generated=False)
    r1 = Request(0, 0.0, list(range(PS)), max_new_tokens=PS + 1)
    _drain(s, r1)
    assert cache.num_pages == 1  # prompt page only


def test_engine_multi_turn_hits_generated_pages(model_setup):
    """End-to-end multi-turn chat on the engine: turn 2 resends turn 1's
    reply and must hit the radix tree beyond the client-resent prompt —
    and produce identical outputs to a cold engine (pure optimization)."""
    cfg, model, params = model_setup
    rng = np.random.default_rng(21)
    system_user1 = rng.integers(0, cfg.vocab_size, 2 * PS).tolist()
    user2 = rng.integers(0, cfg.vocab_size, 5).tolist()
    n_reply = PS + 1  # KV exists for the first PS generated tokens

    def turn2_prompt(reply):
        return system_user1 + reply + user2

    def run(enable):
        eng = PagedEngine(cfg, params, EngineConfig(
            num_pages=64, page_size=PS, max_slots=2,
            enable_prefix_cache=enable))
        r1 = Request(0, 0.0, list(system_user1), max_new_tokens=n_reply)
        eng.add_request(r1)
        eng.run_to_completion()
        r2 = Request(1, 0.0, turn2_prompt(r1.full_output), max_new_tokens=4)
        eng.add_request(r2)
        eng.run_to_completion()
        return r1, r2

    r1c, r2c = run(False)
    r1w, r2w = run(True)
    assert r1c.full_output == r1w.full_output
    assert r2c.full_output == r2w.full_output, \
        "generated-page reuse must not change the decode"
    # turn-2 hit covers prompt pages AND the first generated page: the
    # resent history is 2*PS prompt + PS+1 reply tokens -> 3 full pages
    assert r2w.num_cached_tokens == 3 * PS
    assert r2c.num_cached_tokens == 0


# -- block-table sizing (satellite) --------------------------------------------

def test_block_table_width_from_context_limit(model_setup):
    cfg, model, params = model_setup
    eng = PagedEngine(cfg, params, EngineConfig(
        num_pages=64, page_size=PS, max_slots=2, max_context_len=40))
    assert eng.max_pages_per_seq == 5  # ceil(40/8), not num_pages=64
    bt, _, _, _ = eng._ctx_arrays()
    assert bt.shape == (2, 5)
    with pytest.raises(ValueError):
        eng.add_request(Request(0, 0.0, [1] * 30, max_new_tokens=20))
    # a fitting request still runs through decode with the narrow table
    r = Request(1, 0.0, [1, 2, 3, 4], max_new_tokens=3)
    eng.add_request(r)
    eng.run_to_completion()
    assert len(r.full_output) == 3


def test_arch_max_seq_len_bounds_width(model_setup):
    cfg, model, params = model_setup
    cfg2 = dataclasses.replace(cfg, max_seq_len=64)
    eng = PagedEngine(cfg2, params, EngineConfig(
        num_pages=64, page_size=PS, max_slots=2))
    assert eng.max_pages_per_seq == 8  # from ArchConfig, not the page supply
