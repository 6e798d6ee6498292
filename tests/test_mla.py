"""Layout-agnostic KV pages + matrix-absorbed MLA decode.

Covers the PR's acceptance criteria and satellites: ``KVPageLayout``
schema derivation for GQA and MLA arches (pool shapes, page bytes, the
>=5x latent-KV compression on the full deepseek-v2 geometry),
layout-true network charges so swap/borrow decisions see the real wire
bytes (satellite 2), loud schema-mismatch rejection on every
page-payload exchange path — board publish, zero-copy lease grant,
payload import, KV handoff install, router prefix_share wiring
(satellite 1) — a cluster drain property over both layouts under random
share settings (satellite 3), and the MLA engine ACCEPTANCE proofs:
matrix-absorbed decode over latent ``ckv``/``krope`` pages is
token-identical to the fp32 decompress-then-GQA oracle, including a
host swap round trip and a zero-copy borrowed prefix."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs import get_config, smoke_config
from repro.core.distkv import GManager, NetworkModel, RManager, RemoteLease
from repro.core.distkv.prefixshare import PrefixShareBoard
from repro.core.paging import BlockAllocator, KVPageLayout, check_schema
from repro.core.scheduling import Phase, Request
from repro.models import Model
from repro.serving.engine import EngineConfig, PagedEngine
from repro.serving.simulator import SimBackend, make_shared_prefix_workload

PS = 8  # page size used throughout

GQA_SMOKE = KVPageLayout.from_arch(smoke_config("h2o-danube-1.8b"))
MLA_SMOKE = KVPageLayout.from_arch(smoke_config("deepseek-v2-236b"))


# -- KVPageLayout: schema derivation + byte accounting -------------------------

def test_layout_gqa_schema_and_pools():
    lay = GQA_SMOKE
    assert lay.flavor == "gqa"
    assert lay.schema == "gqa:k4x64+v4x64:bf16"
    assert lay.bytes_per_token_layer == 2 * (4 * 64) * 2  # two pools, bf16
    assert lay.page_bytes(PS) == lay.bytes_per_token * PS
    shapes = lay.pool_shapes(6, PS)
    assert shapes == ((lay.num_layers, 6, PS, 4, 64),) * 2


def test_layout_mla_schema_and_pools():
    lay = MLA_SMOKE
    assert lay.flavor == "mla"
    assert lay.schema == "mla:ckv64+krope16:bf16"
    # one shared latent per token, NOT per kv head: ckv + krope elems
    assert lay.bytes_per_token_layer == (64 + 16) * 2
    assert lay.pool_shapes(6, PS) == ((lay.num_layers, 6, PS, 64),  # ckv
                                      (lay.num_layers, 6, PS, 16))  # krope


def test_full_deepseek_latent_compression_ratio():
    """ACCEPTANCE: the MLA layout stores >=5x fewer KV bytes per token
    than the equivalent GQA layout on the real deepseek-v2-236b geometry
    (it is ~57x: 2*128*128 head elems vs a 512+64 shared latent)."""
    cfg = get_config("deepseek-v2-236b")
    mla = KVPageLayout.from_arch(cfg)
    gqa = KVPageLayout.from_arch(dataclasses.replace(cfg, attention="gqa"))
    assert mla.schema == "mla:ckv512+krope64:bf16"
    ratio = gqa.bytes_per_token / mla.bytes_per_token
    assert ratio == pytest.approx((2 * 128 * 128) / (512 + 64))
    assert ratio >= 5.0


def test_check_schema_guard():
    check_schema("mla:ckv64+krope16:bf16", None, where="x")  # unknown: pass
    check_schema("mla:ckv64+krope16:bf16", "mla:ckv64+krope16:bf16",
                 where="x")
    with pytest.raises(ValueError, match="schema mismatch at lease read"):
        check_schema(MLA_SMOKE.schema, GQA_SMOKE.schema, where="lease read")


def test_layout_rejects_unknown_dtype():
    lay = dataclasses.replace(MLA_SMOKE, dtype_name="complex128")
    with pytest.raises(ValueError, match="dtype"):
        _ = lay.bytes_per_token


# -- satellite 2: network charges follow the layout's true page bytes ----------

def test_netmodel_charges_layout_bytes():
    base = NetworkModel()
    gqa_pb = GQA_SMOKE.page_bytes(PS)
    mla_pb = MLA_SMOKE.page_bytes(PS)
    assert mla_pb < gqa_pb
    # the per-call override reprices the transfer, leaving the default
    # (and thus the committed swap-sweep baselines) untouched
    assert base.swap_time(4, page_bytes=mla_pb) \
        < base.swap_time(4, page_bytes=gqa_pb) < base.swap_time(4)
    assert base.peer_copy_time(4, page_bytes=mla_pb) \
        < base.peer_copy_time(4, page_bytes=gqa_pb)
    net = NetworkModel.for_layout(MLA_SMOKE, PS)
    assert net.page_bytes == mla_pb
    assert net.swap_time(4) == base.swap_time(4, page_bytes=mla_pb)


def test_prefer_borrow_flips_for_compressed_layout():
    """The copy-vs-borrow break-even moves when a page is ~10x cheaper to
    copy: a decode length where GQA-priced pages favor borrowing must
    favor copying once the same decision is priced at MLA latent bytes."""
    net = NetworkModel()
    gqa_pb = get_config("deepseek-v2-236b").num_layers * 2 * 128 * 128 * 2 * 16
    mla_pb = KVPageLayout.from_arch(get_config("deepseek-v2-236b")) \
        .page_bytes(16)
    flipped = [t for t in (64, 256, 1024, 4096)
               if net.prefer_borrow(32, 16, est_decode_tokens=t,
                                    page_bytes=gqa_pb)
               and not net.prefer_borrow(32, 16, est_decode_tokens=t,
                                         page_bytes=mla_pb)]
    assert flipped, "some decode length must flip from borrow to copy"


def test_allocator_page_bytes_property():
    a = BlockAllocator(8, PS, layout=MLA_SMOKE)
    assert a.page_bytes == MLA_SMOKE.page_bytes(PS)
    assert BlockAllocator(8, PS).page_bytes is None  # layout-less sim


def test_sim_backend_swap_decider_sees_layout_bytes():
    """A swap that is not worth its PCIe time at default (GQA-sized) page
    bytes becomes worth it when the pages are MLA latents."""
    kw = dict(num_blocks=16, block_size=16, swap_mode="auto",
              host_blocks=16)
    fat = SimBackend(**kw)  # default page_bytes: ~13 MB
    thin = SimBackend(layout=KVPageLayout.from_arch(
        get_config("deepseek-v2-236b")), **kw)
    assert thin.kv_page_bytes < fat.swap_net.page_bytes
    req = Request(0, 0.0, [], prompt_len=160, max_new_tokens=8)
    req.prefilled_len = 160  # the decider prices the COMPUTED context
    n_pages = 10
    flips = thin._swap_worth_it(req, n_pages) \
        and not fat._swap_worth_it(req, n_pages)
    assert flips, "layout bytes must flip the swap-vs-recompute decision"


# -- satellite 1: every payload exchange path refuses foreign layouts ----------

def test_board_refuses_mixed_schema_publish():
    board = PrefixShareBoard()
    board.publish(0, list(range(PS)), [None], PS, schema=GQA_SMOKE.schema)
    assert board.schema == GQA_SMOKE.schema
    before = board.num_pages
    with pytest.raises(ValueError, match="schema mismatch on one board"):
        board.publish(1, list(range(100, 100 + PS)), [None], PS,
                      schema=MLA_SMOKE.schema)
    assert board.num_pages == before, "the refused path must not land"
    # schema-less (sim) publishers still interoperate
    board.publish(1, list(range(200, 200 + PS)), [None], PS)


def _mixed_cluster():
    g = GManager(2)
    rms = {0: RManager(0, BlockAllocator(8, PS, layout=MLA_SMOKE), g),
           1: RManager(1, BlockAllocator(8, PS, layout=GQA_SMOKE), g)}
    for r in rms.values():
        r.register_peers(rms)
    return g, rms


def test_lease_grant_refuses_mixed_layouts():
    """REGRESSION: the zero-copy wiring used to validate only page size, so
    a GQA home could lend pages to an MLA debtor (or vice versa) and the
    debtor would attend over reinterpreted garbage. The grant must refuse
    loudly, before any pin or ledger entry."""
    g, rms = _mixed_cluster()
    b = rms[1].allocator.alloc_block()
    with pytest.raises(ValueError, match="schema mismatch on lease grant"):
        rms[0].borrow_blocks(1, [b])
    assert not g.ledger, "a refused grant must not touch the debt ledger"
    assert rms[1].allocator.refcount_of(b) == 1, "no stray lease pin"


def test_lease_carries_creditor_schema():
    g = GManager(2)
    rms = {i: RManager(i, BlockAllocator(8, PS, layout=MLA_SMOKE), g)
           for i in range(2)}
    for r in rms.values():
        r.register_peers(rms)
    b = rms[1].allocator.alloc_block()
    lease = rms[0].borrow_blocks(1, [b])
    assert lease.schema == MLA_SMOKE.schema, \
        "the lease must carry the creditor's layout for the install check"
    lease.release()


def test_router_refuses_mixed_layout_children():
    from repro.serving.router import RouterBackend
    children = [SimBackend(num_blocks=16, block_size=PS, prefix_cache=True,
                           layout=lay) for lay in (GQA_SMOKE, MLA_SMOKE)]
    with pytest.raises(ValueError, match="schema mismatch across"):
        RouterBackend(children, prefix_share=True)
    # same layout everywhere is fine
    ok = [SimBackend(num_blocks=16, block_size=PS, prefix_cache=True,
                     layout=MLA_SMOKE) for _ in range(2)]
    RouterBackend(ok, prefix_share=True)


# -- satellite 3: cluster ledgers drain to empty for both layouts --------------

def _check_cluster_drain(layout, seed, share_mode, swap_overlap):
    from repro.serving.router import RouterBackend
    children = [SimBackend(num_blocks=32, block_size=PS, max_running=8,
                           max_tokens_per_iter=128, prefix_cache=True,
                           host_blocks=16, swap_mode="swap",
                           swap_overlap=swap_overlap, layout=layout)
                for _ in range(2)]
    router = RouterBackend(children, prefix_share=True,
                           share_mode=share_mode, net=NetworkModel())
    for r in make_shared_prefix_workload(16, rate=200.0, n_groups=2,
                                         prefix_len=2 * PS, suffix_len=PS,
                                         out_len=8, seed=seed,
                                         group_draw="random"):
        router.add_request(r)
    for _ in range(5000):
        if not router.has_work:
            break
        router.step()
        for c in children:
            a = c.allocator
            assert a.num_used + a.num_free == a.num_blocks
            assert a.swapped_pages + a.host_num_free == a.num_host_blocks
    else:
        raise AssertionError("cluster did not drain")
    for c in children:
        c.prefix_cache.clear()
    # pages the board still pins as lendable (zero_copy homes keep their
    # published blocks referenced until board eviction) are accounted, not
    # leaked: residual usage must equal exactly the pin count
    pinned = {i: 0 for i in range(len(children))}
    stack = [router.g.prefix_board._root]
    while stack:
        node = stack.pop()
        for ch in node.children.values():
            if ch.block is not None:
                pinned[ch.home] += 1
            stack.append(ch)
    for i, c in enumerate(children):
        a = c.allocator
        assert a.num_used == pinned[i] and a.swapped_pages == 0
        assert a.pending_out_pages == 0
        assert router.g.lent_by(i) == 0 and router.g.borrowed_by(i) == 0, \
            "every lease must be repaid at drain"


@settings(max_examples=8, deadline=None)
@given(mla=st.booleans(), seed=st.integers(0, 10_000),
       zero_copy=st.booleans(), swap_overlap=st.booleans())
def test_cluster_conservation_over_layouts(mla, seed, zero_copy,
                                           swap_overlap):
    """Property: device/host/pending ledgers hold every iteration and the
    allocators, spill budgets, and lease debt all drain to empty — for
    BOTH page layouts, under random share/overlap settings. The layout
    changes every byte charge but must never change ledger accounting."""
    _check_cluster_drain(MLA_SMOKE if mla else GQA_SMOKE, seed,
                         "zero_copy" if zero_copy else "copy", swap_overlap)


@pytest.mark.parametrize("layout", [GQA_SMOKE, MLA_SMOKE],
                         ids=["gqa", "mla"])
@pytest.mark.parametrize("share_mode", ["copy", "zero_copy"])
def test_cluster_conservation_examples(layout, share_mode):
    """Example-based companion so both layouts are exercised even where
    hypothesis is unavailable."""
    _check_cluster_drain(layout, 7, share_mode, swap_overlap=True)


# -- MLA engine: matrix-absorbed decode over latent pages (ACCEPTANCE) ---------

@pytest.fixture(scope="module")
def mla_setup():
    cfg = smoke_config("deepseek-v2-236b")
    cfg = dataclasses.replace(cfg, dtype="float32", logits_fp32=True)
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _oracle(model, params, prompt, n):
    """Greedy reference: naive decompress-then-attend MLA forward (the
    ``mla_forward`` path inside ``Model``), fp32, ring caches."""
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


def _engine(cfg, params, **kw):
    kw.setdefault("num_pages", 48)
    kw.setdefault("page_size", PS)
    kw.setdefault("max_slots", 4)
    return PagedEngine(cfg, params, EngineConfig(**kw))


@pytest.mark.parametrize("flavor,schema", [
    ("gqa", "gqa:k4x64+v4x64:f32"),   # k, v pools (L, P+1, ps, Hkv, Dh)
    ("mla", "mla:ckv64+krope16:f32"),  # ckv (L, P+1, ps, r), krope (.., dr)
])
def test_mla_engine_pools_follow_layout(mla_setup, flavor, schema):
    cfg, model, params = mla_setup
    if flavor == "gqa":
        cfg = dataclasses.replace(smoke_config("h2o-danube-1.8b"),
                                  sliding_window=None, dtype="float32")
        params = Model(cfg, remat=False).init(jax.random.PRNGKey(0))
    eng = _engine(cfg, params, host_pages=4)
    lay = eng.kv_layout
    assert lay.flavor == flavor
    assert lay.schema == schema
    shapes = lay.pool_shapes(48 + 1, PS)  # +1: the trash page
    assert len(eng.pools) == len(lay.pools) == len(shapes)
    assert tuple(p.shape for p in eng.pools) == shapes
    assert tuple(h.shape for h in eng.host_pools) == \
        lay.pool_shapes(4, PS)
    assert eng.allocator.page_bytes == lay.page_bytes(PS)


def test_mla_engine_rejects_kernel_and_window(mla_setup):
    cfg, model, params = mla_setup
    with pytest.raises(ValueError, match="kernel"):
        _engine(cfg, params, use_kernel=True)


def test_mla_engine_token_identity(mla_setup):
    """ACCEPTANCE (the tentpole): matrix-absorbed MLA decode over paged
    latent ckv/krope — W_UK absorbed into the query path, W_UV into the
    output path, never materializing per-head K/V — produces exactly the
    oracle's greedy tokens (fp32 decompress-then-attend)."""
    cfg, model, params = mla_setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(3)
    reqs = [Request(i, 0.0,
                    rng.integers(1, cfg.vocab_size, 13 + i).tolist(),
                    max_new_tokens=8) for i in range(3)]
    for r in reqs:
        eng.add_request(r)
    eng.run_to_completion()
    for r in reqs:
        want = _oracle(model, params, r.prompt, len(r.full_output))
        assert r.full_output == want, f"req {r.request_id}"
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_mla_engine_swap_round_trip_token_identity(mla_setup):
    """ACCEPTANCE: an MLA request swapped to host mid-decode and back
    resumes mid-sequence with its latent pages intact — the device<->host
    copies move ckv/krope pools, and the greedy tokens still match."""
    cfg, model, params = mla_setup
    eng = _engine(cfg, params, num_pages=8, max_slots=2, host_pages=16,
                  swap_mode="swap")
    # seed 4: both prompts individually match the sequential oracle in a
    # roomy no-swap run (some seeds hit unrelated fp32 near-ties), so any
    # mismatch here is attributable to the swap round trip
    rng = np.random.default_rng(4)
    reqs = [Request(i, 0.0,
                    rng.integers(1, cfg.vocab_size, 17).tolist(),
                    max_new_tokens=20) for i in range(2)]
    for r in reqs:
        eng.add_request(r)
    eng.run_to_completion()
    assert eng.swapped_out == eng.swapped_in > 0, \
        "the crunch must force a swap round trip"
    for r in reqs:
        assert r.preemptions == 0
        want = _oracle(model, params, r.prompt, len(r.full_output))
        assert r.full_output == want, f"req {r.request_id}"
    assert eng.allocator.num_free == eng.allocator.num_blocks
    assert eng.allocator.swapped_pages == 0


class _Script:
    def __init__(self, script):
        self.script = list(script)

    def choose(self, req, children):
        return self.script.pop(0)


def test_mla_engine_zero_copy_token_identity(mla_setup):
    """ACCEPTANCE: instance B decodes with its prefix ckv/krope pages
    living in instance A's pools, served through the latent partial merge
    — no payload copy — and B's output matches the fp32 oracle."""
    from repro.serving.router import RouterBackend
    cfg, model, params = mla_setup
    engines = [_engine(cfg, params, enable_prefix_cache=True)
               for _ in range(2)]
    router = RouterBackend(engines, policy=_Script([0, 0, 1]),
                           prefix_share=True, share_mode="zero_copy",
                           hot_threshold=1)
    rng = np.random.default_rng(12)
    prefix = rng.integers(1, cfg.vocab_size, 2 * PS).tolist()
    prompts = [prefix + rng.integers(1, cfg.vocab_size, 4).tolist()
               for _ in range(3)]
    reqs = [Request(i, 0.0, list(p), max_new_tokens=3)
            for i, p in enumerate(prompts)]
    for r in reqs:
        router.add_request(r)
        while router.has_work:
            router.step()
    assert reqs[2].instance_id == 1
    assert router.pages_borrowed >= 2, "the prefix must be borrowed"
    assert engines[1].prefix_cache.adopted_pages == 0, \
        "zero_copy must not copy latent payloads"
    assert reqs[2].num_cached_tokens == 2 * PS
    assert not router.g.ledger, "every lease repaid at request finish"
    for r, p in zip(reqs, prompts):
        want = _oracle(model, params, p, 3)
        assert r.full_output == want, f"req {r.request_id}"


def test_mla_engine_payload_export_import_round_trip(mla_setup):
    """Copy-mode sharing of latent pages: exported payloads carry the MLA
    schema tag and re-import bit-identically; a foreign-schema payload is
    refused before any pool is touched."""
    cfg, model, params = mla_setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(7)
    r = Request(0, 0.0, rng.integers(1, cfg.vocab_size, 2 * PS).tolist(),
                max_new_tokens=2)
    eng.add_request(r)
    eng.run_to_completion()
    payload = eng.export_page_payload(0)
    assert payload[0] == eng.kv_layout.schema
    assert payload[1].shape == (eng.nlayers, PS) \
        + eng.kv_layout.pools[0].token_shape
    assert len(payload) == 1 + len(eng.pools)
    blk = eng.allocator.alloc_block()
    eng.import_page_payloads([blk], [payload])
    for pool, page in zip(eng.pools, payload[1:]):
        np.testing.assert_array_equal(np.asarray(pool[:, blk]), page)
    eng.allocator.decref(blk)
    foreign = (GQA_SMOKE.schema, payload[1], payload[2])
    with pytest.raises(ValueError, match="payload import"):
        eng.import_page_payloads([0], [foreign])


def test_mla_engine_handoff_install_refuses_foreign_lease(mla_setup):
    """REGRESSION: the disaggregated handoff used to install any lease
    whose page size matched; a lease over GQA pages must be refused before
    a slot is claimed."""
    cfg, model, params = mla_setup
    eng = _engine(cfg, params)
    eng.remote_reader = lambda home: eng.pools
    lease = RemoteLease(home=1, debtor=0, blocks=[0], page_size=PS,
                        schema=GQA_SMOKE.schema)
    req = Request(0, 0.0, [1, 2, 3], max_new_tokens=1)
    req.output.append(5)
    free_before = len(eng.free_slots)
    with pytest.raises(ValueError, match="handoff install"):
        eng.install_for_handoff(req, None, lease=lease)
    assert len(eng.free_slots) == free_before, "no slot may leak"
