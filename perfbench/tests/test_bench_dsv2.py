"""The DeepSeek-V2 stage on the CPU at a tiny size (fixture ``tiny-dsv2``:
latent attention with YaRN, a dense layer, then shared experts and one
routing group of 4 experts held of a group-limited router over 32, an
untied head): the engine's logits against the plain reference
(``references/mla_moe.py``) over prefill chunks, a prefix-cache hit and
paged decode; the expert shares against the uncut layer; the routing
records the engine emits and the readers that take them; and the
program's own translation of the configuration files."""

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import driver, published, roofline, spec, weights
from perfbench.references import mla_moe
from perfbench.tracefile import Event as TraceEvent, Trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tiny")
with open(os.path.join(FIXTURE, "configs", "tiny-dsv2.json")) as f:
    CONF = json.load(f)
MODEL = CONF["model"]
SEED = 2 ** 31 + 1601
V5E = roofline.peaks("TPU v5 lite")

# The engine against the reference: the largest difference over the
# vocabulary at each sampled position, bounded at the median and at the
# 90th percentile of the positions, never at the largest. Attention runs
# bf16 operands with float32 accumulation in the models at either dtype,
# and the reference is float32 throughout: the float32 engine differs by a
# median of 0.027-0.036 (90th percentile 0.052-0.063) at logits of 3-4, the
# bf16 one by 0.057-0.084 (0.10-0.14), over four seeds. A routing choice
# near a tie between the 6th and 7th expert, or the 3rd and 4th group, can
# go either way between the two; where one of the two is held here the
# flip moves that position's logits by up to 4 (weights times 16 at a
# width of 64) and, through attention, later ones a little: a few positions
# (0.13-3.7 at the largest). A wrong equation moves them all: each of YaRN
# left out, the softmax scale without mscale squared, normalised routing
# weights, no group limit, the held share one expert off and the embedding
# as the head raises the median to 0.78-3.5. The float8 control differs
# by a median of 0.79-1.05.
TOLERANCES = {"float32": (0.06, 0.12), "bfloat16": (0.15, 0.25)}


@pytest.fixture(scope="module", params=sorted(TOLERANCES))
def served(request):
    """One engine with telemetry at the fixture's widths in ``dtype``: a
    100-token prompt alone (two chunks of the 64-token budget), then a
    prompt that shares its first 70 tokens (a prefix-cache hit) beside a
    fresh one; the logits of every sampled position, as the engine
    computed them."""
    from repro.serving.api import LLMService, SamplingParams
    from repro.serving.engine import PagedEngine
    conf = dict(CONF, model=dict(MODEL, torch_dtype=request.param))
    engine, _ = driver.build("tiny-dsv2", conf, SEED, telemetry=True)
    seen = {}
    sample = PagedEngine._sample_rows

    def spy(self, logits, rows, routes=()):
        lg = np.asarray(logits, np.float32)
        for i, r in enumerate(rows):
            if r is not None:
                seen[(r.request_id, r.prompt_len + len(r.output) - 1)] = \
                    lg[i, :MODEL["vocab_size"]]
        return sample(self, logits, rows, routes)

    rng = np.random.default_rng(1)
    a = rng.integers(1, MODEL["vocab_size"], 100).tolist()
    prompts = [a, a[:70] + rng.integers(1, MODEL["vocab_size"], 20).tolist(),
               rng.integers(1, MODEL["vocab_size"], 30).tolist()]
    svc = LLMService(engine)
    outs, ids = {}, []
    PagedEngine._sample_rows = spy
    try:
        for batch in ([prompts[0]], prompts[1:]):
            ids += [svc.submit(p, SamplingParams(temperature=0.0,
                                                 max_new_tokens=12))
                    for p in batch]
            while svc.pending or engine.has_work:
                for ch in svc.poll():
                    outs.setdefault(ch.request_id, []).extend(ch.token_ids)
    finally:
        PagedEngine._sample_rows = sample
    return request.param, engine, prompts, [outs[i] for i in ids], ids, \
        seen


def test_engine_logits_follow_the_reference(served):
    dtype, engine, prompts, outs, ids, seen = served
    assert engine.prefix_cache.hit_tokens >= 64
    seqs = [np.asarray(p + o[:-1], np.int32) for p, o in zip(prompts, outs)]
    firsts = [len(p) - 1 for p in prompts]
    ref = mla_moe.logits(MODEL, SEED, seqs, firsts)
    ctl = mla_moe.logits(MODEL, SEED, seqs, firsts, control=True)
    got = np.array([np.abs(seen[(rid, len(p) - 1 + j)] - rows[j]).max()
                    for rid, p, rows in zip(ids, prompts, ref)
                    for j in range(len(rows))])
    low = np.array([np.abs(a - b).max() for rows, lows in zip(ref, ctl)
                    for a, b in zip(rows, lows)])
    assert len(got) == sum(len(o) for o in outs) == 36
    median, p90 = TOLERANCES[dtype]
    assert np.median(got) <= median and np.percentile(got, 90) <= p90
    # the tolerances refuse the float8 control
    assert np.median(low) > p90


def test_routing_records_pair_with_program_calls(served):
    """One ``engine/moe_route`` record per program call, in call order, as
    ``roofline.routes`` pairs them; the counters sum the records."""
    _, engine, _, _, _, _ = served
    events = engine.trace.events()
    chunks = [e for e in events if e.cat == "engine" and e.name == "chunk"]
    decodes = [e for e in events if e.cat == "engine"
               and e.name == "iteration" and e.args["decodes"]]
    pre = roofline.routes(MODEL, events, "prefill", len(chunks))
    dec = roofline.routes(MODEL, events, "decode", len(decodes))
    assert pre is not None and dec is not None and dec
    moe_layers = MODEL["num_hidden_layers"] - MODEL["first_k_dense_replace"]
    k, held = MODEL["num_experts_per_tok"], MODEL["n_routed_experts"]
    for ch, r in zip(chunks, pre):
        assert len(r["assigned"]) == len(r["hit"]) == moe_layers
        assert all(0 <= a <= ch.args["length"] * k for a in r["assigned"])
        assert all(0 <= h <= held for h in r["hit"])
    for it, r in zip(decodes, dec):
        assert all(a <= it.args["decodes"] * k for a in r["assigned"])
    c = engine.metrics.counters
    assert c["moe_decode_assigned"] == sum(sum(r["assigned"]) for r in dec)
    assert c["moe_decode_hit"] == sum(sum(r["hit"]) for r in dec)
    assert c["moe_prefill_assigned"] == sum(sum(r["assigned"]) for r in pre)


# A token's held assignments may differ between the engine and the
# reference only where its routing lies within rounding of a tie: a relative
# margin (``mla_moe.routing``) under this share between the deciding scores.
# The engine's activations differ from the reference's by about 1% here
# (bf16 attention operands); a flip reaches at most every choice of a token.
NEAR_TIE = 0.02


def test_routing_records_count_the_tokens_alone(served):
    """Each prefill chunk's ``assigned`` per layer is the reference router's
    count of held assignments over the chunk's real tokens, not the rows it
    is padded to; the decode calls' sum is the count over the tokens fed
    back, not the idle slots' rows."""
    from repro.serving.engine import _pow2_bucket
    _, engine, prompts, outs, ids, _ = served
    events = engine.trace.events()
    chunks = [e for e in events if e.cat == "engine" and e.name == "chunk"]
    decodes = [e for e in events if e.cat == "engine"
               and e.name == "iteration" and e.args["decodes"]]
    pre = roofline.routes(MODEL, events, "prefill", len(chunks))
    dec = roofline.routes(MODEL, events, "decode", len(decodes))
    seqs = [np.asarray(p + o[:-1], np.int32) for p, o in zip(prompts, outs)]
    stats = mla_moe.routing(MODEL, SEED, seqs)
    k = MODEL["num_experts_per_tok"]
    assert len(stats) == MODEL["num_hidden_layers"] - \
        MODEL["first_k_dense_replace"]

    def count(layer, i, lo, hi):
        held, margin = (a[lo:hi] for a in stats[layer][i])
        return int(held.sum()), k * int((margin < NEAR_TIE).sum())

    pads = 0
    for ch, r in zip(chunks, pre):
        i = ids.index(ch.rid)
        lo, hi = ch.args["start"], ch.args["start"] + ch.args["length"]
        pads += _pow2_bucket(ch.args["length"]) > ch.args["length"]
        for layer, got in enumerate(r["assigned"]):
            want, room = count(layer, i, lo, hi)
            assert abs(got - want) <= room, (layer, lo, hi, got, want)
    assert pads  # some chunk was padded
    # every decode call had idle rows
    assert max(it.args["decodes"] for it in decodes) < \
        CONF["engine"]["max_slots"]
    for layer in range(len(stats)):
        got = sum(r["assigned"][layer] for r in dec)
        want = room = 0
        for i, (p, o) in enumerate(zip(prompts, outs)):
            w, rm = count(layer, i, len(p), len(p) + len(o) - 1)
            want, room = want + w, room + rm
        assert abs(got - want) <= room, (layer, got, want, room)


def test_untraced_engines_read_no_routing(monkeypatch):
    from repro.serving.api import LLMService, SamplingParams
    from repro.serving.engine import PagedEngine

    def refuse(self, routes):
        assert not routes

    monkeypatch.setattr(PagedEngine, "_emit_routes", refuse)
    engine, _ = driver.build("tiny-dsv2", CONF, SEED, telemetry=False)
    svc = LLMService(engine)
    svc.submit(list(range(1, 40)), SamplingParams(max_new_tokens=3))
    while svc.pending or engine.has_work:
        svc.poll()
    assert engine.trace is None and engine.metrics is None


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of four experts under one router of 32, each adding
    its own experts' part, plus the shared experts once, give the uncut
    reference layer (model-configs guide, section 4)."""
    from repro.models import moe
    from repro.models.layers import mlp
    uncut = dict(MODEL, n_routed_experts=32)
    w = mla_moe._layer_weights(uncut, weights.base_key(SEED), 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (48, MODEL["hidden_size"]))
    want = mla_moe.moe(x, w, m=mla_moe._statics(uncut, False)[-1],
                       control=False)
    cfg = driver.arch_config("tiny-dsv2", CONF)
    total = mlp({n: {"w": w["shared_" + n]} for n in ("gate", "up", "down")},
                x)
    assigned = 0
    for s in range(8):
        share = dataclasses.replace(cfg, expert_offset=4 * s)
        p = {n: w[n][4 * s:4 * s + 4] for n in ("gate", "up", "down")}
        route = moe.route(share, w["router"], x)
        part, got, _ = moe.grouped_experts(share, p, x, *route[:2])
        total, assigned = total + part, assigned + int(got)
    assert assigned == 48 * MODEL["num_experts_per_tok"]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(total, want, rtol=0, atol=1e-5 * scale)


def test_the_check_takes_routing_flips_and_refuses_a_wrong_model():
    """``mla_moe.widest_gaps`` reads a high quantile of a sequence's gaps:
    tokens chosen otherwise at a few positions (a routing near a tie) pass,
    tokens chosen otherwise at many (a wrong equation, a lower precision)
    do not."""
    rng = np.random.default_rng(0)
    rows = [rng.normal(size=(200, 64)) for _ in range(3)]
    best = [r.argmax(-1) for r in rows]
    assert mla_moe.widest_gaps(rows, best) == [0.0, 0.0, 0.0]

    def second(r, toks, share):
        toks = toks.copy()
        pick = rng.permutation(len(toks))[:int(share * len(toks))]
        toks[pick] = np.argsort(r[pick], -1)[:, -2]
        return toks

    few = [second(r, b, 0.05) for r, b in zip(rows, best)]
    many = [second(r, b, 0.3) for r, b in zip(rows, best)]
    assert max(mla_moe.widest_gaps(rows, few)) == 0.0
    assert min(mla_moe.widest_gaps(rows, many)) > 0.0


# -- the readers ---------------------------------------------------------------


def _route(program, assigned, hit):
    from repro.core.telemetry.tracer import Event
    return Event(0.0, "engine", "moe_route", "i", 0, None, 5, None,
                 {"program": program, "assigned": assigned, "hit": hit})


def test_the_moe_readers():
    dev = "/device:TPU:0"
    modules = [TraceEvent("jit__decode_fn", 1.0, 1.1)]
    ops = [TraceEvent("%gmm.3 = bf16[768,1536]{1,0} custom-call(...)",
                      1.0, 1.01),
           TraceEvent("%gmm = bf16[768,5120]{1,0} custom-call(...)",
                      1.02, 1.04),
           TraceEvent("%gmm_like.1 = f32[2] fusion(...)", 1.05, 1.06),
           TraceEvent("%fusion.7 = bf16[2] fusion(...)", 1.06, 1.08)]
    trace = Trace((1.0, 2.0), {dev: modules}, {dev: ops}, [])
    run = driver.Run(MODEL, V5E, 10.0, [], [], {}, trace=trace)
    run.traced_events = [_route("decode", [10, 6], [4, 3]),
                         _route("prefill", [300, 280], [4, 4])]
    per = 2.0 * roofline.expert_params(MODEL)
    least = roofline.least_time(16 * per, 7 * per, V5E) + \
        roofline.least_time(580 * per, 8 * per, V5E)
    bench = spec.Bench()
    assert bench.reader("moe_expert_roofline")(run) == \
        pytest.approx(100 * least / 0.03)
    run.traced_events = []
    assert bench.reader("moe_expert_roofline")(run) is None
    tokens = bench.reader("moe_tokens_per_hit")
    assert tokens(driver.Run(MODEL, V5E, 1.0, [], [], {
        "moe_decode_assigned": 96.0, "moe_decode_hit": 20.0})) == 4.8
    assert tokens(driver.Run(MODEL, V5E, 1.0, [], [], {})) is None


# -- the translation -----------------------------------------------------------

SERVED_BY_BOTH = sorted(
    glob.glob(os.path.join(spec.HERE, "configs", "*.json")) +
    glob.glob(os.path.join(FIXTURE, "configs", "*.json")))


@pytest.mark.parametrize("path", SERVED_BY_BOTH,
                         ids=[os.path.basename(p) for p in SERVED_BY_BOTH])
def test_the_programs_translation_keeps_every_file_the_harness_took(path):
    """``repro.configs.from_published`` gives every file that
    ``perfbench/published.py`` takes the same ``ArchConfig``; a file that
    only the program serves is the DeepSeek-V2 stage's."""
    from repro.configs import from_published
    with open(path) as f:
        conf = json.load(f)
    mine = from_published(conf["name"], conf["model"], conf["source"])
    try:
        theirs = published.from_published(conf["name"], conf["model"],
                                          conf["source"])
    except ValueError:
        assert conf["reference"] == "mla_moe"
        assert mine.rope_scaling is not None and not mine.tie_embeddings
        assert mine.n_router == conf["model"].get(
            "router_experts", conf["model"]["n_routed_experts"])
        assert (mine.router_groups, mine.router_topk_groups,
                mine.norm_topk) == (8, 3, False)
        return
    assert mine == theirs


def test_the_stage_file_translates_to_the_published_model():
    bench = spec.Bench()
    conf = bench.config("deepseek-v2-236b-5l")
    cfg = driver.arch_config("deepseek-v2-236b-5l", conf)
    assert (cfg.d_model, cfg.num_heads, cfg.kv_lora_rank, cfg.q_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.d_ff, cfg.moe_d_ff) == (5120, 128, 512, 1536, 128, 64, 128,
                                         12288, 1536)
    assert (cfg.num_experts, cfg.n_router, cfg.expert_offset, cfg.moe_top_k,
            cfg.num_shared_experts, cfg.routed_scale) == (20, 160, 0, 6, 2,
                                                          16.0)
    assert cfg.rope_scaling.factor == 40.0 and not cfg.tie_embeddings
    for key in conf["reduced"]:
        assert key in conf["model"]


def test_the_stage_file_states_its_published_keys_once_at_the_top():
    """The published keys stand at the file's top level as in the model's
    ``config.json`` and again under ``model``, which the harness reads;
    the two copies agree key for key, and only the ``reduced`` keys
    differ from the published model."""
    conf = spec.Bench().config("deepseek-v2-236b-5l")
    model = conf["model"]
    published_keys = set(model) - {"router_experts"}
    assert published_keys <= set(conf)
    assert {k: conf[k] for k in published_keys} == \
        {k: model[k] for k in published_keys}
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            model["router_experts"]) == (5, 20, 160)
    assert sorted(conf["reduced"]) == ["n_routed_experts",
                                       "num_hidden_layers"]
