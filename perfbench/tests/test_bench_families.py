"""Configurations of another family enter the benchmark by files alone:
the translation of published keys, weights for stacks of matrices, the
counts of latent attention (MLA) and sparse experts (MoE) with the
routing read from the run record, and the engine's counters in it. The
two configurations the benchmark already runs read exactly what they
read before: their ``ArchConfig``, weights and counts are pinned here."""

import dataclasses
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from perfbench import driver, generator, published, roofline, spec, weights
from perfbench.tracefile import Event as TraceEvent, Trace
from repro.core.telemetry.tracer import Event

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tiny")
MLA_MOE_FILE = os.path.join(FIXTURE, "configs", "tiny-mla-moe.json")
with open(MLA_MOE_FILE) as f:
    MLA_MOE = json.load(f)
TINY = MLA_MOE["model"]
V5E = roofline.peaks("TPU v5 lite")
SEED = 2 ** 31 + 777

# DeepSeek-V2's published config.json
# (huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json)
DEEPSEEK_V2 = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 3, "topk_method": "group_limited_greedy",
    "v_head_dim": 128, "vocab_size": 102400, "torch_dtype": "bfloat16"}


def _config(name):
    return spec.Bench().config(name)


# -- what must not move -------------------------------------------------------

# the parent's ArchConfig of each existing file, field by field (the fields
# not listed keep the ArchConfig defaults)
PINNED_ARCH = {
    "h2o-danube-1.8b": dict(
        arch_id="h2o-danube-1.8b", family="dense",
        source="https://arxiv.org/abs/2401.16818", num_layers=24,
        d_model=2560, num_heads=32, num_kv_heads=8, head_dim=80, d_ff=6912,
        vocab_size=32000, attention="gqa", sliding_window=4096,
        max_seq_len=16384, rope_theta=10000.0, norm_eps=1e-05,
        tie_embeddings=True, dtype="bfloat16"),
    "mistral-large-123b-3l": dict(
        arch_id="mistral-large-123b-3l", family="dense",
        source="https://huggingface.co/mistralai/Mistral-Large-Instruct-2407",
        num_layers=3, d_model=12288, num_heads=96, num_kv_heads=8,
        head_dim=128, d_ff=28672, vocab_size=32768, attention="gqa",
        sliding_window=None, max_seq_len=131072, rope_theta=1000000.0,
        norm_eps=1e-05, tie_embeddings=True, dtype="bfloat16"),
}


@pytest.mark.parametrize("name", sorted(PINNED_ARCH))
def test_translation_of_the_existing_files_is_unchanged(name):
    from repro.configs import ArchConfig
    want = ArchConfig(**PINNED_ARCH[name])
    got = driver.arch_config(name, _config(name))
    assert dataclasses.asdict(got) == dataclasses.asdict(want)
    conf = _config(name)
    assert published.from_published(name, conf["model"], conf["source"]) \
        == want


# sha256 of the bf16 bits of each 2-D-per-layer leaf of the tiny dense
# plan, from seed 2**31 + 777, as the parent made them
PINNED_LEAVES = {
    "embed/table":
        "f4d2dba379fc85a6f9ce23c2e94f5457ff9db79dd87ca7ae135b6a2b01a5a46a",
    "segments/0/attn/wk/w":
        "daf238c6e32c06a96a35cd6b5071846800e8f12826dd46d5b423f113ee34f412",
    "segments/0/attn/wq/w":
        "4c5bed72ea16e99be72a71dfbddff8016db6fd624eeaad83d15b6b928c233314",
    "segments/0/mlp/down/w":
        "4867cfd742b69248a180d77df4bf97053b5e77c98ba6aa93ce24d0a90ee33b23",
    "segments/0/mlp/gate/w":
        "751c0ea9db7ef893e6bc7fe963aecbcc2f5507b2ce09de3ba3428ae50c058276",
}


@pytest.fixture(scope="module")
def tiny_dense_params():
    from repro.models import Model
    conf = spec.Bench(root=FIXTURE, base=FIXTURE).config("tiny")
    model = Model(driver.arch_config("tiny", conf), remat=False)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, [s.n for s in model.plan], SEED)
    return {weights._name(p): np.asarray(a) for p, a in
            jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.mark.parametrize("name", sorted(PINNED_LEAVES))
def test_matrix_leaves_are_bit_identical(tiny_dense_params, name):
    bits = tiny_dense_params[name].view(np.uint16).tobytes()
    assert hashlib.sha256(bits).hexdigest() == PINNED_LEAVES[name]


CTXS = [[1], [100, 5000, 16000], [4096, 4097, 8191, 2, 777]]
CHUNKS = [(0, 1), (0, 2048), (3000, 2048), (4095, 3), (5000, 700),
          (6144, 2048)]
# the parent's decode (flops, bytes) over CTXS and prefill flops over CHUNKS
PINNED_COUNTS = {
    "h2o-danube-1.8b": (
        [(3498557440.0, 3498685440.0), (12532776960.0, 4008207360.0),
         (20702904320.0, 4301706240.0)],
        [3498557440.0, 7344809574400.0, 8743275397120.0, 13187153920.0,
         3038937088000.0, 8890746142720.0]),
    "mistral-large-123b-3l": (
        [(9110175744.0, 9110224896.0), (30441406464.0, 9369513984.0),
         (48080928768.0, 9321160704.0)],
        [9110175744.0, 17318264438784.0, 18224234102784.0, 27531853824.0,
         6366384979968.0, 19173690310656.0]),
}


@pytest.mark.parametrize("name", sorted(PINNED_COUNTS))
def test_counts_of_the_existing_files_are_unchanged(name):
    m = _config(name)["model"]
    decode, prefill = PINNED_COUNTS[name]
    assert [(roofline.decode_flops(m, c), roofline.decode_bytes(m, c))
            for c in CTXS] == decode
    assert [roofline.prefill_flops(m, s, n) for s, n in CHUNKS] == prefill
    # a routing record means nothing to a dense model
    assert roofline.routes(m, [], "decode", 3) == [None] * 3


# -- the translation ----------------------------------------------------------


def test_the_tiny_mla_moe_file_uses_only_honoured_keys():
    cfg = published.from_published("t", TINY, "src")
    assert (cfg.family, cfg.attention) == ("moe", "mla")
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (32, 48, 16, 8, 16)
    assert (cfg.num_experts, cfg.moe_top_k, cfg.moe_d_ff,
            cfg.num_shared_experts, cfg.first_k_dense) == (4, 2, 32, 1, 1)
    # dropless, as the published routing is: every expert can take
    # every token
    assert cfg.capacity_factor * cfg.moe_top_k / cfg.num_experts == 1.0


@pytest.mark.parametrize("key", ["rope_scaling", "topk_method", "n_group",
                                 "topk_group", "norm_topk_prob",
                                 "routed_scaling_factor",
                                 "tie_word_embeddings"])
def test_deepseek_v2_is_refused_by_each_key_it_needs(key):
    with pytest.raises(ValueError) as e:
        published.from_published("deepseek-v2", DEEPSEEK_V2, "src")
    assert f"{key}=" in str(e.value)
    # each one alone is enough to refuse
    one = dict(TINY, **{key: DEEPSEEK_V2[key]})
    with pytest.raises(ValueError, match=f"^t: {key}="):
        published.from_published("t", one, "src")


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("attention_bias", True), ("moe_layer_freq", 2),
    ("scoring_func", "sigmoid"), ("router_experts", 160),
    ("num_key_value_heads", 2), ("sliding_window", 64), ("head_dim", 16),
    ("an_unknown_key", 1)])
def test_other_values_the_program_does_not_serve_are_refused(key, value):
    with pytest.raises(ValueError, match=f"^t: {key}="):
        published.from_published("t", dict(TINY, **{key: value}), "src")


@pytest.mark.parametrize("key", ["torch_dtype", "qk_rope_head_dim",
                                 "moe_intermediate_size"])
def test_a_file_without_a_key_its_family_needs_is_refused(key):
    short = {k: v for k, v in TINY.items() if k != key}
    with pytest.raises(ValueError, match=f"^t: {key}=None"):
        published.from_published("t", short, "src")


# -- weights for stacks of matrices -------------------------------------------


def test_the_stack_rule_is_the_one_matrix_function():
    key = weights.base_key(SEED)
    got = weights.leaf(key, "segments/1/mlp/down", (2, 3, 8, 16),
                       jax.numpy.bfloat16, 2, 1)
    assert got.shape == (2, 3, 8, 16)
    for j in range(2):
        for i in range(3):
            one = weights.matrix(key, "segments/1/mlp/down", 1 + j, 8, 16,
                                 index=i)
            assert (np.asarray(got[j, i]) == np.asarray(one)).all()
    # experts differ from each other and from the plain matrix
    plain = weights.matrix(key, "segments/1/mlp/down", 1, 8, 16)
    assert not (np.asarray(got[0, 0]) == np.asarray(got[0, 1])).all()
    assert not (np.asarray(got[0, 0]) == np.asarray(plain)).all()


# -- a new family through driver.build and LLMService -------------------------


def test_an_mla_moe_file_is_built_and_served_by_files_alone(tmp_path):
    """The fixture file goes in as a later PR's would: a configuration
    entry, its file and a cell; nothing of the harness is edited."""
    with open(os.path.join(FIXTURE, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "tiny-mla-moe", "source": "test",
                           "file": "configs/tiny-mla-moe.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "tiny-mla-moe-chat",
                             "config": "tiny-mla-moe", "traffic": "chat",
                             "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "tiny-mla-moe.json").write_text(
        open(MLA_MOE_FILE).read())
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "chat.json").write_text(
        open(os.path.join(FIXTURE, "traffic", "chat.json")).read())
    bench = spec.Bench(root=str(tmp_path), base=str(tmp_path))
    cell = bench.cell("tiny-mla-moe-chat")
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])

    engine, params = driver.build(cell["config"], conf, SEED, telemetry=True)
    # the expert stacks come from the seed, one matrix at a time
    key = weights.base_key(SEED)
    gate = np.asarray(params["segments"][1]["mlp"]["gate"])
    d, ff = TINY["hidden_size"], TINY["moe_intermediate_size"]
    assert gate.shape == (2, 4, d, ff)
    for j, i in ((0, 0), (1, 3)):
        one = weights.matrix(key, "segments/1/mlp/gate", 1 + j, d, ff,
                             index=i)
        assert (gate[j, i] == np.asarray(one)).all()

    from repro.serving.api import LLMService
    svc = LLMService(engine)
    driver.warm_up(svc, engine.ecfg, mix, TINY["vocab_size"])
    traffic = generator.Traffic(mix, SEED, TINY["vocab_size"])
    recs, polls, counters, _, _ = driver.drive(
        svc, engine, mix, traffic, 1.0, None, driver.Compiles())
    served = [r for r in recs if r.tokens]
    assert served and all(0 <= t < TINY["vocab_size"]
                          for r in served for t in r.tokens)
    assert any(len(r.tokens) > 1 for r in served)
    # the engine's counters reach the run record, beside the prefix cache's
    assert counters["decode_table_pages"] > 0
    assert "prefix_hit_tokens" in counters and \
        "prefix_lookup_tokens" in counters
    run = driver.Run(conf["model"], V5E, 1.0, recs, polls, counters)
    share = bench.reader("decode_live_share")(run)
    assert 0 < share <= 100


# -- counts of MLA and MoE ----------------------------------------------------


def test_mla_counts_at_deepseek_v2_width():
    m = DEEPSEEK_V2
    layers = m["num_hidden_layers"]
    # wq_a, wq_b, wkv_a, wkv_b, wo
    assert roofline.attn_params(m) == 149_225_472
    assert roofline.kv_bytes_per_token(m) == 1152 * layers
    assert roofline._attn_flops_per_key(m) == 278_528 * layers
    # the decompressed form: 2 H (dn + dr + dv) per attended key and
    # 2 r H (dn + dv) per key decompressed
    assert 2 * 128 * (128 + 64 + 128) == 81_920
    assert 2 * roofline._wkv_b(m) == 33_554_432
    # the router keeps its published width where a share is held
    share = dict(m, n_routed_experts=20, router_experts=160)
    assert roofline.token_params(share) == roofline.token_params(m)


def test_tiny_mla_moe_counts_by_hand():
    # attention per layer: wq_a 64x48 + wq_b 48x(4x24) + wkv_a 64x40 +
    # wkv_b 32x(4x32) + wo 64x64 = 3072 + 4608 + 2560 + 4096 + 4096
    assert roofline.attn_params(TINY) == 18432
    # 3 attentions + 1 dense MLP (3x64x128) + 2 x (router 64x4 + one
    # shared expert 3x64x32)
    assert roofline.token_params(TINY) == 3 * 18432 + 24576 + 2 * 6400
    assert roofline.matmul_params(TINY) == 92672 + 256 * 64
    # + norms (2 x 64 + 32 + 48 per layer) + final norm + tied table
    assert roofline.weight_bytes(TINY) == 2 * (92672 + 3 * 208 + 64 + 16384)
    assert roofline.kv_bytes_per_token(TINY) == 3 * 40 * 2

    route = {"program": "decode", "assigned": [4, 3], "hit": [3, 2]}
    # 2 x 2 x 109056 + 3 x 2 x 4 x (2 x 32 + 8) x (10 + 20) + 2 x 6144 x 7
    assert roofline.decode_flops(TINY, [10, 20], route) == \
        436224 + 51840 + 86016
    # weights + 240 B x 30 keys read + 2 written + 5 experts hit x 2 x 6144
    assert roofline.decode_bytes(TINY, [10, 20], route) == \
        219488 + 7200 + 480 + 61440
    chunk = {"program": "prefill", "assigned": [32, 32], "hit": [4, 4]}
    # 16 queries after 8 cached: 264 attended keys, 24 decompressed; the
    # decompressed form (2 x 80384 x 16 + 32768 + 960 x 264 + 24576 x 24)
    # beats the absorbed one (2 x 92672 x 16 + 32768 + 1728 x 264)
    assert roofline.prefill_flops(TINY, 8, 16, chunk) == \
        3448320 + 2 * 6144 * 64
    # far into a context the absorbed form is the lesser
    assert roofline.prefill_flops(TINY, 1000, 4, chunk) == \
        7703424 + 2 * 6144 * 64
    # without a routing record an MoE step has no count
    assert roofline.decode_flops(TINY, [10, 20]) is None
    assert roofline.decode_bytes(TINY, [10, 20]) is None
    assert roofline.prefill_flops(TINY, 8, 16) is None


def test_the_counts_match_the_program_tree():
    from repro.models import Model
    cfg = published.from_published("t", TINY, "src")
    shapes = jax.eval_shape(Model(cfg, remat=False).init,
                            jax.random.PRNGKey(0))
    seg = shapes["segments"][1]
    attn = sum(np.prod(v["w"].shape[1:]) for v in seg["attn"].values()
               if "w" in v)
    assert attn == roofline.attn_params(TINY)
    experts = seg["mlp"]["gate"].shape[1]
    assert 3 * np.prod(seg["mlp"]["gate"].shape[2:]) == \
        roofline.expert_params(TINY) and experts == TINY["n_routed_experts"]


def _route(program, assigned, hit, it=5):
    return Event(0.0, "engine", "moe_route", "i", 0, None, it, None,
                 {"program": program, "assigned": assigned, "hit": hit})


def _moe_run(events):
    dev = "/device:TPU:0"
    modules = [TraceEvent("jit__prefill_chunk_fn", 1.0, 1.2),
               TraceEvent("jit__decode_fn", 1.3, 1.35),
               TraceEvent("jit__decode_fn", 1.5, 1.55)]
    trace = Trace((1.0, 2.0), {dev: modules}, {dev: list(modules)}, [])
    run = driver.Run(TINY, V5E, 10.0, [], [], {}, trace=trace)
    run.traced_decodes = [[10, 20], [11]]
    run.traced_chunks = [(8, 16)]
    run.traced_events = events
    return run


def test_moe_readers_take_the_routing_from_the_run_record():
    bench = spec.Bench()
    dec = [_route("decode", [4, 3], [3, 2]), _route("decode", [2, 2], [2, 2])]
    pre = [_route("prefill", [32, 32], [4, 4])]
    other = [Event(0.0, "engine", "chunk", "i", 0, None, 5, None,
                   {"start": 8, "length": 16, "last": True})]
    run = _moe_run(other + dec[:1] + pre + dec[1:])
    routes = [e.args for e in dec]
    least = sum(roofline.least_time(roofline.decode_flops(TINY, c, r),
                                    roofline.decode_bytes(TINY, c, r), V5E)
                for c, r in zip(run.traced_decodes, routes))
    assert bench.reader("decode_step_roofline")(run) == \
        pytest.approx(100 * least / 0.1)
    flops = sum(roofline.decode_flops(TINY, c, r)
                for c, r in zip(run.traced_decodes, routes)) + \
        roofline.prefill_flops(TINY, 8, 16, pre[0].args)
    assert bench.reader("step_mfu")(run) == \
        pytest.approx(100 * flops / (0.3 * 197e12))


@pytest.mark.parametrize("events", [[], [_route("decode", [4, 3], [3, 2])]],
                         ids=["none", "too-few"])
def test_moe_readers_read_nothing_without_a_routing_record(events):
    bench = spec.Bench()
    run = _moe_run(events)
    assert bench.reader("decode_step_roofline")(run) is None
    assert bench.reader("step_mfu")(run) is None


def test_decode_live_share_reads_the_engine_counters():
    bench = spec.Bench()
    run = driver.Run({}, {}, 1.0, [], [], {"decode_live_pages": 25.0,
                                           "decode_table_pages": 100.0})
    assert bench.reader("decode_live_share")(run) == 25.0
    assert bench.reader("decode_live_share")(
        driver.Run({}, {}, 1.0, [], [], {})) is None
