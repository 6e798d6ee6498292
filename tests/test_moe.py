"""The expert layer and the rotary scaling that DeepSeek-V2 needs, on the
CPU at a small size: DeepSeek-V2's routing, the dropless grouped expert
path against a dense every-expert computation (with JAX's ragged dot and
with the TPU's grouped-matmul kernel in interpret mode), the routing
counts the engine's telemetry reads, and YaRN, which must leave the plain
rotary path exactly as it was where ``rope_scaling`` is null."""

import dataclasses
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ArchConfig, YaRN, get_config, smoke_config
from repro.models import Model, moe
from repro.models import attention as attn
from repro.models.layers import apply_rope, rope_freqs

D, FF = 32, 16


def _cfg(**kw):
    base = dict(arch_id="t", family="moe", source="test", num_layers=1,
                d_model=D, num_heads=2, num_kv_heads=2, head_dim=16,
                vocab_size=64, num_experts=4, moe_top_k=2, moe_d_ff=FF,
                dtype="float32")
    base.update(kw)
    return ArchConfig(**base)


def _params(cfg, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    e = cfg.num_experts
    return {"router": {"w": jax.random.normal(ks[0], (D, cfg.n_router))},
            "gate": jax.random.normal(ks[1], (e, D, FF)) / math.sqrt(D),
            "up": jax.random.normal(ks[2], (e, D, FF)) / math.sqrt(D),
            "down": jax.random.normal(ks[3], (e, FF, D)) / math.sqrt(FF)}


def _dense_every_expert(cfg, p, x, weights, experts):
    """Every held expert over every token, weighted by the token's routing
    weight for it (zero where the token did not choose it)."""
    outs = jnp.stack([
        (jax.nn.silu(x @ p["gate"][e]) * (x @ p["up"][e])) @ p["down"][e]
        for e in range(cfg.num_experts)])                    # (E, T, D)
    held = cfg.expert_offset + jnp.arange(cfg.num_experts)
    w = ((experts[..., None] == held) * weights[..., None]).sum(1)  # (T, E)
    return jnp.einsum("te,etd->td", w, outs)


CASES = {
    # every expert held, normalised top-2
    "whole": dict(),
    # one group of 4 held of a group-limited router over 16 (4 groups, the
    # best 2 kept), unnormalised weights times 16: DeepSeek-V2's shape
    "share": dict(router_experts=16, expert_offset=4, router_groups=4,
                  router_topk_groups=2, moe_top_k=3, norm_topk=False,
                  routed_scale=16.0),
    # the last group held
    "last-share": dict(router_experts=16, expert_offset=12, router_groups=4,
                       router_topk_groups=2, moe_top_k=3, norm_topk=False,
                       routed_scale=16.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("skewed", [False, True], ids=["even", "skewed"])
def test_grouped_experts_equal_every_expert_computed_densely(case, skewed):
    cfg = _cfg(**CASES[case])
    p = _params(cfg)
    if skewed:  # one expert takes nearly every token, others none
        w = p["router"]["w"].at[:, cfg.expert_offset].add(4.0)
        p = dict(p, router={"w": w})
    x = jax.random.normal(jax.random.PRNGKey(7), (37, D))
    weights, experts, _ = moe.route(cfg, p["router"]["w"], x)
    got, assigned, hit = moe.grouped_experts(cfg, p, x, weights, experts)
    want = _dense_every_expert(cfg, p, x, weights, experts)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    local = np.asarray(experts) - cfg.expert_offset
    mine = (local >= 0) & (local < cfg.num_experts)
    assert int(assigned) == mine.sum()
    assert int(hit) == len(set(local[mine].tolist()))


@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_that_are_no_tokens_are_neither_computed_nor_counted(case):
    """A chunk's padding and idle decode slots (``valid`` False) add no
    output and no assignment; the token rows are as without the mask."""
    cfg = _cfg(**CASES[case])
    p = _params(cfg, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 19, D))
    valid = jnp.arange(19)[None, :] < jnp.array([[13], [19]])
    whole, (a_all, _), (experts, _) = moe.moe_layer(cfg, p, x)
    got, (assigned, hit), _ = moe.moe_layer(cfg, p, x, valid=valid)
    np.testing.assert_allclose(got[valid], whole[valid], rtol=1e-6,
                               atol=1e-6)
    assert not np.asarray(got[~valid]).any()
    local = np.asarray(experts).reshape(2, 19, -1) - cfg.expert_offset
    mine = (local >= 0) & (local < cfg.num_experts) & \
        np.asarray(valid)[..., None]
    assert int(assigned) == mine.sum() < int(a_all)
    assert int(hit) == len(set(local[mine].tolist()))


def test_the_tpu_kernel_path_equals_the_plain_one(monkeypatch):
    """The grouped matmul kernel of JAX's megablox, in interpret mode: its
    rows past the held assignments are left undefined, and the layer must
    not let them through."""
    import jax.experimental.pallas.ops.tpu.megablox  # noqa: F401
    kernel = sys.modules["jax.experimental.pallas.ops.tpu.megablox.gmm"]
    cfg = _cfg(**CASES["share"])
    p = _params(cfg, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(8), (29, D))
    weights, experts, _ = moe.route(cfg, p["router"]["w"], x)
    plain = moe.grouped_experts(cfg, p, x, weights, experts)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        jax.experimental.pallas.ops.tpu.megablox, "gmm",
        lambda *a: kernel.gmm(*a, interpret=True))
    tiled = jax.jit(lambda *a: moe.grouped_experts(cfg, *a))(
        p, x, weights, experts)
    assert bool(jnp.isfinite(tiled[0]).all())
    np.testing.assert_allclose(tiled[0], plain[0], rtol=1e-5, atol=1e-5)
    assert [int(a) for a in tiled[1:]] == [int(a) for a in plain[1:]]


def test_group_limited_routing_keeps_the_best_groups():
    cfg = _cfg(**CASES["share"])
    p = _params(cfg, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(9), (64, D))
    weights, experts, scores = moe.route(cfg, p["router"]["w"], x)
    scores, experts = np.asarray(scores), np.asarray(experts)
    groups = scores.reshape(64, 4, 4).max(-1)
    for t in range(64):
        kept = set(np.argsort(-groups[t])[:2].tolist())
        assert {e // 4 for e in experts[t]} <= kept
        allowed = [e for e in range(16) if e // 4 in kept]
        best = sorted(allowed, key=lambda e: -scores[t, e])[:3]
        assert sorted(experts[t].tolist()) == sorted(best)
        # unnormalised: the scores times the scaling factor
        np.testing.assert_allclose(np.asarray(weights)[t],
                                   16.0 * scores[t, experts[t]], rtol=1e-6)


def test_the_expert_parallel_mesh_path_routes_as_the_layer():
    """``MeshPolicy.moe_apply`` routes with ``moe.route`` too (one routing
    for both paths of the registry's DeepSeek-V2), and stands down for a
    share of the experts, which it has no exchange for."""
    from repro.launch.sharding import MeshPolicy
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = _cfg(**CASES["share"])
    policy = MeshPolicy(mesh, cfg)
    x = jnp.zeros((1, 4, D))
    assert policy.moe_apply(cfg, _params(cfg), x, False) is None


# -- YaRN -------------------------------------------------------------------


def _plain_rope(x, positions, theta, *, heads=True, scaling=None):
    """The rotary embedding as it was before YaRN."""
    assert scaling is None
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * inv
    if heads:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def test_null_rope_scaling_is_the_plain_rotary_path(monkeypatch):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 4, 16), jnp.bfloat16)
    pos = jnp.arange(9) + 100
    assert (np.asarray(apply_rope(x, pos, 1e4)) ==
            np.asarray(_plain_rope(x, pos, 1e4))).all()
    # a GQA model's logits are bit for bit those of the plain path
    cfg = smoke_config("h2o-danube-1.8b")
    assert cfg.rope_scaling is None
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0,
                                cfg.vocab_size)
    now, _ = model.forward(params, tokens)
    monkeypatch.setattr(attn, "apply_rope", _plain_rope)
    before, _ = model.forward(params, tokens)
    assert (np.asarray(now) == np.asarray(before)).all()


def test_yarn_follows_deepseek_v2():
    y = get_config("deepseek-v2-236b").rope_scaling
    assert y == YaRN(factor=40.0, original_max_position=4096,
                     beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                     mscale_all_dim=0.707)
    plain = np.asarray(rope_freqs(64, 1e4))
    inv = np.asarray(rope_freqs(64, 1e4, y))
    # yarn_find_correction_range(32, 1, 64, 10000, 4096) = (10, 23):
    # original frequencies up to index 10, divided by 40 from index 23, a
    # linear ramp between
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(1e4)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(1e4)))
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(inv[:low + 1], plain[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(inv[high:], plain[high:] / 40, rtol=1e-6)
    ramp = (np.arange(low + 1, high) - low) / (high - low)
    np.testing.assert_allclose(
        inv[low + 1:high], plain[low + 1:high] * (1 - ramp + ramp / 40),
        rtol=1e-6)
    # cos and sin keep their scale (mscale equals mscale_all_dim); the
    # softmax scale grows by mscale(40, 0.707) squared
    cfg = dataclasses.replace(smoke_config("deepseek-v2-236b"),
                              qk_nope_head_dim=128, qk_rope_head_dim=64)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert attn._mla_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                 rel=1e-12)
    assert m * m == pytest.approx(1.5897, abs=1e-4)
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 2, 64))
    pos = jnp.arange(5)
    assert np.allclose(np.linalg.norm(apply_rope(x, pos, 1e4, scaling=y),
                                      axis=-1),
                       np.linalg.norm(x, axis=-1), rtol=1e-5)
