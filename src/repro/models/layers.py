"""Pure-JAX building blocks shared by every architecture.

No flax: parameters are explicit nested-dict pytrees built by ``init_*``
functions and consumed by pure ``apply``-style functions. Every function takes
an optional :class:`ShardingPolicy` that inserts ``with_sharding_constraint``
annotations — models stay mesh-agnostic, the launcher supplies the policy.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


class ShardingPolicy:
    """Identity policy — no constraints. Launch code subclasses this."""

    def act(self, x, kind: str):
        """Constrain an activation. ``kind`` names the logical layout:

        tokens_bs, act_bsd, heads_bshd, ffn_bsf, logits_bsv, kv_bskd,
        expert_ecd, expert_ecf, state_bhpn
        """
        return x

    def param(self, x, kind: str):
        return x


NO_POLICY = ShardingPolicy()


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, *, bias: bool = False,
               scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * jnp.asarray(scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x, policy: ShardingPolicy = NO_POLICY, kind: Optional[str] = None):
    w = policy.param(p["w"], "matmul_weight")
    y = x @ w
    if "b" in p:
        y = y + p["b"]
    if kind is not None:
        y = policy.act(y, kind)
    return y


def norm_init(d: int, dtype, *, bias: bool = False):
    p = {"scale": jnp.ones((d,), dtype)}
    if bias:
        p["b"] = jnp.zeros((d,), dtype)
    return p


def rms_norm(p, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    y = x.astype(dt) * p["scale"]
    if "b" in p:
        y = y + p["b"]
    return y


def pad_last(x, target: int):
    """Zero-pad the trailing axis of ``x`` up to ``target`` elements.

    Used by the absorbed-MLA path to lift latent values to the effective
    key width (attention output is linear in v, so zero rows are inert)."""
    pad = target - x.shape[-1]
    if pad <= 0:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, scaling=None):
    """Inverse rotary frequencies, (head_dim/2,). ``scaling`` (a
    :class:`~repro.configs.YaRN`, or None for plain RoPE) blends them as
    DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` does: the interpolated
    frequencies (divided by ``factor``) below the correction range of
    ``beta_slow`` rotations over the original context, the original ones
    above that of ``beta_fast``, a linear ramp between."""
    extra = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if scaling is None:
        return extra

    def correction_dim(rotations):
        return head_dim * math.log(
            scaling.original_max_position / (rotations * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: the original frequency
    return extra / scaling.factor * (1.0 - keep) + extra * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature, ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float, *, heads: bool = True,
               scaling=None):
    """x: (..., S, H, D) if ``heads`` else (..., S, D).

    ``positions``: (S,) shared across batch, or batched (..., S).
    ``scaling``: a :class:`~repro.configs.YaRN` or None (plain RoPE, the
    same ops as without it); under YaRN cos and sin are also multiplied by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
    """
    d = x.shape[-1]
    inv = rope_freqs(d, theta, scaling)  # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, d/2)
    if heads:
        ang = ang[..., None, :]  # broadcast over the heads axis
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) / \
            yarn_mscale(scaling.factor, scaling.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, ff: int, dtype, *, gated: bool, bias: bool = False):
    ks = jax.random.split(key, 3)
    p = {"up": dense_init(ks[0], d, ff, dtype, bias=bias),
         "down": dense_init(ks[1], ff, d, dtype, bias=bias)}
    if gated:
        p["gate"] = dense_init(ks[2], d, ff, dtype, bias=bias)
    return p


def mlp(p, x, policy: ShardingPolicy = NO_POLICY):
    up = dense(p["up"], x, policy, "ffn_bsf")
    if "gate" in p:
        h = jax.nn.silu(dense(p["gate"], x, policy, "ffn_bsf")) * up
    else:
        h = jax.nn.gelu(up)
    return dense(p["down"], h, policy, "act_bsd")


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def pad_vocab(vocab: int, multiple: int = 512) -> int:
    """Megatron-style vocab padding so the vocab axis shards cleanly."""
    return ((vocab + multiple - 1) // multiple) * multiple


def embed_init(key, vocab: int, d: int, dtype):
    v = pad_vocab(vocab)
    return {"table": jax.random.normal(key, (v, d), dtype) * 0.02}


def head_init(key, vocab: int, d: int, dtype):
    """An untied output head, (D, padded V), at the embedding's scale."""
    return {"w": jax.random.normal(key, (d, pad_vocab(vocab)), dtype) * 0.02}


def embed(p, tokens, policy: ShardingPolicy = NO_POLICY):
    return policy.act(jnp.take(p["table"], tokens, axis=0), "act_bsd")


def output_head(params):
    """The unembedding's parameters: the model's own ``lm_head`` where its
    embeddings are untied, else the embedding table."""
    return params.get("lm_head", params["embed"])


def unembed(p, x, vocab: int, policy: ShardingPolicy = NO_POLICY,
            fp32: bool = False):
    """Project hidden states to vocab logits through ``p``: an untied head
    ``{"w": (D, V)}`` or the tied embedding ``{"table": (V, D)}``
    (:func:`output_head` picks). ``fp32`` computes the projection in
    float32: bf16 logits round near-equal candidates onto the same value,
    so greedy argmax between two implementations can diverge on the
    tie-break even when both are correct (ArchConfig.logits_fp32)."""
    w = p["w"] if "w" in p else p["table"].T
    if fp32:
        logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    else:
        logits = x @ w
    logits = policy.act(logits, "logits_bsv")
    # mask padded vocab entries so they never win a softmax/argmax
    v_pad = w.shape[1]
    if v_pad != vocab:
        mask = jnp.arange(v_pad) < vocab
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    return logits


def cross_entropy(logits, labels, vocab: int):
    """Mean token loss in fp32; labels < 0 are masked out.

    The gold logit is extracted with a masked sum over the vocab axis rather
    than ``take_along_axis`` — a gather along a *sharded* vocab dimension
    would force GSPMD to all-gather the full logits; the masked sum reduces
    locally and psums (Megatron-style vocab-parallel loss)."""
    logits = logits.astype(jnp.float32)
    valid = labels >= 0
    labels_c = jnp.clip(labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    vocab_iota = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels_c[..., None], logits, 0.0),
                   axis=-1)
    loss = (lse - gold) * valid
    return loss.sum() / jnp.maximum(valid.sum(), 1)
