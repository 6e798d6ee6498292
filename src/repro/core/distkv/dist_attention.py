"""DistAttention: sequence-sharded micro-attention (InfiniteLLM §III.D.2),
TPU-native.

The paper partitions a long KV cache into Micro Attentions (MAs), "each
handling a subset of KV cache tokens independently", then "aggregates their
results for the final attention computation". On TPU the KV sequence axis is
sharded across a mesh axis; each device runs the shard-local attention
producing partial ``(o, m, l)`` (flash-decoding-style), and the partials are
merged with the numerically-stable log-sum-exp combine over the mesh axis —
ICI collectives replace the paper's datacenter RDMA reads.

Used by the ``long_500k`` decode path (where it is what makes the shape
feasible) and exposed standalone for tests/benchmarks.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def micro_attention_partial(q, k, v, valid, *, scale: Optional[float] = None):
    """Shard-local Micro Attention (single-query view of
    :func:`attention_partial`).

    q: (B, H, Dh); k, v: (B, S_local, Hkv, Dh); valid: (B, S_local) bool.
    Returns (o_unnorm (B,H,Dh) fp32, m (B,H), l (B,H)) — un-normalized
    weighted values plus the local softmax statistics.
    """
    o, m, l = attention_partial(q[:, None], k, v, valid[:, None, :],
                                scale=scale)
    return o[:, 0], m[:, 0], l[:, 0]


def merge_partials(o, m, l, axis_name: str):
    """Log-sum-exp merge of micro-attention partials over a mesh axis.

    o: un-normalized (B,H,Dh); m, l: (B,H). Returns normalized (B,H,Dh).
    """
    m_glob = lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_glob)  # (B,H)
    l_glob = lax.psum(l * corr, axis_name)
    o_glob = lax.psum(o * corr[..., None], axis_name)
    return o_glob / jnp.maximum(l_glob, 1e-9)[..., None]


def attention_partial(q, k, v, mask, *, scale: Optional[float] = None):
    """Masked multi-query Micro Attention partial (the ``T > 1`` sibling of
    :func:`micro_attention_partial`, with a per-query mask).

    q: (B, T, H, Dh); k, v: (B, S, Hkv, Dh); mask: (B, T, S) bool — entry
    ``[b, t, s]`` says query ``t`` may attend key ``s`` (causality and
    validity folded into one mask by the caller). Returns
    ``(o_unnorm (B,T,H,Dh) fp32, m (B,T,H), l (B,T,H))`` ready for
    :func:`merge_partials_tree` — the pieces the engine's zero-copy paths
    merge across local pages and pages borrowed from a peer instance.
    """
    b, t, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    qg = q.reshape(b, t, hkv, g, dh).astype(jnp.float32)
    s = jnp.einsum("bthgd,bshd->bthgs", qg, k.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, :, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # (b,t,hkv,g)
    m_safe = jnp.maximum(m, -1e30)  # fully-masked queries must not NaN
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask[:, :, None, None, :], p, 0.0)
    l = p.sum(-1)
    o = jnp.einsum("bthgs,bshd->bthgd", p, v.astype(jnp.float32))
    return (o.reshape(b, t, h, dh), m_safe.reshape(b, t, h),
            l.reshape(b, t, h))


def merge_partials_tree(os, ms, ls):
    """Host-side merge across a *list* of partials (used by the engine when
    rBlocks of one sequence live on several instances)."""
    m_glob = jnp.max(jnp.stack(ms), axis=0)
    acc_o = 0.0
    acc_l = 0.0
    for o, m, l in zip(os, ms, ls):
        corr = jnp.exp(m - m_glob)
        acc_l = acc_l + l * corr
        acc_o = acc_o + o * corr[..., None]
    return acc_o / jnp.maximum(acc_l, 1e-9)[..., None]


def dist_attention(mesh, q, k, v, context_lens, *, axis: str = "model"):
    """Full DistAttention decode over a sequence-sharded KV cache.

    q: (B, H, Dh) replicated over ``axis``; k, v: (B, S, Hkv, Dh) with S
    sharded over ``axis``; context_lens: (B,).
    """
    s_total = k.shape[1]
    n_shards = mesh.shape[axis]
    s_local = s_total // n_shards

    def shard_fn(q_l, k_l, v_l, lens):
        idx = lax.axis_index(axis)
        pos = idx * s_local + jnp.arange(s_local)  # absolute positions
        valid = pos[None, :] < lens[:, None]
        o, m, l = micro_attention_partial(q_l, k_l, v_l, valid)
        return merge_partials(o, m, l, axis)

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None),
                  P()),
        out_specs=P(),
    )
    return fn(q, k, v, context_lens)


def dist_attention_ref(q, k, v, context_lens):
    """Unsharded oracle."""
    b, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    pos = jnp.arange(k.shape[1])
    valid = pos[None, :] < context_lens[:, None]
    qg = q.reshape(b, hkv, g, dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k.astype(jnp.float32)) / (dh ** 0.5)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(b, h, dh)
