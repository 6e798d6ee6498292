"""Attention variants: GQA (+MQA, sliding window) and MLA (DeepSeek-V2).

Two execution regimes:

* **train / prefill** — full-sequence blockwise attention (flash-style scan
  over query chunks; pure XLA ops so the dry-run's ``cost_analysis`` sees the
  true FLOPs/bytes). The Pallas kernels in ``repro.kernels`` implement the
  same math for the serving engine; ``ops.use_pallas`` switches paths.
* **decode** — one query token against a KV cache. The cache is a ring buffer
  of capacity ``Sc`` (``Sc < seq_len`` for sliding-window layers — this is what
  makes ``long_500k`` bounded-memory); each slot remembers the absolute
  position it holds so masking works after wraparound.

MLA decode uses the matrix-absorption trick: only the 512-d latent + 64-d
rope-key are cached (the paged "KV" for DeepSeek is the latent — see
DESIGN.md §2.3), and W_UK / W_UV are folded into the query/output sides.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import (NO_POLICY, ShardingPolicy, apply_rope, dense,
                                 dense_init, mlp, norm_init, pad_last,
                                 rms_norm)

# The dry-run's cost-model compiles set this so the query-chunk scan unrolls:
# XLA's cost analysis counts a while body once regardless of trip count, so
# attention FLOPs would otherwise be undercounted by the chunk count.
CHUNK_UNROLL = False


class KVCache(NamedTuple):
    """Ring-buffer KV cache for one layer group. Leaves may carry a leading
    stacked-layer axis when used under ``lax.scan``."""

    k: jax.Array  # (B, Sc, Hkv, Dh)
    v: jax.Array  # (B, Sc, Hkv, Dh)
    pos: jax.Array  # (B, Sc) absolute position per slot, -1 = empty


class MLACache(NamedTuple):
    ckv: jax.Array  # (B, Sc, r)       compressed kv latent
    krope: jax.Array  # (B, Sc, dr)    pre-roped shared rope key
    pos: jax.Array  # (B, Sc)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def gqa_init(cfg, key, dtype):
    ks = jax.random.split(key, 4)
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": dense_init(ks[0], d, h * dh, dtype, bias=cfg.use_bias),
        "wk": dense_init(ks[1], d, hkv * dh, dtype, bias=cfg.use_bias),
        "wv": dense_init(ks[2], d, hkv * dh, dtype, bias=cfg.use_bias),
        "wo": dense_init(ks[3], h * dh, d, dtype, bias=cfg.use_bias),
    }


def mla_init(cfg, key, dtype):
    ks = jax.random.split(key, 6)
    d, h = cfg.d_model, cfg.num_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    p = {
        "wkv_a": dense_init(ks[0], d, r + dr, dtype),
        "kv_norm": norm_init(r, dtype),
        "wkv_b": dense_init(ks[1], r, h * (dn + dv), dtype),
        "wo": dense_init(ks[2], h * dv, d, dtype),
    }
    if qr:
        p["wq_a"] = dense_init(ks[3], d, qr, dtype)
        p["q_norm"] = norm_init(qr, dtype)
        p["wq_b"] = dense_init(ks[4], qr, h * (dn + dr), dtype)
    else:
        p["wq"] = dense_init(ks[5], d, h * (dn + dr), dtype)
    return p


# ---------------------------------------------------------------------------
# full-sequence blockwise attention (train / prefill)
# ---------------------------------------------------------------------------

def _chunk_size(s: int) -> int:
    for c in (512, 256, 128, 64):
        if s % c == 0 and s >= c:
            return c
    return s


def blockwise_attention(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        q_offset: int = 0,
                        scale: Optional[float] = None,
                        policy: ShardingPolicy = NO_POLICY):
    """q: (B,S,H,Dh); k,v: (B,Skv,Hkv,Dh). GQA broadcast, fp32 softmax.

    Scans over query chunks so the score matrix never materializes at
    (S x Skv); per-chunk live memory is (B, C, H, Skv).
    ``q_offset``: absolute position of q[0] relative to k[0] (cross-attention
    passes causal=False and ignores it). ``scale`` overrides the default
    ``1/sqrt(Dh)`` (the absorbed-MLA path scores in a lifted latent dim but
    must scale by the *conceptual* head dim).
    """
    b, s, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // hkv
    c = _chunk_size(s)
    scale = (1.0 / math.sqrt(dh)) if scale is None else scale
    kg = k.astype(jnp.bfloat16)
    vg = v.astype(jnp.bfloat16)
    kv_pos = jnp.arange(skv)

    # GQA head layout for sharding: when the flat head count H divides the
    # model axis but Hkv does not (all 8-kv-head archs on a 16-way mesh),
    # broadcast K/V to H heads — the per-shard materialization is H_local
    # heads only, and scores then expose a shardable flat-h axis with a
    # fully local softmax. (Perf iteration 4.)
    flat_heads = bool(getattr(policy, "prefers_flat_heads", lambda a, b: False)(h, hkv))
    if flat_heads:
        kg = jnp.broadcast_to(kg[:, :, :, None, :], (b, skv, hkv, g, dh)
                              ).reshape(b, skv, h, dh)
        vg = jnp.broadcast_to(vg[:, :, :, None, :], (b, skv, hkv, g, dv)
                              ).reshape(b, skv, h, dv)
        kg = policy.act(kg, "kvrep_bshd")
        vg = policy.act(vg, "kvrep_bshd")

    def one_chunk(qc, qpos):
        mask = jnp.ones((qpos.shape[0], skv), dtype=bool)
        if causal:
            mask &= qpos[:, None] >= kv_pos[None, :]
        if window is not None:
            mask &= kv_pos[None, :] > qpos[:, None] - window
        if flat_heads:
            scores = jnp.einsum("bchd,bshd->bchs", qc.astype(jnp.bfloat16),
                                kg, preferred_element_type=jnp.float32)
            scores = policy.act(scores * scale, "scores_bchs")
            scores = jnp.where(mask[None, :, None, :], scores, -jnp.inf)
            probs = policy.act(jax.nn.softmax(scores, -1), "scores_bchs")
            out = jnp.einsum("bchs,bshd->bchd", probs.astype(jnp.bfloat16),
                             vg, preferred_element_type=jnp.float32)
            return out.astype(q.dtype)
        # grouped path: (B,C,H,Dh) -> (B,C,Hkv,G,Dh)
        qc = qc.reshape(b, -1, hkv, g, dh)
        scores = jnp.einsum("bchgd,bshd->bchgs", qc.astype(jnp.bfloat16), kg,
                            preferred_element_type=jnp.float32) * scale
        scores = policy.act(scores, "scores_bchgs")
        scores = jnp.where(mask[None, :, None, None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        probs = policy.act(probs, "scores_bchgs")
        out = jnp.einsum("bchgs,bshd->bchgd", probs.astype(jnp.bfloat16), vg,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, -1, h, dv).astype(q.dtype)

    if s == c:
        return one_chunk(q, q_offset + jnp.arange(s))

    nq = s // c
    qs = q.reshape(b, nq, c, h, dh).transpose(1, 0, 2, 3, 4)
    qpos = (q_offset + jnp.arange(s)).reshape(nq, c)

    # flash-attention backward semantics: recompute scores per chunk instead
    # of saving every chunk's score residuals for the whole sequence
    chunk_fn = jax.checkpoint(one_chunk)

    def body(_, qc_pos):
        qc, pos = qc_pos
        return None, chunk_fn(qc, pos)

    _, outs = lax.scan(body, None, (qs, qpos),
                       unroll=nq if CHUNK_UNROLL else 1)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dv)


def gqa_layer(cfg, p, x, positions, attend, *,
              policy: ShardingPolicy = NO_POLICY, mlp_fn=None):
    """One full GQA transformer layer, parameterized by the attention
    callable — the single layer body shared by the models' full-sequence
    path, the engine's fused paged decode, and the cached-prefix suffix
    prefill (which previously hand-rolled three copies of it).

    ``x``: (B, S, D); ``positions``: (S,) or (B, S) absolute positions.
    ``attend(q, k, v) -> (ctx, carry)`` receives roped q (B, S, H, Dh) and
    roped k / raw v (B, S, Hkv, Dh), returns the attention context
    (B, S, H, Dv) plus an arbitrary carry (e.g. updated KV page buffers)
    threaded back to the caller. Layout: pre-norm, residual attention,
    pre-norm residual MLP. ``mlp_fn(p_mlp, h) -> out`` overrides the dense
    MLP (MoE segments pass their expert dispatch).
    """
    b, s, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    hn = rms_norm(p["ln1"], x, cfg.norm_eps)
    q = dense(p["attn"]["wq"], hn).reshape(b, s, h, dh)
    k = dense(p["attn"]["wk"], hn).reshape(b, s, hkv, dh)
    v = dense(p["attn"]["wv"], hn).reshape(b, s, hkv, dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = policy.act(q, "heads_bshd")
    k = policy.act(k, "kv_bshd")
    v = policy.act(v, "kv_bshd")
    ctx, carry = attend(q, k, v)
    ctx = policy.act(ctx, "heads_bshd")
    y = x + dense(p["attn"]["wo"], ctx.reshape(b, s, -1), policy, "act_bsd")
    h2 = rms_norm(p["ln2"], y, cfg.norm_eps)
    y = y + (mlp(p["mlp"], h2, policy) if mlp_fn is None
             else mlp_fn(p["mlp"], h2))
    return y, carry


def gqa_forward(cfg, p, x, positions, *, window=None, causal=True,
                policy: ShardingPolicy = NO_POLICY, kv_override=None,
                return_kv: bool = False):
    """Full-sequence GQA. ``kv_override=(k,v)`` implements cross-attention.

    Returns (out, (k, v) roped) — k/v for cache seeding during prefill.
    """
    b, s, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(p["wq"], x).reshape(b, s, h, dh)
    if kv_override is None:
        q = apply_rope(q, positions, cfg.rope_theta)  # no rope for cross-attn
    q = policy.act(q, "heads_bshd")
    if kv_override is None:
        k = dense(p["wk"], x).reshape(b, s, hkv, dh)
        v = dense(p["wv"], x).reshape(b, s, hkv, dh)
        k = apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override
    k = policy.act(k, "kv_bshd")
    v = policy.act(v, "kv_bshd")
    out = blockwise_attention(q, k, v, causal=causal, window=window,
                              policy=policy)
    out = policy.act(out, "heads_bshd")
    y = dense(p["wo"], out.reshape(b, s, h * dh), policy, "act_bsd")
    if return_kv:
        return y, (k, v)
    return y


def encode_kv(cfg, p, x):
    """Project encoder output to cross-attention K/V (no rope for cross-attn)."""
    b, s, _ = x.shape
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    k = dense(p["wk"], x).reshape(b, s, hkv, dh)
    v = dense(p["wv"], x).reshape(b, s, hkv, dh)
    return k, v


# ---------------------------------------------------------------------------
# decode (one token, ring-buffer cache)
# ---------------------------------------------------------------------------

def cache_update(cache_pos, pos):
    """slot index for absolute position ``pos`` in a ring of capacity Sc."""
    sc = cache_pos.shape[-1]
    return pos % sc


def _write_slot(buf, slot, new):
    """buf: (B, Sc, ...); new: (B, ...) written at per-batch ``slot``."""
    b = buf.shape[0]
    return buf.at[jnp.arange(b), slot].set(new.astype(buf.dtype))


def _decode_mask(cache_pos, pos, window):
    """(B, Sc) validity of each cache slot for query at absolute ``pos``."""
    valid = (cache_pos >= 0) & (cache_pos <= pos[:, None])
    if window is not None:
        valid &= cache_pos > (pos[:, None] - window)
    return valid


def gqa_decode(cfg, p, x, cache: KVCache, pos, *, window=None,
               policy: ShardingPolicy = NO_POLICY, kv_override=None):
    """x: (B,1,D); pos: (B,) absolute position of the new token.

    Returns (y (B,1,D), new_cache). With ``kv_override`` (cross-attention) the
    cache is the static encoder KV and is returned unchanged.
    """
    b = x.shape[0]
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(p["wq"], x).reshape(b, 1, h, dh)
    if kv_override is None:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        knew = dense(p["wk"], x).reshape(b, 1, hkv, dh)
        vnew = dense(p["wv"], x).reshape(b, 1, hkv, dh)
        knew = apply_rope(knew, pos[:, None], cfg.rope_theta)
        slot = cache_update(cache.pos, pos)
        cache = KVCache(
            k=_write_slot(cache.k, slot, knew[:, 0]),
            v=_write_slot(cache.v, slot, vnew[:, 0]),
            pos=_write_slot(cache.pos, slot, pos),
        )
        mask = _decode_mask(cache.pos, pos, window)  # (B, Sc)
        k, v = cache.k, cache.v
    else:
        k, v = kv_override
        mask = jnp.ones((b, k.shape[1]), dtype=bool)

    k = policy.act(k, "kvcache_bskd")
    v = policy.act(v, "kvcache_bskd")
    out = decode_attention(q[:, 0], k, v, mask)
    out = out.reshape(b, 1, h * dh).astype(x.dtype)
    y = dense(p["wo"], out, policy, "act_bsd")
    return y, cache


def decode_attention(q, k, v, mask):
    """One query token per sequence against its keys, with the numerics of
    every attention in the models: bf16 operands into both matmuls, fp32
    accumulation and fp32 softmax. The serving engine's decode steps call
    this too, so a token decoded by the engine matches the model's own
    decode.

    q: (B, H, Dh); k, v: (B, S, Hkv, Dh); mask: (B, S) bool. Returns
    (B, H, Dh) fp32."""
    b, h, dh = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, hkv, h // hkv, dh)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.bfloat16),
                        k.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(dh)
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs.astype(jnp.bfloat16),
                     v.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, dh)


def paged_decode_attention(q, k_pages, v_pages, block_tables, context_lens,
                           *, page_size: int, window: Optional[int] = None):
    """:func:`decode_attention` over a paged KV cache: gathers each
    sequence's pages through its block table. Same signature as the Pallas
    ``paged_attention`` kernel. q: (B, H, Dh); pages: (P, ps, Hkv, Dh);
    block_tables: (B, n); context_lens: (B,)."""
    b = q.shape[0]
    _, _, hkv, dh = k_pages.shape
    k = k_pages[block_tables].reshape(b, -1, hkv, dh)
    v = v_pages[block_tables].reshape(b, -1, hkv, dh)
    pos = jnp.arange(block_tables.shape[1] * page_size)
    mask = pos[None, :] < context_lens[:, None]
    if window is not None:
        mask &= pos[None, :] > context_lens[:, None] - 1 - window
    return decode_attention(q, k, v, mask).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _mla_q(cfg, p, x):
    b, s, _ = x.shape
    h = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if "wq_a" in p:
        ql = rms_norm(p["q_norm"], dense(p["wq_a"], x), cfg.norm_eps)
        q = dense(p["wq_b"], ql)
    else:
        q = dense(p["wq"], x)
    q = q.reshape(b, s, h, dn + dr)
    return q[..., :dn], q[..., dn:]


def _mla_scale(cfg):
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def mla_forward(cfg, p, x, positions, *, policy: ShardingPolicy = NO_POLICY,
                return_latent: bool = False):
    """Full-sequence MLA: decompress K/V and run standard MHA.

    With ``return_latent`` also returns ``(ckv_normed, krope_roped)`` — the
    compressed cache seed for absorbed decode."""
    b, s, _ = x.shape
    h = cfg.num_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    qn, qr = _mla_q(cfg, p, x)
    qr = apply_rope(qr, positions, cfg.rope_theta)

    kv = dense(p["wkv_a"], x)
    ckv, krope = kv[..., :r], kv[..., r:]
    ckv = rms_norm(p["kv_norm"], ckv, cfg.norm_eps)
    krope = apply_rope(krope, positions, cfg.rope_theta, heads=False)  # (b,s,dr) shared
    kvb = dense(p["wkv_b"], ckv).reshape(b, s, h, dn + dv)
    kn, v = kvb[..., :dn], kvb[..., dn:]

    q = jnp.concatenate([qn, qr], axis=-1)
    k = jnp.concatenate([kn, jnp.broadcast_to(krope[:, :, None, :], (b, s, h, dr))],
                        axis=-1)
    q = policy.act(q, "heads_bshd")
    # blockwise_attention scales by 1/sqrt(dn+dr) via head_dim of concat — correct.
    out = blockwise_attention(q, k, v[..., :dv], causal=True, policy=policy)
    y = dense(p["wo"], out.reshape(b, s, h * dv), policy, "act_bsd")
    if return_latent:
        return y, (ckv, krope)
    return y


def mla_decode(cfg, p, x, cache: MLACache, pos, *,
               policy: ShardingPolicy = NO_POLICY):
    """Matrix-absorbed MLA decode: score against the latent cache directly."""
    b = x.shape[0]
    h = cfg.num_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    qn, qr = _mla_q(cfg, p, x)  # (b,1,h,dn), (b,1,h,dr)
    qr = apply_rope(qr, pos[:, None], cfg.rope_theta)

    kv = dense(p["wkv_a"], x)  # (b,1,r+dr)
    ckv_new = rms_norm(p["kv_norm"], kv[..., :r], cfg.norm_eps)
    krope_new = apply_rope(kv[..., r:], pos[:, None], cfg.rope_theta, heads=False)
    slot = cache_update(cache.pos, pos)
    cache = MLACache(
        ckv=_write_slot(cache.ckv, slot, ckv_new[:, 0]),
        krope=_write_slot(cache.krope, slot, krope_new[:, 0]),
        pos=_write_slot(cache.pos, slot, pos),
    )
    mask = _decode_mask(cache.pos, pos, None)  # (b, Sc)

    wkv_b = p["wkv_b"]["w"].reshape(r, h, dn + dv)
    w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]  # (r,h,dn), (r,h,dv)
    # absorb W_UK into q: (b,1,h,dn) x (r,h,dn) -> (b,h,r)
    q_lat = jnp.einsum("bhd,rhd->bhr", qn[:, 0].astype(jnp.bfloat16),
                       w_uk.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    ckv = policy.act(cache.ckv, "mlacache_bsr")
    scores = jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.bfloat16),
                        ckv.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    scores += jnp.einsum("bhd,bsd->bhs", qr[:, 0].astype(jnp.bfloat16),
                         cache.krope.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
    scores = scores * _mla_scale(cfg)
    scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", probs.astype(jnp.bfloat16),
                     ckv.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    out = jnp.einsum("bhr,rhd->bhd", ctx.astype(jnp.bfloat16),
                     w_uv.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    y = dense(p["wo"], out.reshape(b, 1, h * dv).astype(x.dtype), policy, "act_bsd")
    return y, cache


def mla_absorb(cfg, p):
    """Split ``wkv_b`` into the absorbed matrices: ``(w_uk, w_uv)`` with
    shapes ``(r, h, dn)`` / ``(r, h, dv)``. W_UK folds into the query path
    (queries lifted to the latent dim), W_UV into the output projection —
    decode then attends *directly over latent pages*, never materializing
    per-head K/V."""
    r, h = cfg.kv_lora_rank, cfg.num_heads
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    wkv_b = p["wkv_b"]["w"].reshape(r, h, dn + dv)
    return wkv_b[..., :dn], wkv_b[..., dn:]


def mla_effective_ctx(ckv, krope):
    """Latent context as single-kv-head effective K/V: keys are
    ``concat(ckv, krope)`` with ``Hkv = 1`` (the latent is shared across
    heads — MQA in the latent space), values are ``ckv`` zero-padded to the
    key width (attention is linear in v, so the pad columns stay zero —
    slice the context back to ``[..., :r]`` after attending).

    ckv: (B,T,r); krope: (B,T,dr) -> k_eff, v_eff: (B,T,1,r+dr)."""
    k_eff = jnp.concatenate([ckv, krope], axis=-1)[:, :, None, :]
    v_eff = pad_last(ckv, k_eff.shape[-1])[:, :, None, :]
    return k_eff, v_eff


def mla_effective_kv(q_lat, qr, ckv, krope):
    """Express absorbed-MLA attention as single-kv-head MHA so the generic
    machinery (``blockwise_attention``, ``attention_partial`` merges) runs
    it unchanged: queries are ``concat(q_lat, qr)`` — scores decompose as
    ``q_lat . ckv + qr . krope`` — and K/V come from
    :func:`mla_effective_ctx`.

    q_lat: (B,S,H,r); qr: (B,S,H,dr); ckv: (B,T,r); krope: (B,T,dr).
    Callers must pass ``scale=_mla_scale(cfg)`` — the conceptual head dim is
    ``dn + dr``, not the lifted ``r + dr``.
    """
    q_eff = jnp.concatenate([q_lat, qr], axis=-1)
    k_eff, v_eff = mla_effective_ctx(ckv, krope)
    return q_eff, k_eff, v_eff


def mla_layer(cfg, p, x, positions, attend_latent, *,
              policy: ShardingPolicy = NO_POLICY, mlp_fn=None):
    """One full MLA transformer layer parameterized by the latent attention
    callable — the MLA sibling of :func:`gqa_layer`, shared by the engine's
    paged prefill/decode paths.

    ``attend_latent(q_lat, qr, ckv_new, krope_new) -> (ctx_lat, carry)``
    receives absorbed queries ``q_lat`` (B,S,H,r), roped rope-queries ``qr``
    (B,S,H,dr), and this chunk's latent page payloads ``ckv_new`` (B,S,r) /
    ``krope_new`` (B,S,dr) (normed / pre-roped — exactly what the pools
    store); it returns the latent-space context (B,S,H,r) plus a carry
    (e.g. updated latent page buffers). The output projection absorbs W_UV.
    """
    b, s, _ = x.shape
    h, r, dv = cfg.num_heads, cfg.kv_lora_rank, cfg.v_head_dim
    hn = rms_norm(p["ln1"], x, cfg.norm_eps)
    qn, qr = _mla_q(cfg, p["attn"], hn)
    qr = apply_rope(qr, positions, cfg.rope_theta)
    kv = dense(p["attn"]["wkv_a"], hn)
    ckv_new = rms_norm(p["attn"]["kv_norm"], kv[..., :r], cfg.norm_eps)
    krope_new = apply_rope(kv[..., r:], positions, cfg.rope_theta,
                           heads=False)
    w_uk, w_uv = mla_absorb(cfg, p["attn"])
    q_lat = jnp.einsum("bshd,rhd->bshr", qn.astype(jnp.bfloat16),
                       w_uk.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    ctx_lat, carry = attend_latent(q_lat, qr, ckv_new, krope_new)
    out = jnp.einsum("bshr,rhd->bshd", ctx_lat.astype(jnp.bfloat16),
                     w_uv.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    y = x + dense(p["attn"]["wo"], out.reshape(b, s, h * dv).astype(x.dtype),
                  policy, "act_bsd")
    h2 = rms_norm(p["ln2"], y, cfg.norm_eps)
    y = y + (mlp(p["mlp"], h2, policy) if mlp_fn is None
             else mlp_fn(p["mlp"], h2))
    return y, carry


def mla_prefill_cache(cfg, p, x, positions, capacity: int):
    """Build the latent cache from a full prefill pass (used by the engine)."""
    b, s, _ = x.shape
    r = cfg.kv_lora_rank
    kv = dense(p["wkv_a"], x)
    ckv = rms_norm(p["kv_norm"], kv[..., :r], cfg.norm_eps)
    krope = apply_rope(kv[..., r:], positions, cfg.rope_theta, heads=False)
    pad = capacity - s
    return MLACache(
        ckv=jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
        krope=jnp.pad(krope, ((0, 0), (0, pad), (0, 0))),
        pos=jnp.pad(jnp.broadcast_to(positions, (b, s)), ((0, 0), (0, pad)),
                    constant_values=-1),
    )
