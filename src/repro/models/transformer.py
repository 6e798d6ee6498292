"""Composable decoder(-encoder) transformer covering all assigned families.

A model is a sequence of **segments**: runs of structurally-identical layers
whose stacked parameters run under one ``lax.scan`` (keeps HLO compact for the
88-layer dry-runs) while *different* segments may differ in layer type, attn
kind, or cache capacity. Examples:

* deepseek-v2: ``[1 x (mla+dense-mlp), 59 x (mla+moe)]``
* hymba:       ``[1 x global-hybrid, 15 x swa-hybrid] x 2``
* mamba2:      ``[48 x ssm]``
* danube:      ``[24 x swa-dense]``

Sliding-window segments allocate ring-buffer caches of capacity
``min(window, seq)`` — this is what bounds ``long_500k`` memory.

Decode caches are lists (one entry per segment) of:

* gqa:    ``KVCache``            (ring buffer; +``{"ck","cv"}`` cross-KV for enc-dec)
* mla:    ``MLACache``           (compressed latent — the paged-MLA cache)
* ssm:    ``SSMCache``           (conv window + SSD state)
* hybrid: ``(KVCache, SSMCache)``
"""

from __future__ import annotations

import dataclasses
from typing import Any, List

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (NO_POLICY, ShardingPolicy, cross_entropy,
                                 embed, embed_init, head_init, mlp, mlp_init,
                                 norm_init, output_head, rms_norm, unembed)


@dataclasses.dataclass(frozen=True)
class Segment:
    n: int
    mixer: str  # "gqa" | "mla" | "ssm" | "hybrid"
    mlp_kind: str  # "dense" | "moe" | "none"
    attn_kind: str  # "global" | "swa" | "none"
    cross: bool = False  # decoder cross-attention (enc-dec)


def stack_plan(cfg: ArchConfig) -> List[Segment]:
    if cfg.family == "ssm":
        return [Segment(cfg.num_layers, "ssm",
                        "dense" if cfg.d_ff else "none", "none")]
    if cfg.is_hybrid:
        segs, i = [], 0
        every = cfg.global_attn_every or cfg.num_layers
        while i < cfg.num_layers:
            segs.append(Segment(1, "hybrid", "dense", "global"))
            run = min(every - 1, cfg.num_layers - i - 1)
            if run > 0:
                segs.append(Segment(run, "hybrid", "dense", "swa"))
            i += 1 + run
        return segs
    mixer = "mla" if cfg.attention == "mla" else "gqa"
    attn_kind = "swa" if cfg.sliding_window else "global"
    if cfg.is_moe:
        segs = []
        if cfg.first_k_dense:
            segs.append(Segment(cfg.first_k_dense, mixer, "dense", attn_kind,
                                cross=cfg.is_encdec))
        segs.append(Segment(cfg.num_layers - cfg.first_k_dense, mixer, "moe",
                            attn_kind, cross=cfg.is_encdec))
        return segs
    return [Segment(cfg.num_layers, mixer, "dense", attn_kind,
                    cross=cfg.is_encdec)]


def encoder_plan(cfg: ArchConfig) -> List[Segment]:
    return [Segment(cfg.encoder_layers, "gqa", "dense", "global")]


# ---------------------------------------------------------------------------
# per-layer init / forward
# ---------------------------------------------------------------------------

def _layer_init(cfg: ArchConfig, seg: Segment, key, dtype):
    ks = jax.random.split(key, 8)
    p: dict = {"ln1": norm_init(cfg.d_model, dtype, bias=cfg.use_bias)}
    if seg.mixer in ("gqa", "hybrid"):
        p["attn"] = attn.gqa_init(cfg, ks[0], dtype)
    if seg.mixer == "mla":
        p["attn"] = attn.mla_init(cfg, ks[0], dtype)
    if seg.mixer in ("ssm", "hybrid"):
        p["ssm"] = ssm_mod.ssm_init(cfg, ks[1], dtype)
    if seg.cross:
        p["ln_cross"] = norm_init(cfg.d_model, dtype, bias=cfg.use_bias)
        p["cross"] = attn.gqa_init(cfg, ks[2], dtype)
    if seg.mlp_kind == "moe":
        p["ln2"] = norm_init(cfg.d_model, dtype, bias=cfg.use_bias)
        p["mlp"] = moe_mod.moe_init(cfg, ks[3], dtype)
    elif seg.mlp_kind == "dense":
        p["ln2"] = norm_init(cfg.d_model, dtype, bias=cfg.use_bias)
        p["mlp"] = mlp_init(ks[3], cfg.d_model, cfg.d_ff, dtype,
                            gated=cfg.gated_mlp, bias=cfg.use_bias)
    return p


def _layer_forward(cfg, seg: Segment, p, x, positions, *, policy,
                   enc_out=None, causal=True, collect_cache=False):
    """Full-sequence layer. Returns (x, aux, cache_seed).

    ``cache_seed`` (when ``collect_cache``) carries what decode needs:
    gqa -> (k, v); mla -> (ckv, krope); ssm -> SSMCache;
    hybrid -> ((k, v), SSMCache); +(ck, cv) appended for cross layers.
    """
    window = cfg.sliding_window if seg.attn_kind == "swa" else None
    if seg.mixer == "gqa" and seg.mlp_kind == "dense" and not seg.cross:
        # plain GQA layer: share the one layer body with the serving
        # engine's decode / suffix-prefill paths (attend = full-sequence
        # blockwise attention; carry = the prefill cache seed)
        def attend(q, k, v):
            ctx = attn.blockwise_attention(q, k, v, causal=causal,
                                           window=window, policy=policy)
            return ctx, ((k, v) if collect_cache else None)

        x, seed, _ = attn.gqa_layer(cfg, p, x, positions, attend,
                                    policy=policy)
        return x, jnp.zeros((), jnp.float32), seed
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    seed = None
    if seg.mixer == "gqa":
        out = attn.gqa_forward(cfg, p["attn"], h, positions, window=window,
                               causal=causal, policy=policy,
                               return_kv=collect_cache)
        if collect_cache:
            out, seed = out
        x = x + out
    elif seg.mixer == "mla":
        out = attn.mla_forward(cfg, p["attn"], h, positions, policy=policy,
                               return_latent=collect_cache)
        if collect_cache:
            out, seed = out
        x = x + out
    elif seg.mixer == "ssm":
        out = ssm_mod.ssm_forward(cfg, p["ssm"], h, policy=policy,
                                  return_cache=collect_cache)
        if collect_cache:
            out, seed = out
        x = x + out
    elif seg.mixer == "hybrid":
        a = attn.gqa_forward(cfg, p["attn"], h, positions, window=window,
                             causal=causal, policy=policy,
                             return_kv=collect_cache)
        m = ssm_mod.ssm_forward(cfg, p["ssm"], h, policy=policy,
                                return_cache=collect_cache)
        if collect_cache:
            a, kv = a
            m, sc = m
            seed = (kv, sc)
        x = x + 0.5 * (a + m)  # hymba: parallel heads, averaged fusion
    if seg.cross and enc_out is not None:
        hc = rms_norm(p["ln_cross"], x, cfg.norm_eps)
        ckv = attn.encode_kv(cfg, p["cross"], enc_out)
        x = x + attn.gqa_forward(cfg, p["cross"], hc, positions, causal=False,
                                 policy=policy, kv_override=ckv)
        if collect_cache:
            seed = (seed, ckv)
    if seg.mlp_kind == "moe":
        h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
        out, aux = moe_mod.moe_forward(cfg, p["mlp"], h2, policy=policy,
                                       return_aux=True)
        x = x + out
    elif seg.mlp_kind == "dense":
        h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
        x = x + mlp(p["mlp"], h2, policy)
    return x, aux, seed


def _layer_decode(cfg, seg: Segment, p, x, pos, cache, *, policy):
    """One-token layer step against the cache. Returns (x, new_cache)."""
    window = cfg.sliding_window if seg.attn_kind == "swa" else None
    cross_kv = None
    if seg.cross:
        cross_kv = (cache["ck"], cache["cv"])
        cache_self = cache["self"]
    else:
        cache_self = cache
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    if seg.mixer == "gqa":
        out, cache_self = attn.gqa_decode(cfg, p["attn"], h, cache_self, pos,
                                          window=window, policy=policy)
        x = x + out
    elif seg.mixer == "mla":
        out, cache_self = attn.mla_decode(cfg, p["attn"], h, cache_self, pos,
                                          policy=policy)
        x = x + out
    elif seg.mixer == "ssm":
        out, cache_self = ssm_mod.ssm_decode(cfg, p["ssm"], h, cache_self,
                                             policy=policy)
        x = x + out
    elif seg.mixer == "hybrid":
        kv_c, ssm_c = cache_self
        a, kv_c = attn.gqa_decode(cfg, p["attn"], h, kv_c, pos, window=window,
                                  policy=policy)
        m, ssm_c = ssm_mod.ssm_decode(cfg, p["ssm"], h, ssm_c, policy=policy)
        x = x + 0.5 * (a + m)
        cache_self = (kv_c, ssm_c)
    if seg.cross:
        hc = rms_norm(p["ln_cross"], x, cfg.norm_eps)
        out, _ = attn.gqa_decode(cfg, p["cross"], hc, None, pos,
                                 policy=policy, kv_override=cross_kv)
        x = x + out
        cache = dict(cache, self=cache_self)
    else:
        cache = cache_self
    if seg.mlp_kind == "moe":
        h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
        x = x + moe_mod.moe_forward(cfg, p["mlp"], h2, policy=policy)
    elif seg.mlp_kind == "dense":
        h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
        x = x + mlp(p["mlp"], h2, policy)
    return x, cache


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def segment_cache_capacity(cfg, seg: Segment, seq_len: int) -> int:
    if seg.attn_kind == "swa" and cfg.sliding_window:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def _empty_segment_cache(cfg, seg: Segment, batch: int, seq_len: int, dtype,
                         as_specs: bool, enc_len: int = 0):
    cap = segment_cache_capacity(cfg, seg, seq_len)

    def mk(shape, dt, stack=True):
        shape = ((seg.n,) + shape) if (seg.n > 1 and stack) else shape
        if as_specs:
            return jax.ShapeDtypeStruct(shape, dt)
        fill = -1 if dt == jnp.int32 else 0
        return jnp.full(shape, fill, dt)

    def kv():
        return attn.KVCache(
            k=mk((batch, cap, cfg.num_kv_heads, cfg.head_dim), dtype),
            v=mk((batch, cap, cfg.num_kv_heads, cfg.head_dim), dtype),
            pos=mk((batch, cap), jnp.int32))

    def mla():
        return attn.MLACache(
            ckv=mk((batch, cap, cfg.kv_lora_rank), dtype),
            krope=mk((batch, cap, cfg.qk_rope_head_dim), dtype),
            pos=mk((batch, cap), jnp.int32))

    def ssmc():
        return ssm_mod.SSMCache(
            conv=mk((batch, cfg.ssm_conv_width - 1,
                     cfg.ssm_d_inner + 2 * cfg.ssm_groups * cfg.ssm_state),
                    dtype),
            state=mk((batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                     jnp.float32))

    base = {"gqa": kv, "mla": mla, "ssm": ssmc,
            "hybrid": lambda: (kv(), ssmc())}[seg.mixer]()
    if seg.cross:
        ck = mk((batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        cv = mk((batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        return {"self": base, "ck": ck, "cv": cv}
    return base


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ArchConfig, *, remat: bool = True,
                 unroll_layers: bool = False):
        """``unroll_layers``: fully unroll the layer scans. The dry-run uses
        this so ``cost_analysis`` counts every layer (XLA costs a while-loop
        body once regardless of trip count)."""
        self.cfg = cfg
        self.plan = stack_plan(cfg)
        self.enc_plan = encoder_plan(cfg) if cfg.is_encdec else []
        self.remat = remat
        self.unroll_layers = unroll_layers

    def _unroll(self, seg_n: int) -> int:
        return seg_n if self.unroll_layers else 1

    # -- init ---------------------------------------------------------------
    def init(self, key) -> Any:
        cfg = self.cfg
        dtype = cfg.param_dtype
        k_embed, k_dec, k_enc, k_head = jax.random.split(key, 4)
        params = {
            "embed": embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype),
            "final_norm": norm_init(cfg.d_model, dtype, bias=cfg.use_bias),
            "segments": self._init_segments(self.plan, k_dec, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = head_init(k_head, cfg.vocab_size, cfg.d_model,
                                          dtype)
        if cfg.is_encdec:
            params["encoder"] = {
                "segments": self._init_segments(self.enc_plan, k_enc, dtype),
                "final_norm": norm_init(cfg.d_model, dtype, bias=cfg.use_bias),
            }
        return params

    def _init_segments(self, plan, key, dtype):
        segs = []
        keys = jax.random.split(key, max(len(plan), 1))
        for seg, k in zip(plan, keys):
            if seg.n == 1:
                segs.append(_layer_init(self.cfg, seg, k, dtype))
            else:
                segs.append(jax.vmap(
                    lambda kk, seg=seg: _layer_init(self.cfg, seg, kk, dtype))(
                        jax.random.split(k, seg.n)))
        return segs

    # -- stacks ---------------------------------------------------------------
    def _run_stack(self, plan, seg_params, x, positions, *, policy,
                   enc_out=None, causal=True, collect_cache=False):
        aux_total = jnp.zeros((), jnp.float32)
        seeds = []
        for seg, p in zip(plan, seg_params):
            if seg.n == 1:
                x, aux, seed = _layer_forward(
                    self.cfg, seg, p, x, positions, policy=policy,
                    enc_out=enc_out, causal=causal,
                    collect_cache=collect_cache)
                aux_total += aux
                seeds.append(seed)
                continue

            def body(carry, p_i, seg=seg):
                xx, aux_acc = carry
                xx, aux, seed = _layer_forward(
                    self.cfg, seg, p_i, xx, positions, policy=policy,
                    enc_out=enc_out, causal=causal,
                    collect_cache=collect_cache)
                return (xx, aux_acc + aux), seed

            fn = jax.checkpoint(body) if self.remat else body
            (x, aux_total), seed = lax.scan(fn, (x, aux_total), p,
                                            unroll=self._unroll(seg.n))
            seeds.append(seed)
        return x, aux_total, seeds

    def _embed_with_media(self, params, tokens, media, policy):
        x = embed(params["embed"], tokens, policy)
        if media is not None:
            m = media.shape[1]
            x = jnp.concatenate([media.astype(x.dtype), x[:, m:]], axis=1)
        return x

    def encode(self, params, encoder_tokens, media, policy):
        x = self._embed_with_media(params, encoder_tokens, media, policy)
        positions = jnp.arange(x.shape[1])
        x, _, _ = self._run_stack(self.enc_plan, params["encoder"]["segments"],
                                  x, positions, policy=policy, causal=False)
        return rms_norm(params["encoder"]["final_norm"], x, self.cfg.norm_eps)

    # -- public API ----------------------------------------------------------
    def forward(self, params, tokens, *, media=None, encoder_tokens=None,
                policy: ShardingPolicy = NO_POLICY):
        """Teacher-forced logits (B, S, V) + MoE aux loss."""
        cfg = self.cfg
        enc_out = None
        if cfg.is_encdec:
            enc_out = self.encode(params, encoder_tokens,
                                  media if cfg.frontend == "audio" else None,
                                  policy)
            media = None if cfg.frontend == "audio" else media
        x = self._embed_with_media(params, tokens, media, policy)
        positions = jnp.arange(x.shape[1])
        x, aux, _ = self._run_stack(self.plan, params["segments"], x,
                                    positions, policy=policy, enc_out=enc_out)
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(output_head(params), x, cfg.vocab_size, policy,
                         fp32=cfg.logits_fp32)
        return logits, aux

    def loss(self, params, batch, *, policy: ShardingPolicy = NO_POLICY):
        logits, aux = self.forward(
            params, batch["tokens"], media=batch.get("media"),
            encoder_tokens=batch.get("encoder_tokens"), policy=policy)
        ce = cross_entropy(logits[:, :-1], batch["labels"][:, 1:],
                           self.cfg.vocab_size)
        return ce + 0.01 * aux

    # -- caches ---------------------------------------------------------------
    def init_cache(self, batch: int, seq_len: int, *, as_specs: bool = False,
                   enc_len: int = 0):
        dtype = self.cfg.param_dtype
        return [_empty_segment_cache(self.cfg, seg, batch, seq_len, dtype,
                                     as_specs, enc_len)
                for seg in self.plan]

    def prefill(self, params, tokens, *, seq_capacity: int, media=None,
                encoder_tokens=None, last_idx=None, return_raw_kv=False,
                policy: ShardingPolicy = NO_POLICY):
        """Full prompt pass. Returns (last-pos logits (B,V), decode caches).

        ``return_raw_kv``: return the raw full-length per-segment cache seeds
        instead of ring-buffer caches (the paged engine scatters these into
        physical pages itself)."""
        cfg = self.cfg
        b, s = tokens.shape
        enc_out = None
        if cfg.is_encdec:
            enc_out = self.encode(params, encoder_tokens,
                                  media if cfg.frontend == "audio" else None,
                                  policy)
            media = None if cfg.frontend == "audio" else media
        x = self._embed_with_media(params, tokens, media, policy)
        positions = jnp.arange(s)
        x, _, seeds = self._run_stack(self.plan, params["segments"], x,
                                      positions, policy=policy,
                                      enc_out=enc_out, collect_cache=True)
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        if last_idx is None:
            last_idx = jnp.full((b,), s - 1, jnp.int32)
        last_h = x[jnp.arange(b), last_idx]
        logits = unembed(output_head(params), last_h[:, None, :],
                         cfg.vocab_size, policy, fp32=cfg.logits_fp32)
        if return_raw_kv:
            return logits[:, 0], seeds
        caches = self._seed_caches(seeds, b, s, seq_capacity)
        return logits[:, 0], caches

    def _seed_caches(self, seeds, b, s, capacity):
        """Convert prefill seeds into ring-buffer decode caches."""
        cfg = self.cfg
        positions = jnp.arange(s)
        caches = []
        for seg, seed in zip(self.plan, seeds):
            cross_kv = None
            if seg.cross:
                seed, cross_kv = seed
                seg = dataclasses.replace(seg, cross=False)  # base cache only
            cap = segment_cache_capacity(cfg, seg, capacity)
            take = min(cap, s)
            posvec = positions[s - take:]
            slots = posvec % cap

            if seg.mixer == "gqa":
                k, v = seed
                c = _empty_segment_cache(cfg, seg, b, capacity,
                                         cfg.param_dtype, False)
                c = attn.KVCache(
                    k=_ring_set(c.k, k, slots, take, s),
                    v=_ring_set(c.v, v, slots, take, s),
                    pos=_ring_set_pos(c.pos, posvec, slots, b))
            elif seg.mixer == "mla":
                ckv, krope = seed
                c = _empty_segment_cache(cfg, seg, b, capacity,
                                         cfg.param_dtype, False)
                c = attn.MLACache(
                    ckv=_ring_set(c.ckv, ckv, slots, take, s, ndims=1),
                    krope=_ring_set(c.krope, krope, slots, take, s, ndims=1),
                    pos=_ring_set_pos(c.pos, posvec, slots, b))
            elif seg.mixer == "ssm":
                c = seed  # SSMCache straight from the forward pass
            elif seg.mixer == "hybrid":
                (k, v), ssc = seed
                e = _empty_segment_cache(cfg, seg, b, capacity,
                                         cfg.param_dtype, False)
                kvc = attn.KVCache(
                    k=_ring_set(e[0].k, k, slots, take, s),
                    v=_ring_set(e[0].v, v, slots, take, s),
                    pos=_ring_set_pos(e[0].pos, posvec, slots, b))
                c = (kvc, ssc)
            if cross_kv is not None:
                ck, cv = cross_kv
                c = {"self": c, "ck": ck, "cv": cv}
            caches.append(c)
        return caches

    def decode_step(self, params, tokens, pos, caches, *,
                    policy: ShardingPolicy = NO_POLICY):
        """tokens: (B,1); pos: (B,). Returns (logits (B,V), new caches)."""
        cfg = self.cfg
        x = embed(params["embed"], tokens, policy)
        new_caches = []
        for seg, p, cache in zip(self.plan, params["segments"], caches):
            if seg.n == 1:
                x, c = _layer_decode(cfg, seg, p, x, pos, cache,
                                     policy=policy)
                new_caches.append(c)
                continue

            def body(xx, pc, seg=seg):
                p_i, c_i = pc
                xx, c = _layer_decode(cfg, seg, p_i, xx, pos, c_i,
                                      policy=policy)
                return xx, c

            x, c = lax.scan(body, x, (p, cache),
                            unroll=self._unroll(seg.n))
            new_caches.append(c)
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(output_head(params), x, cfg.vocab_size, policy,
                         fp32=cfg.logits_fp32)
        return logits[:, 0], new_caches


def _ring_set(buf, new, slots, take, s, ndims: int = 2):
    """buf: ([n,]B,cap,T...); new: ([n,]B,s,T...) — write last ``take`` tokens
    of ``new`` into ring slots. ``ndims`` = trailing dims after the seq axis."""
    new = new.astype(buf.dtype)
    sl = (Ellipsis, slice(s - take, s)) + (slice(None),) * ndims
    dst = (Ellipsis, slots) + (slice(None),) * ndims
    return buf.at[dst].set(new[sl])


def _ring_set_pos(buf, posvec, slots, b):
    """buf: ([n,]B,cap) int32; write absolute positions into ring slots."""
    val = jnp.broadcast_to(posvec, (b, posvec.shape[0]))
    if buf.ndim == 3:
        val = jnp.broadcast_to(val, (buf.shape[0],) + val.shape)
    return buf.at[..., slots].set(val)
