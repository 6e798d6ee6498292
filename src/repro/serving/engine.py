"""Paged serving engine: ORCA iteration-level scheduling + vLLM paging + the
paged-attention kernel, on a real JAX model.

Execution model per iteration (continuous batching):

1. the :class:`IterationScheduler` plans prefill *chunks* + decodes under
   the token budget and page supply (Sarathi-style chunked prefill: a prompt
   larger than the budget is admitted once and then contributes budget-sized
   chunks across successive iterations, piggybacked with ongoing decodes —
   ``EngineConfig.chunk_policy`` picks decode-first / prefill-first / the
   legacy solo baseline);
2. each planned chunk runs through one jitted ``_prefill_chunk_fn``: the
   chunk's K/V is scattered into the **paged physical cache** at per-token
   (page, offset) slots through the request's block table, and its queries
   attend causally at absolute RoPE positions over every context page —
   radix-cached prefix pages (``enable_prefix_cache``), chunks written in
   earlier iterations, and the chunk itself. Only the final chunk samples a
   token. Chunk starts need not be page-aligned, which is what lets a
   token-level (mid-page) prefix-cache hit resume from an unaligned
   boundary;
3. all running sequences advance one token in a single batched decode step
   over fixed slots — attention reads scattered pages via the block table
   (GQA on a TPU: the Pallas ``repro.kernels.paged_attention`` kernel,
   which reads only each slot's live pages; elsewhere the models' own
   ``paged_decode_attention`` in pure XLA; ``use_kernel`` forces one), and
   sampling runs **fused with vectorized per-slot parameters**: each slot
   applies its own request's temperature / top-k / top-p / seed
   (``repro.models.sampling.sample_batch``), and stop/eos/length finish
   reasons are checked per request.

The engine implements the :class:`~repro.serving.api.ServingBackend`
protocol; drive it through :class:`~repro.serving.api.LLMService` rather
than hand-rolling ``step()`` loops. Per-request sampling lives on
``Request.sampling`` (:class:`~repro.serving.api.SamplingParams`);
``EngineConfig.temperature`` is **deprecated** and only seeds the default
params for requests submitted without any. Best-of-n requests
(``SamplingParams.n > 1``) COW-fork the parent's block table right after
its prefill — siblings share every prompt page and diverge through the
allocator's copy-on-write on the first partial-page write, with the engine
copying the physical page contents for each ``(old, new)`` pair the
scheduler reports.

Divergence from paper noted (DESIGN.md §2.2): ORCA's selective batching fuses
prefill+decode tokens into one ragged batch; XLA needs static shapes, so
prefills run as separate padded calls while decodes fuse across slots — the
iteration-level scheduling semantics (early exit, late join) are identical.

Supports every *attention-cached* arch family (GQA/MQA/SWA). For paging, the
block tables, COW forks and preemption come straight from ``core.paging``.
The per-layer math (ln → qkv+rope → attend → wo → mlp) is the shared
:func:`repro.models.attention.gqa_layer` body, parameterized here by paged
attends.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.core.distkv.dist_attention import (attention_partial,
                                              merge_partials_tree)
from repro.core.paging.allocator import BlockAllocator, BlockTable
from repro.core.paging.layout import KVPageLayout, check_schema
from repro.core.prefixcache.radix import PrefixCache
from repro.core.scheduling.iteration import IterationScheduler
from repro.core.scheduling.request import Phase, Request
from repro.core.telemetry import NO_SPAN, MetricsRegistry, Tracer
from repro.kernels import ops
from repro.kernels.paged_attention import live_pages
from repro.models import Model
from repro.models import moe as moe_mod
from repro.models import sampling
from repro.models.layers import embed, output_head, rms_norm, unembed
from repro.models.attention import (_mla_scale, blockwise_attention,
                                    decode_attention, gqa_layer,
                                    mla_effective_ctx, mla_effective_kv,
                                    mla_latent_attention, mla_layer,
                                    paged_decode_attention)
from repro.serving.api import SamplingParams


def _pow2_bucket(n: int, floor: int = 8) -> int:
    """Smallest power of two >= n (>= floor): jit shape buckets, so a mixed
    chunk-length workload compiles O(log) variants instead of one per
    (chunk_len, n_pages) pair."""
    p = floor
    while p < n:
        p *= 2
    return p


@dataclasses.dataclass
class EngineConfig:
    num_pages: int = 512
    page_size: int = 16
    max_slots: int = 8
    max_tokens_per_iter: int = 2048
    # GQA decode attention: None => the Pallas paged_attention kernel where
    # the pools live on a TPU, the XLA gather elsewhere; True / False force
    # one (True on the CPU runs the kernel in interpret mode)
    use_kernel: Optional[bool] = None
    # DEPRECATED: per-request SamplingParams (serving.api) supersede the
    # engine-global temperature; this only seeds the default params applied
    # to requests submitted without `sampling` set.
    temperature: float = 0.0
    seed: int = 0
    # per-sequence context cap; None falls back to ArchConfig.max_seq_len and
    # then to the whole page supply. Sizes the (n, max_pages) block-table
    # transfer each decode step, so keep it at the real serving limit.
    max_context_len: Optional[int] = None
    # radix-tree prefix KV cache: share prompt pages across requests and
    # prefill only the uncached suffix
    enable_prefix_cache: bool = False
    # drop a request after this many preemptions (finish_reason
    # "preempted-dropped"); None = recompute forever
    max_preemptions: Optional[int] = None
    # chunked-prefill budget policy: "decode_first" (Sarathi stall-free:
    # running decodes get budget before prefill chunks), "prefill_first"
    # (TTFT-optimal, decodes may stall), "monolithic" (no chunking: the
    # whole prompt prefills in one iteration alongside the decodes), or
    # "solo" (legacy: over-budget prompts wait for an idle engine)
    chunk_policy: str = "decode_first"
    # host swap tier: host-memory pages a preemption victim's KV can move
    # to (0 = disabled, classic sacrifice-and-recompute). With pages
    # available, swap_mode ("sacrifice" | "swap" | "auto") and
    # victim_policy ("lifo" | "fifo" | "lru" | "cost") pick who loses
    # device pages and whether their KV survives on host — see
    # core.scheduling.iteration.SWAP_MODES / VICTIM_POLICIES
    host_pages: int = 0
    swap_mode: str = "sacrifice"
    victim_policy: str = "lifo"
    # speculative double-buffered swap-outs: the scheduler issues a decode
    # victim's swap-out one iteration early when free pages trend under the
    # watermark (issue/complete halves behind the allocator's pending
    # ledger), cancelling if pressure recedes before the DMA resolves
    speculative_swap: bool = False
    # prefix-cache spill: cold radix pages move to host pages (bounded LRU
    # budget, drawn from the same host_pages pool) instead of dying — a
    # later match restores them over PCIe instead of recomputing
    cache_spill_pages: int = 0
    # structured event tracing + per-iteration metric timelines
    # (repro.core.telemetry) on this engine's wall clock. Off by default —
    # the disabled path constructs no event objects at all.
    enable_telemetry: bool = False


class PagedEngine:
    """Single-host engine instance (one "LLM service instance" in
    InfiniteLLM terms). Implements the ServingBackend protocol."""

    def __init__(self, cfg: ArchConfig, params, ecfg: EngineConfig):
        self.cfg = cfg
        self.ecfg = ecfg
        self.params = params
        self.model = Model(cfg, remat=False)
        mixers = {seg.mixer for seg in self.model.plan}
        assert len(mixers) == 1 and mixers <= {"gqa", "mla"}, \
            "PagedEngine serves uniform GQA or MLA stacks; others use " \
            "Model.decode_step"
        # the page-payload schema every pool / payload / lease goes through:
        # GQA pools are per-head (k, v); MLA pools are the shared latent
        # (ckv, krope) — ~10x fewer bytes per token
        self.kv_layout = KVPageLayout.from_arch(cfg)
        self.flavor = self.kv_layout.flavor
        if self.flavor == "mla" and ecfg.use_kernel:
            raise ValueError("the Pallas paged_attention kernel is GQA-only;"
                             " MLA decode runs the pure-XLA latent path")
        if self.flavor == "mla" and cfg.sliding_window:
            raise ValueError("MLA + sliding window is unsupported")
        self.nlayers = cfg.num_layers
        L, P, ps = cfg.num_layers, ecfg.num_pages, ecfg.page_size
        # +1 trash page: inactive decode slots park their writes there.
        # Pool attribute names stay ``k_pages``/``v_pages`` for every
        # layout — they are "pool A"/"pool B" of ``kv_layout.pools`` (MLA:
        # ckv / krope); all page-granular plumbing (COW, swap, spill,
        # export) indexes only axis 1 and never the trailing token shape.
        shape_a, shape_b = self.kv_layout.pool_shapes(P + 1, ps)
        # the pools live where the params were committed: one engine per
        # device, each replica on its own chip (one device only — the
        # unpacking refuses params spread over several)
        (self.device,) = jax.tree.leaves(params)[0].devices()
        self.k_pages = jnp.zeros(shape_a, cfg.param_dtype, device=self.device)
        self.v_pages = jnp.zeros(shape_b, cfg.param_dtype, device=self.device)
        self.use_kernel = ecfg.use_kernel if ecfg.use_kernel is not None \
            else self.flavor == "gqa" and self.device.platform == "tpu"
        self.allocator = BlockAllocator(P, ps,
                                        host_blocks=ecfg.host_pages,
                                        layout=self.kv_layout)
        self.prefix_cache = PrefixCache(
            self.allocator, spill_budget=ecfg.cache_spill_pages) \
            if ecfg.enable_prefix_cache else None
        self.scheduler = IterationScheduler(
            self.allocator, max_running=ecfg.max_slots,
            max_tokens_per_iter=ecfg.max_tokens_per_iter,
            prefix_cache=self.prefix_cache,
            max_preemptions=ecfg.max_preemptions,
            chunk_policy=ecfg.chunk_policy,
            swap_mode=ecfg.swap_mode, victim_policy=ecfg.victim_policy,
            speculative_swap=ecfg.speculative_swap)
        # host swap tier: pinned-host-memory stand-ins (numpy arrays, same
        # page geometry as the device pools minus the trash page). The
        # scheduler's swap hooks move payloads synchronously at schedule
        # time — swap-out MUST copy before anything later in the same
        # schedule() can reallocate-and-write the freed device pages
        if ecfg.host_pages:
            H = ecfg.host_pages
            h_shape_a, h_shape_b = self.kv_layout.pool_shapes(H, ps)
            self.h_k_pages = np.zeros(h_shape_a, self.k_pages.dtype)
            self.h_v_pages = np.zeros(h_shape_b, self.v_pages.dtype)
            self.scheduler.swap_out_hook = self._swap_out_copy
            self.scheduler.swap_in_hook = self._swap_in_copy
            # double-buffered (issue/complete) halves for speculative
            # swap-outs: the allocator's pending ledger keeps the source
            # pages allocated and immutable while "in flight"
            self.scheduler.swap_issue_hook = self._swap_out_issue
            self.scheduler.swap_complete_hook = self._swap_out_complete
            self.scheduler.swap_cancel_hook = self._swap_out_cancel
            if self.prefix_cache is not None:
                self.prefix_cache.spill_out_fn = self._spill_out_copy
                self.prefix_cache.spill_in_fn = self._spill_in_copy
        else:
            self.h_k_pages = self.h_v_pages = None
        self.swapped_out = 0
        self.swapped_in = 0
        # block-table width: the real per-sequence context limit, not the
        # whole page supply — shrinks the (n, max_pages) host->device
        # transfer every decode step
        max_ctx = ecfg.max_context_len or cfg.max_seq_len or P * ps
        self.max_context_len = min(max_ctx, P * ps)
        self.max_pages_per_seq = -(-self.max_context_len // ps)  # ceil
        self.slots: Dict[int, int] = {}  # request_id -> slot
        self.free_slots = list(range(ecfg.max_slots - 1, -1, -1))
        self.last_token = np.zeros(ecfg.max_slots, np.int32)
        self.iterations = 0
        self.preemptions = 0
        # requests submitted without sampling params fall back to the
        # (deprecated) engine-global temperature, greedy by default
        self._default_sp = SamplingParams(temperature=ecfg.temperature)
        self._sample_fn = jax.jit(sampling.sample_batch)
        # best-of-n children awaiting their parent's prefill (COW fork)
        self._pending_forks: Dict[int, List[Request]] = {}
        # zero-copy cluster serving: reader(home_instance) -> (k_pages,
        # v_pages) of the creditor engine's pools, wired by RouterBackend
        # when borrowed-rBlock serving is enabled
        self.remote_reader = None
        # per-lease gathered creditor K/V (immutable while leased)
        self._lease_kv_cache: Dict[int, tuple] = {}
        # modeled network seconds (payload copies / lease RPCs) — a
        # wall-clock engine cannot advance time, so observability only
        self.net_time = 0.0
        # telemetry: events are stamped off the caller-supplied `now` (the
        # tracer's mutable .now, updated each step) plus the monotonic time
        # elapsed since the step began; spans also land in a profiler trace
        if ecfg.enable_telemetry:
            self.trace = Tracer()
            self.metrics = MetricsRegistry()
            self.scheduler.trace = self.trace
            if self.prefix_cache is not None:
                self.prefix_cache.trace = self.trace
        else:
            self.trace = None
            self.metrics = None
        self._moe_layers = sum(seg.n for seg in self.model.plan
                               if seg.mlp_kind == "moe")
        self._window = cfg.sliding_window \
            if any(seg.attn_kind == "swa" for seg in self.model.plan) \
            else None

    # -- jitted model steps ----------------------------------------------------

    def _mlp_fn(self, seg, valid):
        """Per-segment MLP dispatch for the shared layer bodies: dense
        segments use the layer default, MoE segments the dropless expert
        layer and its routing counts over the rows ``valid`` (B, S) marks
        as tokens: a chunk's padding and idle decode slots are neither
        routed to an expert nor counted (DeepSeek-V2's plan is 1 dense +
        N-1 MoE layers)."""
        if seg.mlp_kind == "moe":
            return lambda pm, h: moe_mod.moe_layer(self.cfg, pm, h,
                                                   valid=valid)[:2]
        return None

    def _run_segments(self, params, k_pages, v_pages, rk, rv, x, body):
        """Thread ``x`` through every segment of the plan with the layer
        axis of both page pools (and of the remote payload arrays).
        ``body(seg, p_i, poolA, poolB, rA_i, rB_i, x) -> (x, poolA',
        poolB', stats)`` runs ONE layer (``stats``: the MoE layer's
        (assigned, hit) routing counts, None for a dense MLP); stacked
        segments (seg.n > 1) ``lax.scan`` it over their stacked params.
        Returns (x, k_pages, v_pages, routes) with ``routes`` the (MoE
        layers, 2) int32 counts in layer order (None without MoE layers).

        A plan of one segment scans the pools' layers as ``xs``/``ys``,
        which XLA writes into the result in place. A plan of several (a
        leading dense layer before MoE layers) carries the whole pools and
        updates one layer at a time: slicing each segment's layers out and
        concatenating the results would hold two more copies of the
        pools. The carry is not used for one segment: compiled for a v5e,
        danube's decode step through it peaks within 1.5 MB of the scan's
        but copies both whole pools into the carry first (two more
        ``bf16[24,4097,16,8,80]`` copies, 8 GB moved per step, about 10
        ms at 819 GB/s), and Mistral's stage the same."""
        plan, segs = self.model.plan, params["segments"]
        routes = []

        def note(st, stacked):
            if st is not None:
                routes.append(jnp.stack(st, 1) if stacked
                              else jnp.stack(st)[None])

        if len(plan) == 1 and plan[0].n > 1:
            seg, = plan

            def scan_body(carry, scanned):
                xx, = carry
                p_i, kp, vp, rk_i, rv_i = scanned
                xx, kp2, vp2, st = body(seg, p_i, kp, vp, rk_i, rv_i, xx)
                return (xx,), (kp2, vp2, st)

            (x,), (k_pages, v_pages, st) = jax.lax.scan(
                scan_body, (x,), (segs[0], k_pages, v_pages, rk, rv))
            note(st, True)
        else:
            off = 0
            for seg, p_seg in zip(plan, segs):
                def one(carry, scanned, seg=seg, off=off):
                    xx, kp, vp = carry
                    i, p_i = scanned
                    layer = off + i
                    xx, kp2, vp2, st = body(seg, p_i, kp[layer], vp[layer],
                                            rk[layer], rv[layer], xx)
                    kp = jax.lax.dynamic_update_index_in_dim(kp, kp2, layer,
                                                             0)
                    vp = jax.lax.dynamic_update_index_in_dim(vp, vp2, layer,
                                                             0)
                    return (xx, kp, vp), st

                if seg.n == 1:
                    (x, k_pages, v_pages), st = one(
                        (x, k_pages, v_pages), (0, p_seg))
                    note(st, False)
                else:
                    (x, k_pages, v_pages), st = jax.lax.scan(
                        one, (x, k_pages, v_pages),
                        (jnp.arange(seg.n), p_seg))
                    note(st, True)
                off += seg.n
        routes = jnp.concatenate(routes, 0) if routes else None
        return x, k_pages, v_pages, routes

    def _no_remote(self, dtype):
        """Zero-token remote payload arrays (one per pool) for calls
        without a zero-copy lease — shape (L, 0, *token_shape)."""
        a, b = self.kv_layout.pools
        L = self.nlayers
        return (jnp.zeros((L, 0) + a.token_shape, dtype),
                jnp.zeros((L, 0) + b.token_shape, dtype))

    @partial(jax.jit, static_argnums=(0,))
    def _prefill_chunk_fn(self, params, k_pages, v_pages, tokens, page_ids,
                          start, length, r_base, rk, rv):
        """One prefill chunk at absolute positions ``[start, start+length)``.

        tokens: (1, S) chunk token ids padded to a power-of-two bucket
        (positions past ``length`` are pad: their K/V scatters to the trash
        page and their outputs are discarded); page_ids: (n,) physical pages
        — also pow2-padded with the trash page — covering *local* context
        positions ``[r_base, start+length)`` in order: radix-cached prefix
        pages, pages written by earlier chunks, and the pages this chunk
        lands in. ``start`` / ``length`` / ``r_base`` are traced scalars, so
        chunk boundaries (and token-level cache hits mid-page) recompile
        only per shape *bucket* — a mixed-length workload compiles O(log)
        variants, not one per (chunk_len, n_pages) pair. Each chunk token's
        K/V is scattered to its (page, offset) slot, then the chunk queries
        attend causally over every gathered context page — positions beyond
        each query are masked, so stale contents past the chunk's end (and
        the pad pages, which sit at even higher positions) are never read.

        Zero-copy remote prefix: ``rk``/``rv`` (L, R, *token_shape) carry
        the borrowed pages' payloads (gathered from the creditor instance's
        pools — K/V for GQA, ckv/krope for MLA), serving absolute positions
        ``[0, r_base)``; the local causal partial and the remote partial are
        combined with the DistAttention log-sum-exp merge. ``R = 0`` (the
        common case) keeps the original single-softmax path bit-for-bit.

        MLA stacks scatter the *latent* per-token payload (ckv, krope) into
        the two pools and attend with the matrix-absorbed effective
        single-kv-head form (``mla_effective_kv``), so pages hold
        ``kv_lora_rank + qk_rope_head_dim`` elements per token per layer
        instead of ``2 * Hkv * Dh``.

        Returns (logits (V,) of the last real chunk position, k_pages,
        v_pages, routes); callers ignore the logits for non-final chunks;
        ``routes`` holds each MoE layer's routing counts
        (:meth:`_run_segments`). Subsumes
        whole-prompt prefill (start=0, one chunk) and cached-suffix prefill
        (start = cached tokens).
        """
        cfg = self.cfg
        ecfg = self.ecfg
        ps = ecfg.page_size
        s = tokens.shape[1]
        npg = page_ids.shape[0]
        n_remote = rk.shape[1]
        positions = start + jnp.arange(s)        # (s,) absolute
        valid_tok = jnp.arange(s) < length
        loc_pos = positions - r_base             # position within local pages
        # pad tokens park their writes on the trash page, like inactive
        # decode slots — real pages never see pad K/V
        tok_pages = jnp.where(
            valid_tok, page_ids[jnp.clip(loc_pos // ps, 0, npg - 1)],
            ecfg.num_pages)
        in_page = loc_pos % ps
        x = embed(params["embed"], tokens)  # (1, s, d)

        if self.flavor == "mla":
            r_lat, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            scale = _mla_scale(cfg)

            def body(seg, p_i, cp, rp, rc_i, rr_i, xx):
                # cp/rp: (P+1, ps, r) / (P+1, ps, dr) latent pools;
                # rc_i/rr_i: (R, r) / (R, dr) borrowed latent payloads

                def attend_latent(q_lat, qr, ckv_new, krope_new):
                    cp2 = cp.at[tok_pages, in_page].set(
                        ckv_new[0].astype(cp.dtype))
                    rp2 = rp.at[tok_pages, in_page].set(
                        krope_new[0].astype(rp.dtype))
                    ckv_all = cp2[page_ids].reshape(1, npg * ps, r_lat)
                    kr_all = rp2[page_ids].reshape(1, npg * ps, dr)
                    q_eff, k_eff, v_eff = mla_effective_kv(
                        q_lat, qr, ckv_all.astype(q_lat.dtype),
                        kr_all.astype(q_lat.dtype))
                    if n_remote == 0:
                        ctx = blockwise_attention(q_eff, k_eff, v_eff,
                                                  causal=True, q_offset=start,
                                                  scale=scale)
                    else:
                        key_pos = r_base + jnp.arange(npg * ps)
                        mask_l = positions[None, :, None] >= \
                            key_pos[None, None, :]
                        o_l, m_l, l_l = attention_partial(
                            q_eff, k_eff, v_eff, mask_l, scale=scale)
                        kr_eff, vr_eff = mla_effective_ctx(
                            rc_i[None].astype(q_lat.dtype),
                            rr_i[None].astype(q_lat.dtype))
                        mask_r = (jnp.arange(n_remote) < r_base)[None, None, :] \
                            & jnp.ones((1, s, 1), bool)
                        o_r, m_r, l_r = attention_partial(
                            q_eff, kr_eff, vr_eff, mask_r, scale=scale)
                        ctx = merge_partials_tree([o_l, o_r], [m_l, m_r],
                                                  [l_l, l_r])
                    return ctx[..., :r_lat].astype(q_lat.dtype), (cp2, rp2)

                y, (cp2, rp2), st = mla_layer(
                    cfg, p_i, xx, positions, attend_latent,
                    mlp_fn=self._mlp_fn(seg, valid_tok[None]))
                return y, cp2, rp2, st
        else:
            def body(seg, p_i, kp, vp, rk_i, rv_i, xx):
                window = cfg.sliding_window if seg.attn_kind == "swa" \
                    else None

                def attend(q, k, v):
                    kp2 = kp.at[tok_pages, in_page].set(k[0].astype(kp.dtype))
                    vp2 = vp.at[tok_pages, in_page].set(v[0].astype(vp.dtype))
                    kall = kp2[page_ids].reshape(
                        1, npg * ps, cfg.num_kv_heads, cfg.head_dim)
                    vall = vp2[page_ids].reshape(
                        1, npg * ps, cfg.num_kv_heads, cfg.head_dim)
                    if n_remote == 0:
                        ctx = blockwise_attention(q, kall.astype(k.dtype),
                                                  vall.astype(v.dtype),
                                                  causal=True, window=window,
                                                  q_offset=start)
                        return ctx, (kp2, vp2)
                    # zero-copy: local causal partial + remote partial,
                    # merged by log-sum-exp (DistAttention). Local keys sit
                    # at absolute positions r_base + [0, npg*ps); remote
                    # keys at [0, r_base) — all remote positions precede
                    # every chunk query, so only validity masks the remote
                    # side.
                    key_pos = r_base + jnp.arange(npg * ps)
                    mask_l = positions[None, :, None] >= key_pos[None, None, :]
                    o_l, m_l, l_l = attention_partial(q, kall, vall, mask_l)
                    mask_r = (jnp.arange(n_remote) < r_base)[None, None, :] \
                        & jnp.ones((1, s, 1), bool)
                    o_r, m_r, l_r = attention_partial(q, rk_i[None],
                                                      rv_i[None], mask_r)
                    ctx = merge_partials_tree([o_l, o_r], [m_l, m_r],
                                              [l_l, l_r])
                    return ctx.astype(q.dtype), (kp2, vp2)

                y, (kp2, vp2), st = gqa_layer(
                    cfg, p_i, xx, positions, attend,
                    mlp_fn=self._mlp_fn(seg, valid_tok[None]))
                return y, kp2, vp2, st

        x, k_pages, v_pages, routes = self._run_segments(
            params, k_pages, v_pages, rk, rv, x, body)
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        # logits of the last REAL chunk position (pad rows are garbage)
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        logits = unembed(output_head(params), last, cfg.vocab_size,
                         fp32=cfg.logits_fp32)
        return logits[0, 0], k_pages, v_pages, routes

    @partial(jax.jit, static_argnums=(0,))
    def _decode_fn(self, params, k_pages, v_pages, tokens, positions,
                   block_tables, ctx_lens):
        """Batched one-token step over slots.

        tokens: (n,), positions: (n,), block_tables: (n, max_pages),
        ctx_lens: (n,) (0 = inactive slot). Returns (logits (n, V), pages,
        routes) as :meth:`_prefill_chunk_fn` does.

        GQA runs the Pallas paged-attention kernel where ``use_kernel``
        resolves to it (the default on a TPU: it reads only each slot's
        live pages) and otherwise the models' paged decode attention, which
        gathers the whole block table. MLA gathers the latent pools' whole
        table and attends in the matrix-absorbed form over the latent and
        the rotary keys in place (:func:`mla_latent_attention`; the Pallas
        kernel is GQA-shaped); :meth:`_decode_zc_fn` gathers the whole
        table too, in either flavor: neither has a kernel path yet."""
        cfg = self.cfg
        ecfg = self.ecfg
        n = tokens.shape[0]
        ps = ecfg.page_size

        x = embed(params["embed"], tokens[:, None])  # (n, 1, d)
        page_slot = block_tables[jnp.arange(n), positions // ps]  # (n,)
        # inactive slots (ctx_len == 0) write to the trash page
        page_slot = jnp.where(ctx_lens > 0, page_slot, ecfg.num_pages)
        in_page = positions % ps
        active = (ctx_lens > 0)[:, None]  # (n, 1) rows the experts take

        if self.flavor == "mla":
            r_lat, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            scale = _mla_scale(cfg)

            def body(seg, p_i, cp, rp, rc_i, rr_i, xx):
                def attend_latent(q_lat, qr, ckv_new, krope_new):
                    cp2 = cp.at[page_slot, in_page].set(
                        ckv_new[:, 0].astype(cp.dtype))
                    rp2 = rp.at[page_slot, in_page].set(
                        krope_new[:, 0].astype(rp.dtype))
                    ckv_all = cp2[block_tables].reshape(n, -1, r_lat)
                    kr_all = rp2[block_tables].reshape(n, -1, dr)
                    mask = jnp.arange(ckv_all.shape[1])[None, :] < \
                        ctx_lens[:, None]  # (n, S)
                    ctx = mla_latent_attention(q_lat[:, 0], qr[:, 0],
                                               ckv_all, kr_all, mask, scale)
                    return ctx[:, None].astype(q_lat.dtype), (cp2, rp2)

                y, (cp2, rp2), st = mla_layer(
                    cfg, p_i, xx, positions[:, None], attend_latent,
                    mlp_fn=self._mlp_fn(seg, active))
                return y, cp2, rp2, st
        else:
            def body(seg, p_i, kp, vp, rk_i, rv_i, xx):
                window = cfg.sliding_window if seg.attn_kind == "swa" \
                    else None

                def attend(q, k, v):
                    # write each slot's new K/V into its page, then paged
                    # attention over the block tables
                    kp2 = kp.at[page_slot, in_page].set(
                        k[:, 0].astype(kp.dtype))
                    vp2 = vp.at[page_slot, in_page].set(
                        v[:, 0].astype(vp.dtype))
                    att_fn = ops.paged_attention if self.use_kernel \
                        else paged_decode_attention
                    att = att_fn(q[:, 0], kp2, vp2, block_tables, ctx_lens,
                                 page_size=ps, window=window)
                    return att.reshape(n, 1, cfg.num_heads, cfg.head_dim), \
                        (kp2, vp2)

                y, (kp2, vp2), st = gqa_layer(
                    cfg, p_i, xx, positions[:, None], attend,
                    mlp_fn=self._mlp_fn(seg, active))
                return y, kp2, vp2, st

        rk, rv = self._no_remote(k_pages.dtype)
        x, k_pages, v_pages, routes = self._run_segments(
            params, k_pages, v_pages, rk, rv, x, body)
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(output_head(params), x, cfg.vocab_size,
                         fp32=cfg.logits_fp32)[:, 0]
        return logits, k_pages, v_pages, routes

    @partial(jax.jit, static_argnums=(0,))
    def _decode_zc_fn(self, params, k_pages, v_pages, tokens, positions,
                      block_tables, ctx_lens, r_base, rk, rv):
        """Batched one-token step where some slots serve their leading
        context from pages *borrowed* from a peer instance (zero-copy
        prefix lease). Arguments mirror :meth:`_decode_fn` plus:

        r_base: (n,) borrowed tokens per slot (0 = fully local — such slots
        reduce to the plain paged path numerically); rk, rv:
        (L, n, R, *token_shape) the borrowed pages' payloads gathered from
        each creditor's pools (K/V for GQA, ckv/krope for MLA), covering
        absolute positions ``[0, r_base[i])`` of slot ``i``, read from the
        creditor's pages in place of an RDMA fetch. GQA attends over the
        borrowed and the local keys in one softmax, with the numerics of
        the model's own decode (:func:`decode_attention`); MLA merges the
        local and remote partials with the DistAttention log-sum-exp merge
        (InfiniteLLM's micro-attention aggregation).
        """
        cfg = self.cfg
        ecfg = self.ecfg
        n = tokens.shape[0]
        ps = ecfg.page_size
        n_remote = rk.shape[2]

        x = embed(params["embed"], tokens[:, None])  # (n, 1, d)
        loc_pos = jnp.maximum(positions - r_base, 0)  # write slot, local
        loc_lens = jnp.maximum(ctx_lens - r_base, 0)  # local context length
        page_slot = block_tables[jnp.arange(n), loc_pos // ps]  # (n,)
        page_slot = jnp.where(ctx_lens > 0, page_slot, ecfg.num_pages)
        in_page = loc_pos % ps
        active = (ctx_lens > 0)[:, None]

        if self.flavor == "mla":
            r_lat, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            scale = _mla_scale(cfg)

            def body(seg, p_i, cp, rp, rc_i, rr_i, xx):
                # rc_i: (n, R, r), rr_i: (n, R, dr)
                def attend_latent(q_lat, qr, ckv_new, krope_new):
                    cp2 = cp.at[page_slot, in_page].set(
                        ckv_new[:, 0].astype(cp.dtype))
                    rp2 = rp.at[page_slot, in_page].set(
                        krope_new[:, 0].astype(rp.dtype))
                    ckv_all = cp2[block_tables].reshape(n, -1, r_lat)
                    kr_all = rp2[block_tables].reshape(n, -1, dr)
                    q_eff, k_eff, v_eff = mla_effective_kv(
                        q_lat, qr, ckv_all.astype(q_lat.dtype),
                        kr_all.astype(q_lat.dtype))
                    s_loc = k_eff.shape[1]
                    mask_l = (jnp.arange(s_loc)[None, :] <
                              loc_lens[:, None])[:, None, :]
                    o_l, m_l, l_l = attention_partial(q_eff, k_eff, v_eff,
                                                      mask_l, scale=scale)
                    kr_eff, vr_eff = mla_effective_ctx(
                        rc_i.astype(q_lat.dtype), rr_i.astype(q_lat.dtype))
                    mask_r = (jnp.arange(n_remote)[None, :] <
                              r_base[:, None])[:, None, :]
                    o_r, m_r, l_r = attention_partial(q_eff, kr_eff, vr_eff,
                                                      mask_r, scale=scale)
                    att = merge_partials_tree([o_l, o_r], [m_l, m_r],
                                              [l_l, l_r])
                    return att[..., :r_lat].astype(q_lat.dtype), (cp2, rp2)

                y, (cp2, rp2), st = mla_layer(
                    cfg, p_i, xx, positions[:, None], attend_latent,
                    mlp_fn=self._mlp_fn(seg, active))
                return y, cp2, rp2, st
        else:
            def body(seg, p_i, kp, vp, rk_i, rv_i, xx):
                # rk_i: (n, R, Hkv, Dh)
                def attend(q, k, v):
                    kp2 = kp.at[page_slot, in_page].set(
                        k[:, 0].astype(kp.dtype))
                    vp2 = vp.at[page_slot, in_page].set(
                        v[:, 0].astype(vp.dtype))
                    kall = kp2[block_tables].reshape(
                        n, -1, cfg.num_kv_heads, cfg.head_dim)
                    vall = vp2[block_tables].reshape(
                        n, -1, cfg.num_kv_heads, cfg.head_dim)
                    mask_l = jnp.arange(kall.shape[1])[None, :] < \
                        loc_lens[:, None]  # (n, S_loc)
                    mask_r = jnp.arange(n_remote)[None, :] < r_base[:, None]
                    # the borrowed keys hold positions [0, r_base), ahead of
                    # every local key: one softmax over both, in the
                    # model's own decode numerics
                    att = decode_attention(
                        q[:, 0], jnp.concatenate([rk_i, kall], 1),
                        jnp.concatenate([rv_i, vall], 1),
                        jnp.concatenate([mask_r, mask_l], 1))
                    return att.reshape(q.shape).astype(q.dtype), (kp2, vp2)

                y, (kp2, vp2), st = gqa_layer(
                    cfg, p_i, xx, positions[:, None], attend,
                    mlp_fn=self._mlp_fn(seg, active))
                return y, kp2, vp2, st

        x, k_pages, v_pages, routes = self._run_segments(
            params, k_pages, v_pages, rk, rv, x, body)
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(output_head(params), x, cfg.vocab_size,
                         fp32=cfg.logits_fp32)[:, 0]
        return logits, k_pages, v_pages, routes

    # -- ServingBackend protocol -------------------------------------------------

    def add_request(self, req: Request) -> None:
        if req.prompt_len + req.max_new_tokens > self.max_context_len:
            raise ValueError(
                f"request {req.request_id} needs "
                f"{req.prompt_len + req.max_new_tokens} context tokens, "
                f"engine limit is {self.max_context_len}")
        if req.parent_id is not None and any(
                r.request_id == req.parent_id for r in self.scheduler.waiting):
            # best-of-n sibling: COW-forked off the parent's prefill instead
            # of prefilling again (falls back to a plain request if no slot
            # is free at fork time)
            self._pending_forks.setdefault(req.parent_id, []).append(req)
            return
        self.scheduler.add_request(req)

    @property
    def has_work(self) -> bool:
        return bool(self.scheduler.waiting or self.scheduler.running
                    or self._pending_forks)

    def clock(self) -> Optional[float]:
        return None  # wall-clock backend: the caller supplies `now`

    def _ctx_arrays(self):
        n = self.ecfg.max_slots
        bt = np.zeros((n, self.max_pages_per_seq), np.int32)
        lens = np.zeros(n, np.int32)
        pos = np.zeros(n, np.int32)
        toks = np.zeros(n, np.int32)
        return bt, lens, pos, toks

    def charge_network(self, seconds: float) -> None:
        """Record modeled network time (payload copy / lease RPC). A
        wall-clock engine cannot advance its clock, so this only feeds the
        ``net_time`` stat (the virtual-clock SimBackend advances time)."""
        self.net_time += seconds
        if self.trace is not None:
            self.trace.instant("net", "charge", seconds=seconds)

    # -- zero-copy remote prefixes (borrowed rBlocks) -----------------------------

    def _check_zero_copy_ok(self) -> None:
        if self.remote_reader is None:
            raise RuntimeError(
                "request holds a zero-copy lease but no remote_reader is "
                "wired — RouterBackend must connect creditor pools")
        if self._window is not None:
            raise RuntimeError(
                "zero-copy remote prefixes are unsupported with sliding-"
                "window attention (the remote partial ignores the window)")

    def _lease_kv(self, lease):
        """(L, R, *token_shape) payloads of a lease's borrowed pages (one
        array per pool), gathered from the creditor's pools ONCE per lease
        and cached: the pages are pinned on the board, refcounted through
        the home allocator, and never written (any writer COWs a shared
        page first), so their contents are immutable for the lease's
        lifetime — re-gathering per decode step would put a pool-sized
        gather on the hot path."""
        key = id(lease)
        hit = self._lease_kv_cache.get(key)
        if hit is None:
            check_schema(self.kv_layout.schema,
                         getattr(lease, "schema", None),
                         where="zero-copy lease read")
            hk, hv = self.remote_reader(lease.home)
            idx = jnp.asarray(lease.blocks, jnp.int32)
            L = self.nlayers
            pa, pb = self.kv_layout.pools
            # the creditor's pools may sit on another device: the gathered
            # pages move to this engine's device once per lease
            hit = jax.device_put(
                (hk[:, idx].reshape((L, -1) + pa.token_shape),
                 hv[:, idx].reshape((L, -1) + pb.token_shape)), self.device)
            self._lease_kv_cache[key] = hit
        return hit

    def _prune_lease_cache(self) -> None:
        live = {id(l) for l in self.scheduler.leases.values()}
        for key in [k for k in self._lease_kv_cache if k not in live]:
            del self._lease_kv_cache[key]

    def _lease_kv_chunk(self, lease):
        """(L, Rpad, *token_shape) borrowed payloads, pow2-padded (pad
        tokens are masked by ``r_base`` inside the jitted chunk fn)."""
        k, v = self._lease_kv(lease)
        pad = _pow2_bucket(lease.num_pages, 1) * self.ecfg.page_size \
            - lease.num_tokens
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad)) + ((0, 0),) * (k.ndim - 2))
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return k, v

    def _lease_kv_batch(self, row_reqs):
        """(L, n, Rpad, *token_shape) stacked borrowed payloads for a
        decode batch (zero rows for slots without a lease)."""
        leases = self.scheduler.leases
        L = self.nlayers
        pa, pb = self.kv_layout.pools
        rmax = max(leases[r.request_id].num_pages for r in row_reqs
                   if r is not None and r.request_id in leases)
        rpad = _pow2_bucket(rmax, 1) * self.ecfg.page_size
        rk = jnp.zeros((L, self.ecfg.max_slots, rpad) + pa.token_shape,
                       self.k_pages.dtype)
        rv = jnp.zeros((L, self.ecfg.max_slots, rpad) + pb.token_shape,
                       self.v_pages.dtype)
        for slot, req in enumerate(row_reqs):
            if req is None or req.request_id not in leases:
                continue
            lease = leases[req.request_id]
            k, v = self._lease_kv(lease)
            rk = rk.at[:, slot, :lease.num_tokens].set(k)
            rv = rv.at[:, slot, :lease.num_tokens].set(v)
        return rk, rv

    # -- per-request sampling ----------------------------------------------------

    def _sp_of(self, req: Request) -> SamplingParams:
        return req.sampling if req.sampling is not None else self._default_sp

    def _seed_of(self, req: Request) -> int:
        sp = self._sp_of(req)
        if sp.seed is not None:
            return sp.seed & 0x7FFFFFFF
        return (self.ecfg.seed * 1_000_003 + req.request_id * 7919
                + 0x5BD1) & 0x7FFFFFFF

    def _sample_rows(self, logits, reqs_by_row, routes=()):
        """Fused per-slot sampling. ``reqs_by_row``: list (len = batch rows)
        of Request or None (inactive row). Returns (tokens, logprobs) np.
        ``routes``: the iteration's routing counts, read in the same sync
        (:meth:`_emit_routes`)."""
        n = logits.shape[0]
        temp = np.zeros(n, np.float32)
        topk = np.zeros(n, np.int32)
        topp = np.ones(n, np.float32)
        seeds = np.zeros(n, np.int32)
        steps = np.zeros(n, np.int32)
        for i, req in enumerate(reqs_by_row):
            if req is None:
                continue
            sp = self._sp_of(req)
            temp[i] = sp.temperature
            topk[i] = sp.top_k
            topp[i] = sp.top_p
            seeds[i] = self._seed_of(req)
            # cumulative token index: keeps the stream aligned across
            # preemption/recompute (committed tokens advance the counter)
            steps[i] = req.total_generated
        if self.metrics is not None:
            # calls, and calls in which some row samples: only those sort
            self.metrics.count("sample_calls")
            self.metrics.count("sample_sorted_calls", int((temp > 0).any()))
        toks, lps = self._sample_fn(logits, jnp.asarray(seeds),
                                    jnp.asarray(steps), jnp.asarray(temp),
                                    jnp.asarray(topk), jnp.asarray(topp))
        tr = self.trace
        with tr.span("engine", "sync") if tr is not None else NO_SPAN:
            out = np.asarray(toks), np.asarray(lps)
            self._emit_routes(routes)
            return out

    def _emit_routes(self, routes) -> None:
        """Telemetry of the expert layers: for each ``(program, counts)`` of
        ``routes`` (the MoE program calls of one iteration, in call order;
        ``counts`` the (MoE layers, 2) device array of held assignments and
        held experts hit) one ring event ``engine``/``moe_route`` with
        ``program``, ``assigned`` and ``hit`` per layer, and the counters
        ``moe_<program>_assigned`` / ``moe_<program>_hit``. Empties
        ``routes``."""
        if not routes:
            return
        for (program, _), counts in zip(
                routes, jax.device_get([c for _, c in routes])):
            assigned, hit = counts[:, 0].tolist(), counts[:, 1].tolist()
            self.trace.instant("engine", "moe_route", program=program,
                               assigned=assigned, hit=hit)
            self.metrics.count(f"moe_{program}_assigned", sum(assigned))
            self.metrics.count(f"moe_{program}_hit", sum(hit))
        routes.clear()

    def _sample_one(self, req: Request, logits_row):
        toks, lps = self._sample_rows(logits_row[None], [req])
        return int(toks[0]), float(lps[0])

    def _emit(self, req: Request, slot: int, tok: int, lp: float,
              now: float) -> None:
        req.output.append(tok)
        req.cumulative_logprob += lp
        req.logprobs.append(lp)
        req.record_token_time(now)
        self.last_token[slot] = tok

    # -- engine loop ------------------------------------------------------------

    def step(self, now: Optional[float] = None) -> List[Request]:
        """Run ONE iteration (ORCA's unit of scheduling). ``now`` is the
        caller's clock at the step's start; a token is stamped when it
        reaches the host, ``now`` plus the monotonic time elapsed since."""
        t0 = time.monotonic()
        now = t0 if now is None else now
        tr = self.trace
        if tr is not None:
            # scheduler instants default to `now`; spans are offset by the
            # monotonic time elapsed since t0
            tr.now = now
            tr.t_step0 = t0
            tr.iteration = self.iterations
        with tr.span("engine", "iteration") if tr is not None else NO_SPAN \
                as args:
            return self._step(now, t0, args)

    def _step(self, now: float, t0: float,
              args: Optional[dict]) -> List[Request]:
        tr = self.trace
        plan = self.scheduler.schedule()
        if self._lease_kv_cache:  # drop gathers of released leases
            self._prune_lease_cache()
        # release slots of preempted requests
        self.preemptions += len(plan.preempted)
        for req in plan.preempted:
            if req.request_id in self.slots:
                self.free_slots.append(self.slots.pop(req.request_id))
        # swap transfers already ran via the scheduler hooks; here only the
        # decode slots move: a swapped-out request gives its slot up, a
        # swapped-in one claims a fresh slot and re-arms its input token
        # (the last sampled token, whose KV was never written — it resumes
        # decode exactly where the swap interrupted it)
        for req, _pairs in plan.swap_out + plan.swap_issue:
            if req.request_id in self.slots:
                self.free_slots.append(self.slots.pop(req.request_id))
        # a cancelled speculative swap re-enters decode this iteration:
        # its pages never left the device, so only the slot comes back
        for req, _pairs in plan.swap_in + plan.swap_cancel:
            slot = self.free_slots.pop()
            self.slots[req.request_id] = slot
            if req.output:
                self.last_token[slot] = req.output[-1]
        if plan.empty:
            if args is not None:
                args.update(tokens=0, decodes=0, chunks=0)
            # a self-preempted request can leave an otherwise-empty plan:
            # run completion anyway so the max_preemptions drop policy
            # applies (otherwise it bounces in waiting forever)
            return self.scheduler.complete_iteration(plan, now) \
                if plan.preempted else []
        # COW: copy replaced shared pages before anything writes this
        # iteration (the old block keeps its pre-iteration contents until
        # the decode/prefill writes below)
        if plan.cow:
            old = jnp.asarray([o for o, _ in plan.cow], jnp.int32)
            new = jnp.asarray([w for _, w in plan.cow], jnp.int32)
            self.k_pages = self.k_pages.at[:, new].set(self.k_pages[:, old])
            self.v_pages = self.v_pages.at[:, new].set(self.v_pages[:, old])

        # --- prefill chunks (initiation phase) ---
        forked: List[Request] = []
        # (program, routing counts) of each MoE program call, telemetry only
        routes: list = []
        track = self.metrics is not None and self._moe_layers > 0
        t_host = now  # when the newest sampled tokens reached the host
        ps = self.ecfg.page_size
        for ch in plan.chunks:
            req = ch.req
            if req.request_id not in self.slots:
                # first chunk: claim the decode slot the request will keep
                self.slots[req.request_id] = self.free_slots.pop()
            slot = self.slots[req.request_id]
            if req.scheduled_time is None:
                req.scheduled_time = now
            table = self.scheduler.tables[req.request_id]
            # positions [0, r_base) are served from a creditor's pages
            # (zero-copy lease); the local table covers [r_base, end)
            r_base = self.scheduler.remote_tokens_of(req.request_id)
            n_ctx_pages = -(-(ch.end - r_base) // ps)  # ceil, local pages
            npg_pad = _pow2_bucket(n_ctx_pages, 1)
            # pad with a REAL page, not the trash page: pad key positions
            # are causally masked either way (they sit past every real
            # query), but the trash page holds NaN K/V (inactive decode
            # slots write their fully-masked attention output there) and a
            # gathered NaN poisons the masked value einsum (0 * NaN = NaN)
            page_arr = np.full(npg_pad, table.blocks[0], np.int32)
            page_arr[:n_ctx_pages] = table.blocks[:n_ctx_pages]
            s_pad = _pow2_bucket(ch.length)
            tok_arr = np.zeros(s_pad, np.int32)
            tok_arr[:ch.length] = req.prompt[ch.start:ch.end]
            if r_base:
                self._check_zero_copy_ok()
                rk, rv = self._lease_kv_chunk(
                    self.scheduler.leases[req.request_id])
            else:
                rk, rv = self._no_remote(self.k_pages.dtype)
            # the span times the dispatch (the call returns before the
            # device has run the chunk)
            with tr.span("engine", "chunk", rid=req.request_id,
                         start=ch.start, length=ch.length, last=ch.is_last) \
                    if tr is not None else NO_SPAN:
                logits, self.k_pages, self.v_pages, moe = \
                    self._prefill_chunk_fn(
                        self.params, self.k_pages, self.v_pages,
                        jnp.asarray(tok_arr)[None], jnp.asarray(page_arr),
                        jnp.int32(ch.start), jnp.int32(ch.length),
                        jnp.int32(r_base), rk, rv)
            if track:
                routes.append(("prefill", moe))
            if ch.is_last:
                tok, lp = self._sample_one(req, logits)
                t_host = now + (time.monotonic() - t0)
                self._emit(req, slot, tok, lp, t_host)
                forked.extend(self._fork_children(req, logits, t_host))

        # best-of-n children join the plan so completion/insertion sees them
        plan.prefill.extend(forked)

        # --- fused decode step (increment phase) ---
        decode_reqs = [r for r in plan.decode]
        if decode_reqs:
            bt, lens, pos, toks = self._ctx_arrays()
            rbase = np.zeros(self.ecfg.max_slots, np.int32)
            row_reqs: List[Optional[Request]] = [None] * self.ecfg.max_slots
            for req in decode_reqs:
                slot = self.slots[req.request_id]
                table = self.scheduler.tables[req.request_id]
                bt[slot, :len(table.blocks)] = table.blocks
                # input token t_g sits at absolute position ctx_len-1; after
                # its KV is written the attention span is ctx_len tokens
                # (scheduler already grew the table by one for it)
                lens[slot] = req.context_len
                pos[slot] = req.context_len - 1
                toks[slot] = self.last_token[slot]
                rbase[slot] = self.scheduler.remote_tokens_of(req.request_id)
                row_reqs[slot] = req
            if rbase.any():
                # >=1 slot reads a borrowed prefix: local paged partial +
                # remote partial, merged (DistAttention). Fully-local slots
                # ride along with r_base = 0.
                self._check_zero_copy_ok()
                rk, rv = self._lease_kv_batch(row_reqs)
                logits, self.k_pages, self.v_pages, moe = \
                    self._decode_zc_fn(
                        self.params, self.k_pages, self.v_pages,
                        jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(bt),
                        jnp.asarray(lens), jnp.asarray(rbase), rk, rv)
            else:
                logits, self.k_pages, self.v_pages, moe = self._decode_fn(
                    self.params, self.k_pages, self.v_pages,
                    jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(bt),
                    jnp.asarray(lens))
                if self.metrics is not None:
                    # cumulative: the pages inside each active slot's
                    # context and window (what the kernel reads) against
                    # the block table's width (what the XLA gather reads)
                    _, live = live_pages(lens, ps, self._window)
                    self.metrics.count("decode_live_pages", int(live.sum()))
                    self.metrics.count(
                        "decode_table_pages",
                        len(decode_reqs) * self.max_pages_per_seq)
            if track:
                routes.append(("decode", moe))
            sampled, lps = self._sample_rows(logits, row_reqs, routes)
            t_host = now + (time.monotonic() - t0)
            for req in decode_reqs:
                slot = self.slots[req.request_id]
                self._emit(req, slot, int(sampled[slot]), float(lps[slot]),
                           t_host)

        if routes:  # an iteration of prefill chunks alone
            with tr.span("engine", "sync"):
                self._emit_routes(routes)

        finished = self.scheduler.complete_iteration(plan, t_host)
        for req in finished:
            if req.request_id in self.slots:
                self.free_slots.append(self.slots.pop(req.request_id))
        if args is not None:
            args.update(tokens=plan.token_count(), decodes=len(plan.decode),
                        chunks=len(plan.chunks))
            m = self.metrics
            m.gauge("kv_util_frac",
                    self.allocator.num_used / self.allocator.num_blocks)
            m.gauge("prefill_backlog_tokens",
                    self.scheduler.prefill_backlog_tokens())
            m.gauge("budget_fill_frac",
                    plan.token_count() / self.scheduler.max_tokens)
            m.gauge("running", len(self.scheduler.running))
            m.gauge("waiting", len(self.scheduler.waiting))
            m.gauge("net_time_s", self.net_time)
            if self.allocator.num_host_blocks:
                m.gauge("swapped_pages", self.allocator.swapped_pages)
                m.gauge("swap_pending_pages",
                        self.allocator.pending_out_pages)
            if self.prefix_cache is not None:
                # cumulative: a reader takes the window's differences
                m.counters["prefix_hit_tokens"] = self.prefix_cache.hit_tokens
                m.counters["prefix_lookup_tokens"] = \
                    self.prefix_cache.lookup_tokens
            m.count("tokens", plan.token_count())
            m.count("decode_tokens", len(plan.decode))
            m.count("prefill_tokens", sum(c.length for c in plan.chunks))
            m.count("preemptions", len(plan.preempted))
            m.count("swap_outs", len(plan.swap_out) + len(plan.swap_complete))
            m.count("swap_ins", len(plan.swap_in))
            m.count("swap_issues", len(plan.swap_issue))
            m.count("swap_cancels", len(plan.swap_cancel))
            m.snapshot(now, self.iterations)
        self.iterations += 1
        return finished

    def _fork_children(self, parent: Request, logits, now) -> List[Request]:
        """COW-fork best-of-n siblings off ``parent``'s fresh prefill: each
        child shares the prompt pages (no second prefill) and samples its
        own first token from the same last-position logits."""
        children = self._pending_forks.pop(parent.request_id, [])
        forked = []
        for child in children:
            if self.free_slots and \
                    len(self.scheduler.running) < self.scheduler.max_running:
                self.scheduler.fork_from(parent, child)
                slot = self.free_slots.pop()
                self.slots[child.request_id] = slot
                child.scheduled_time = now
                child.first_token_time = now
                if self.trace is not None:
                    self.trace.instant("req", "first_token",
                                       rid=child.request_id)
                tok, lp = self._sample_one(child, logits)
                self._emit(child, slot, tok, lp, now)
                forked.append(child)
            else:
                # no slot free: fall back to an ordinary request (with the
                # prefix cache on it still reuses the parent's prompt pages)
                self.scheduler.add_request(child)
        return forked

    # -- host swap tier -----------------------------------------------------------

    def _swap_out_copy(self, pairs) -> None:
        """Device -> host page payloads for one table's swap-out (scheduler
        hook, called before the freed device pages can be reallocated)."""
        devs = jnp.asarray([d for d, _ in pairs], jnp.int32)
        hosts = [h for _, h in pairs]
        tr = self.trace
        with tr.span("engine", "sync") if tr is not None else NO_SPAN:
            self.h_k_pages[:, hosts] = np.asarray(self.k_pages[:, devs])
            self.h_v_pages[:, hosts] = np.asarray(self.v_pages[:, devs])
        self.swapped_out += 1

    def _swap_out_issue(self, pairs) -> None:
        """Issue half of a double-buffered swap-out: the DMA is in flight
        against the next iteration's compute. The source device pages stay
        allocated through the allocator's pending ledger and are never
        written while pending, so the payload copy is deferred to the
        complete half — byte-identical to copying now, with nothing
        serialized into this iteration."""

    def _swap_out_complete(self, pairs) -> None:
        """Complete half: materialize the device->host payloads (sources
        untouched since issue), called by the scheduler *before* the
        allocator decrefs the device pages."""
        self._swap_out_copy(pairs)

    def _swap_out_cancel(self, pairs) -> None:
        """Pressure receded before the transfer resolved: the pages never
        left the device, nothing to copy (host blocks are returned by the
        allocator's cancel path)."""

    def _swap_in_copy(self, pairs) -> None:
        """Host -> device onto the freshly allocated blocks (batched: one
        pool update per direction, same idiom as the COW copy in step)."""
        hosts = [h for h, _ in pairs]
        devs = jnp.asarray([d for _, d in pairs], jnp.int32)
        self.k_pages = self.k_pages.at[:, devs].set(
            jnp.asarray(self.h_k_pages[:, hosts]))
        self.v_pages = self.v_pages.at[:, devs].set(
            jnp.asarray(self.h_v_pages[:, hosts]))
        self.swapped_in += 1

    def _spill_out_copy(self, pairs) -> None:
        """Prefix-cache spill movers: same transfers as a table swap, kept
        out of the swapped_out/in event counters."""
        devs = jnp.asarray([d for d, _ in pairs], jnp.int32)
        hosts = [h for _, h in pairs]
        tr = self.trace
        with tr.span("engine", "sync") if tr is not None else NO_SPAN:
            self.h_k_pages[:, hosts] = np.asarray(self.k_pages[:, devs])
            self.h_v_pages[:, hosts] = np.asarray(self.v_pages[:, devs])

    def _spill_in_copy(self, pairs) -> None:
        hosts = [h for h, _ in pairs]
        devs = jnp.asarray([d for _, d in pairs], jnp.int32)
        self.k_pages = self.k_pages.at[:, devs].set(
            jnp.asarray(self.h_k_pages[:, hosts]))
        self.v_pages = self.v_pages.at[:, devs].set(
            jnp.asarray(self.h_v_pages[:, hosts]))

    # -- cross-instance prefix sharing -------------------------------------------

    def export_page_payload(self, block: int):
        """KV contents of one physical page as host arrays, tagged with the
        engine's :attr:`KVPageLayout.schema` — the payload a cluster router
        publishes to the distkv board so a peer engine (same arch + params)
        can adopt the page without recomputing it. An importer with a
        different layout refuses the payload loudly."""
        return (self.kv_layout.schema,
                np.asarray(self.k_pages[:, block]),
                np.asarray(self.v_pages[:, block]))

    def import_page_payloads(self, blocks, payloads) -> None:
        """Materialize published pages into freshly adopted local blocks
        (counterpart of :meth:`export_page_payload`). Every payload's
        schema tag is validated against the local layout before any pool is
        touched — reinterpreting foreign-layout bytes would corrupt pages
        silently. Batched: one update per KV pool regardless of page count
        — ``.at[].set`` outside jit copies the whole pool, so per-page
        calls would copy it 2x per page (same batching the COW path in
        :meth:`step` uses)."""
        if not blocks:
            return
        for p in payloads:
            check_schema(self.kv_layout.schema, p[0],
                         where="page-payload import")
        idx = jnp.asarray(list(blocks), jnp.int32)
        # host payloads (exported by a peer on any device) go straight to
        # this engine's device
        k, v = jax.device_put(
            (np.stack([p[1] for p in payloads], axis=1).astype(
                self.k_pages.dtype),  # (L, n, ps, *token_shape)
             np.stack([p[2] for p in payloads], axis=1).astype(
                 self.v_pages.dtype)), self.device)
        self.k_pages = self.k_pages.at[:, idx].set(k)
        self.v_pages = self.v_pages.at[:, idx].set(v)

    # -- disaggregated prefill/decode handoff -------------------------------------

    @property
    def free_decode_slots(self) -> int:
        """Decode slots a KVHandoff placement can still claim."""
        return min(len(self.free_slots),
                   self.scheduler.max_running - len(self.scheduler.running))

    def release_for_handoff(self, req: Request) -> None:
        """Prefill side of a KV handoff: return the request's decode slot
        and detach it from the scheduler WITHOUT finishing. The caller must
        already have secured the KV (exported payloads / lent the blocks)."""
        slot = self.slots.pop(req.request_id, None)
        if slot is not None:
            self.free_slots.append(slot)
        self.scheduler.release_request(req)

    def install_for_handoff(self, req: Request, table: BlockTable,
                            lease=None) -> None:
        """Decode side of a KV handoff: claim a slot and enter decode
        directly. ``table`` holds the locally-materialized KV pages (all of
        them under migration; only the partial tail page under a zero-copy
        lease, whose full pages stay on the prefill host)."""
        if lease is not None:
            self._check_zero_copy_ok()
            check_schema(self.kv_layout.schema,
                         getattr(lease, "schema", None),
                         where="KV handoff install")
        slot = self.free_slots.pop()
        self.slots[req.request_id] = slot
        # the decode input token is the first token, sampled on the prefill
        # instance from its final chunk's logits
        self.last_token[slot] = req.output[-1]
        self.scheduler.install_running(req, table, lease)

    def run_to_completion(self, max_iters: int = 10_000) -> None:
        for _ in range(max_iters):
            self.step()
            if not self.has_work:
                return
        raise RuntimeError("engine did not drain")

    # -- stats ------------------------------------------------------------------
    def kv_utilization(self) -> float:
        return self.allocator.utilization(list(self.scheduler.tables.values()))

    def prefix_cache_stats(self) -> Dict[str, float]:
        if self.prefix_cache is None:
            return {}
        return self.prefix_cache.stats()
