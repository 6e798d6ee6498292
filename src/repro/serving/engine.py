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
requests submitted without any decode greedily. Best-of-n requests
(``SamplingParams.n > 1``) COW-fork the parent's block table right after
its prefill — siblings share every prompt page and diverge through the
allocator's copy-on-write on the first partial-page write, with the engine
copying the physical page contents for each ``(old, new)`` pair the
scheduler reports.

Divergence from paper noted (DESIGN.md §2.2): ORCA's selective batching fuses
prefill+decode tokens into one ragged batch; XLA needs static shapes, so
prefills run as separate padded calls while decodes fuse across slots — the
iteration-level scheduling semantics (early exit, late join) are identical.

Serves uniform GQA (MQA/SWA) or MLA stacks. For paging, the block tables,
COW forks and preemption come straight from ``core.paging``; the page pools
are one device array per pool of the arch's
:class:`~repro.core.paging.layout.KVPageLayout`. The per-layer math (ln →
qkv+rope → attend → wo → mlp) is the models' shared
:func:`~repro.models.attention.gqa_layer` / ``mla_layer`` body, which both
step programs run through one paged attend: write the new payloads, then
read.
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


def _take_pages(pools, pages):
    """Each pool's ``pages`` (L, n, ps, *token_shape), on the pools'
    device."""
    idx = jnp.asarray(pages, jnp.int32)
    return tuple(p[:, idx] for p in pools)


def _merge_two(q, local, remote, scale=None):
    """DistAttention over local and borrowed keys: the masked partial of
    each ``(k, v, mask)`` side (:func:`attention_partial`), merged by
    log-sum-exp."""
    parts = [attention_partial(q, k, v, mask, scale=scale)
             for k, v, mask in (local, remote)]
    return merge_partials_tree(*zip(*parts))


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
        P, ps = ecfg.num_pages, ecfg.page_size
        # the pools live where the params were committed: one engine per
        # device, each replica on its own chip (one device only — the
        # unpacking refuses params spread over several)
        (self.device,) = jax.tree.leaves(params)[0].devices()
        # one device array per pool of the layout (GQA: k, v; MLA: ckv,
        # krope), +1 trash page: inactive decode slots park their writes
        # there. Page-granular plumbing (COW, swap, spill, export) indexes
        # only axis 1 and never the trailing token shape.
        self.pools = tuple(
            jnp.zeros(shape, cfg.param_dtype, device=self.device)
            for shape in self.kv_layout.pool_shapes(P + 1, ps))
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
            self.host_pools = tuple(
                np.zeros(shape, pool.dtype) for shape, pool in zip(
                    self.kv_layout.pool_shapes(ecfg.host_pages, ps),
                    self.pools))
            self.scheduler.swap_out_hook = self._swap_out_copy
            self.scheduler.swap_in_hook = self._swap_in_copy
            # speculative swap-outs: the allocator's pending ledger keeps
            # the source pages allocated and unwritten while "in flight",
            # so the copy waits for the complete half (issue and cancel
            # move nothing)
            self.scheduler.swap_complete_hook = self._swap_out_copy
            if self.prefix_cache is not None:
                self.prefix_cache.spill_out_fn = self._pages_to_host
                self.prefix_cache.spill_in_fn = self._pages_from_host
        else:
            self.host_pools = None
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
        # requests submitted without sampling params decode greedily
        self._default_sp = SamplingParams()
        self._sample_fn = jax.jit(sampling.sample_batch)
        # best-of-n children awaiting their parent's prefill (COW fork)
        self._pending_forks: Dict[int, List[Request]] = {}
        # zero-copy cluster serving: reader(home_instance) -> the creditor
        # engine's pools, wired by RouterBackend when borrowed-rBlock
        # serving is enabled
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
        """Per-segment MLP dispatch for the shared layer body: dense
        segments use the layer default, MoE segments the dropless expert
        layer and its routing counts over the rows ``valid`` (B, S) marks
        as tokens: a chunk's padding and idle decode slots are neither
        routed to an expert nor counted (DeepSeek-V2's plan is 1 dense +
        N-1 MoE layers)."""
        if seg.mlp_kind == "moe":
            return lambda pm, h: moe_mod.moe_layer(self.cfg, pm, h,
                                                   valid=valid)[:2]
        return None

    def _run_segments(self, params, pools, remote, x, body):
        """Thread ``x`` through every segment of the plan with the layer
        axis of the page pools (and of the remote payloads, None without a
        lease). ``body(seg, p_i, pools_i, remote_i, x) -> (x, pools_i',
        stats)`` runs ONE layer (``stats``: the MoE layer's (assigned, hit)
        routing counts, None for a dense MLP); stacked segments (seg.n > 1)
        ``lax.scan`` it over their stacked params. Returns (x, pools,
        routes) with ``routes`` the (MoE layers, 2) int32 counts in layer
        order (None without MoE layers).

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
                xx, pools_i, st = body(seg, *scanned, xx)
                return (xx,), (pools_i, st)

            (x,), (pools, st) = jax.lax.scan(scan_body, (x,),
                                             (segs[0], pools, remote))
            note(st, True)
        else:
            off = 0
            for seg, p_seg in zip(plan, segs):
                def one(carry, scanned, seg=seg, off=off):
                    xx, pl = carry
                    i, p_i = scanned
                    layer = off + i
                    xx, pl_i, st = body(
                        seg, p_i, tuple(p[layer] for p in pl),
                        jax.tree.map(lambda r: r[layer], remote), xx)
                    pl = tuple(jax.lax.dynamic_update_index_in_dim(
                        p, new, layer, 0) for p, new in zip(pl, pl_i))
                    return (xx, pl), st

                if seg.n == 1:
                    (x, pools), st = one((x, pools), (0, p_seg))
                    note(st, False)
                else:
                    (x, pools), st = jax.lax.scan(
                        one, (x, pools), (jnp.arange(seg.n), p_seg))
                    note(st, True)
                off += seg.n
        routes = jnp.concatenate(routes, 0) if routes else None
        return x, pools, routes

    def _forward(self, params, pools, remote, tokens, positions, w_page,
                 w_off, valid, read, length=None):
        """The model both step programs run: embed ``tokens`` (B, S), then
        every layer writes its new per-token payloads, one per pool, and
        attends over the written pools through ``read(seg, *queries, pools,
        remote_i)`` (GQA queries: ``q``; MLA: ``q_lat, qr``; ``remote_i``:
        the layer's borrowed payloads, None without a lease). ``w_page`` and
        ``w_off`` are flat (rows,) arrays, a row per chunk token or decode
        slot: the page and offset its payload lands at; ``valid`` marks the
        rows that are tokens the experts take, flat or (B, S).
        ``positions`` are the layers' RoPE positions. The final norm and
        the unembedding close it, over each sequence's row ``length - 1``
        (a chunk's last real token; ``length`` is traced) or over all rows.
        Returns (logits (B, S', V), pools, routes)."""
        cfg = self.cfg
        layer = mla_layer if self.flavor == "mla" else gqa_layer
        rows = w_page.shape[0]

        def body(seg, p_i, pools_i, rem_i, xx):
            def attend(*args):
                # the queries, then one new payload per pool
                qs, new = args[:-len(pools_i)], args[-len(pools_i):]
                written = tuple(
                    pool.at[w_page, w_off].set(
                        a.reshape((rows,) + pool.shape[2:]).astype(
                            pool.dtype))
                    for pool, a in zip(pools_i, new))
                return read(seg, *qs, written, rem_i), written

            return layer(cfg, p_i, xx, positions, attend, mlp_fn=self._mlp_fn(
                seg, valid.reshape(xx.shape[:2])))

        x = embed(params["embed"], tokens)
        x, pools, routes = self._run_segments(params, pools, remote, x, body)
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        if length is not None:
            x = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        logits = unembed(output_head(params), x, cfg.vocab_size,
                         fp32=cfg.logits_fp32)
        return logits, pools, routes

    @partial(jax.jit, static_argnums=(0,))
    def _prefill_chunk_fn(self, params, pools, tokens, page_ids, start,
                          length, remote=None):
        """One prefill chunk at absolute positions ``[start, start+length)``.

        tokens: (1, S) chunk token ids padded to a power-of-two bucket
        (positions past ``length`` are pad: their payloads scatter to the
        trash page and their outputs are discarded); page_ids: (n,)
        physical pages — also pow2-padded — covering *local* context
        positions ``[r_base, start+length)`` in order: radix-cached prefix
        pages, pages written by earlier chunks, and the pages this chunk
        lands in. ``start`` / ``length`` / ``r_base`` are traced scalars,
        so chunk boundaries (and token-level cache hits mid-page) recompile
        only per shape *bucket* — a mixed-length workload compiles O(log)
        variants, not one per (chunk_len, n_pages) pair. Each chunk token's
        payload is scattered to its (page, offset) slot, then the chunk
        queries attend causally over every gathered context page —
        positions beyond each query are masked, so stale contents past the
        chunk's end (and the pad pages, which sit at even higher positions)
        are never read.

        Zero-copy remote prefix: ``remote = (r_base, *payloads)``, one
        (L, R, *token_shape) payload per pool holding the borrowed pages'
        contents (gathered from the creditor instance's pools), serving
        absolute positions ``[0, r_base)``; the local causal partial and
        the remote partial are combined with the DistAttention log-sum-exp
        merge. ``remote=None`` (the common case) is ``r_base = 0`` and
        keeps the single-softmax path bit-for-bit.

        GQA scatters K/V and attends with ``blockwise_attention``. MLA
        scatters the *latent* per-token payload (ckv, krope) and attends
        with the matrix-absorbed effective single-kv-head form
        (``mla_effective_kv``), so pages hold ``kv_lora_rank +
        qk_rope_head_dim`` elements per token per layer instead of ``2 *
        Hkv * Dh``.

        Returns (logits (V,) of the last real chunk position, pools,
        routes); callers ignore the logits for non-final chunks; ``routes``
        holds each MoE layer's routing counts (:meth:`_run_segments`).
        Subsumes whole-prompt prefill (start=0, one chunk) and
        cached-suffix prefill (start = cached tokens).
        """
        cfg = self.cfg
        ecfg = self.ecfg
        ps = ecfg.page_size
        s = tokens.shape[1]
        npg = page_ids.shape[0]
        r_base, payloads = (0, None) if remote is None \
            else (remote[0], remote[1:])
        positions = start + jnp.arange(s)        # (s,) absolute
        valid_tok = jnp.arange(s) < length
        loc_pos = positions - r_base             # position within local pages
        # pad tokens park their writes on the trash page, like inactive
        # decode slots — real pages never see pad payloads
        tok_pages = jnp.where(
            valid_tok, page_ids[jnp.clip(loc_pos // ps, 0, npg - 1)],
            ecfg.num_pages)
        in_page = loc_pos % ps
        if remote is not None:
            # local keys sit at absolute positions r_base + [0, npg*ps),
            # remote keys at [0, r_base): all remote positions precede
            # every chunk query, so only validity masks the remote side
            key_pos = r_base + jnp.arange(npg * ps)
            mask_l = positions[None, :, None] >= key_pos[None, None, :]
            mask_r = (jnp.arange(payloads[0].shape[1]) < r_base)[
                None, None, :] & jnp.ones((1, s, 1), bool)

        def context(pools):  # (1, npg*ps, *token_shape) per pool
            return [p[page_ids].reshape((1, npg * ps) + p.shape[2:])
                    for p in pools]

        if self.flavor == "mla":
            r_lat, scale = cfg.kv_lora_rank, _mla_scale(cfg)

            def read(seg, q_lat, qr, pools, rem):
                ckv, krope = context(pools)
                q_eff, k_eff, v_eff = mla_effective_kv(
                    q_lat, qr, ckv.astype(q_lat.dtype),
                    krope.astype(q_lat.dtype))
                if rem is None:
                    ctx = blockwise_attention(q_eff, k_eff, v_eff,
                                              causal=True, q_offset=start,
                                              scale=scale)
                else:
                    kr_eff, vr_eff = mla_effective_ctx(
                        *(r[None].astype(q_lat.dtype) for r in rem))
                    ctx = _merge_two(q_eff, (k_eff, v_eff, mask_l),
                                     (kr_eff, vr_eff, mask_r), scale)
                return ctx[..., :r_lat].astype(q_lat.dtype)
        else:
            def read(seg, q, pools, rem):
                k, v = context(pools)
                if rem is None:
                    window = cfg.sliding_window \
                        if seg.attn_kind == "swa" else None
                    return blockwise_attention(q, k.astype(q.dtype),
                                               v.astype(q.dtype),
                                               causal=True, window=window,
                                               q_offset=start)
                rk, rv = rem
                return _merge_two(q, (k, v, mask_l),
                                  (rk[None], rv[None], mask_r)
                                  ).astype(q.dtype)

        logits, pools, routes = self._forward(
            params, pools, payloads, tokens, positions, tok_pages, in_page,
            valid_tok, read, length=length)
        # logits of the last REAL chunk position (pad rows are garbage)
        return logits[0, 0], pools, routes

    @partial(jax.jit, static_argnums=(0,))
    def _decode_fn(self, params, pools, tokens, positions, block_tables,
                   ctx_lens, remote=None):
        """Batched one-token step over slots.

        tokens: (n,), positions: (n,), block_tables: (n, max_pages),
        ctx_lens: (n,) (0 = inactive slot). Returns (logits (n, V), pools,
        routes) as :meth:`_prefill_chunk_fn` does.

        GQA runs the Pallas paged-attention kernel where ``use_kernel``
        resolves to it (the default on a TPU: it reads only each slot's
        live pages) and otherwise the models' paged decode attention, which
        gathers the whole block table. MLA gathers the latent pools' whole
        table and attends in the matrix-absorbed form over the latent and
        the rotary keys in place (:func:`mla_latent_attention`; the Pallas
        kernel is GQA-shaped).

        Zero-copy remote prefixes: ``remote = (r_base, *payloads)`` where
        some slots serve their leading context from pages *borrowed* from a
        peer instance. r_base: (n,) borrowed tokens per slot (0 = fully
        local — such slots reduce to the plain paged path numerically);
        payloads: one (L, n, R, *token_shape) array per pool, the borrowed
        pages' contents gathered from each creditor's pools, covering
        absolute positions ``[0, r_base[i])`` of slot ``i``, read from the
        creditor's pages in place of an RDMA fetch. Both flavors gather the
        whole local table then: GQA attends over the borrowed and the local
        keys in one softmax, with the numerics of the model's own decode
        (:func:`decode_attention`); MLA merges the local and remote
        partials with the DistAttention log-sum-exp merge (InfiniteLLM's
        micro-attention aggregation)."""
        cfg = self.cfg
        ecfg = self.ecfg
        n = tokens.shape[0]
        ps = ecfg.page_size
        if remote is None:
            payloads, loc_pos, loc_lens = None, positions, ctx_lens
        else:
            r_base, payloads = remote[0], remote[1:]
            loc_pos = jnp.maximum(positions - r_base, 0)  # write slot, local
            loc_lens = jnp.maximum(ctx_lens - r_base, 0)  # local context
            mask_r = jnp.arange(payloads[0].shape[2])[None, :] < \
                r_base[:, None]  # (n, R)
        page_slot = block_tables[jnp.arange(n), loc_pos // ps]  # (n,)
        # inactive slots (ctx_len == 0) write to the trash page
        page_slot = jnp.where(ctx_lens > 0, page_slot, ecfg.num_pages)
        in_page = loc_pos % ps

        def context(pools):  # (n, max_pages*ps, *token_shape) per pool
            return [p[block_tables].reshape((n, -1) + p.shape[2:])
                    for p in pools]

        if self.flavor == "mla":
            r_lat, scale = cfg.kv_lora_rank, _mla_scale(cfg)

            def read(seg, q_lat, qr, pools, rem):
                ckv, krope = context(pools)
                mask = jnp.arange(ckv.shape[1])[None, :] < \
                    loc_lens[:, None]  # (n, S)
                if rem is None:
                    ctx = mla_latent_attention(q_lat[:, 0], qr[:, 0], ckv,
                                               krope, mask, scale)
                    return ctx[:, None].astype(q_lat.dtype)
                q_eff, k_eff, v_eff = mla_effective_kv(
                    q_lat, qr, ckv.astype(q_lat.dtype),
                    krope.astype(q_lat.dtype))
                kr_eff, vr_eff = mla_effective_ctx(
                    *(r.astype(q_lat.dtype) for r in rem))
                att = _merge_two(q_eff, (k_eff, v_eff, mask[:, None]),
                                 (kr_eff, vr_eff, mask_r[:, None]), scale)
                return att[..., :r_lat].astype(q_lat.dtype)
        else:
            def read(seg, q, pools, rem):
                if rem is None:
                    window = cfg.sliding_window \
                        if seg.attn_kind == "swa" else None
                    att_fn = ops.paged_attention if self.use_kernel \
                        else paged_decode_attention
                    att = att_fn(q[:, 0], *pools, block_tables, ctx_lens,
                                 page_size=ps, window=window)
                    return att.reshape(n, 1, cfg.num_heads, cfg.head_dim)
                k, v = context(pools)
                mask_l = jnp.arange(k.shape[1])[None, :] < \
                    loc_lens[:, None]  # (n, S_loc)
                # the borrowed keys hold positions [0, r_base), ahead of
                # every local key: one softmax over both, in the model's
                # own decode numerics
                rk, rv = rem
                att = decode_attention(
                    q[:, 0], jnp.concatenate([rk, k], 1),
                    jnp.concatenate([rv, v], 1),
                    jnp.concatenate([mask_r, mask_l], 1))
                return att.reshape(q.shape).astype(q.dtype)

        logits, pools, routes = self._forward(
            params, pools, payloads, tokens[:, None], positions[:, None],
            page_slot, in_page, (ctx_lens > 0)[:, None], read)
        return logits[:, 0], pools, routes

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
            # the creditor's pools may sit on another device: the gathered
            # pages move to this engine's device once per lease
            hit = jax.device_put(tuple(
                a.reshape((self.nlayers, -1) + a.shape[3:])
                for a in _take_pages(self.remote_reader(lease.home),
                                     lease.blocks)), self.device)
            self._lease_kv_cache[key] = hit
        return hit

    def _prune_lease_cache(self) -> None:
        live = {id(l) for l in self.scheduler.leases.values()}
        for key in [k for k in self._lease_kv_cache if k not in live]:
            del self._lease_kv_cache[key]

    def _lease_kv_chunk(self, lease):
        """(L, Rpad, *token_shape) borrowed payloads, pow2-padded (pad
        tokens are masked by ``r_base`` inside the jitted chunk fn)."""
        pad = _pow2_bucket(lease.num_pages, 1) * self.ecfg.page_size \
            - lease.num_tokens
        return tuple(
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            if pad else a for a in self._lease_kv(lease))

    def _lease_kv_batch(self, row_reqs):
        """(L, n, Rpad, *token_shape) stacked borrowed payloads for a
        decode batch (zero rows for slots without a lease)."""
        leases = self.scheduler.leases
        rmax = max(leases[r.request_id].num_pages for r in row_reqs
                   if r is not None and r.request_id in leases)
        rpad = _pow2_bucket(rmax, 1) * self.ecfg.page_size
        out = [jnp.zeros((self.nlayers, self.ecfg.max_slots, rpad)
                         + p.shape[3:], p.dtype) for p in self.pools]
        for slot, req in enumerate(row_reqs):
            if req is None or req.request_id not in leases:
                continue
            lease = leases[req.request_id]
            out = [o.at[:, slot, :lease.num_tokens].set(a)
                   for o, a in zip(out, self._lease_kv(lease))]
        return tuple(out)

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
            self._put_pages([w for _, w in plan.cow],
                            _take_pages(self.pools, [o for o, _ in plan.cow]))

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
            remote = None
            if r_base:
                self._check_zero_copy_ok()
                remote = (jnp.int32(r_base), *self._lease_kv_chunk(
                    self.scheduler.leases[req.request_id]))
            # the span times the dispatch (the call returns before the
            # device has run the chunk)
            with tr.span("engine", "chunk", rid=req.request_id,
                         start=ch.start, length=ch.length, last=ch.is_last) \
                    if tr is not None else NO_SPAN:
                logits, self.pools, moe = self._prefill_chunk_fn(
                    self.params, self.pools, jnp.asarray(tok_arr)[None],
                    jnp.asarray(page_arr), jnp.int32(ch.start),
                    jnp.int32(ch.length), remote)
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
            remote = None
            if rbase.any():
                # >=1 slot reads a borrowed prefix: local paged partial +
                # remote partial, merged (DistAttention). Fully-local slots
                # ride along with r_base = 0.
                self._check_zero_copy_ok()
                remote = (jnp.asarray(rbase),
                          *self._lease_kv_batch(row_reqs))
            logits, self.pools, moe = self._decode_fn(
                self.params, self.pools, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(bt), jnp.asarray(lens), remote)
            if remote is None and self.metrics is not None:
                # cumulative: the pages inside each active slot's context
                # and window (what the kernel reads) against the block
                # table's width (what the XLA gather reads)
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

    def _pages_to_host(self, pairs) -> None:
        """Device -> host page payloads for ``(device, host)`` page pairs:
        the prefix cache's spill-out, and a table's swap-out (called
        before the freed device pages can be reallocated)."""
        hosts = [h for _, h in pairs]
        tr = self.trace
        with tr.span("engine", "sync") if tr is not None else NO_SPAN:
            for h, a in zip(self.host_pools,
                            _take_pages(self.pools, [d for d, _ in pairs])):
                h[:, hosts] = np.asarray(a)

    def _pages_from_host(self, pairs) -> None:
        """Host -> device payloads onto freshly allocated blocks for
        ``(host, device)`` page pairs: spill-in and swap-in."""
        hosts = [h for h, _ in pairs]
        self._put_pages([d for _, d in pairs],
                        [h[:, hosts] for h in self.host_pools])

    def _put_pages(self, pages, payloads) -> None:
        """Write ``payloads`` — one (L, n, ps, *token_shape) array per pool,
        from the host, a peer or these pools — onto device ``pages``.
        Batched: one update per pool whatever the page count, since
        ``.at[].set`` outside jit copies the whole pool."""
        idx = jnp.asarray(pages, jnp.int32)
        payloads = jax.device_put(tuple(payloads), self.device)
        self.pools = tuple(p.at[:, idx].set(a)
                           for p, a in zip(self.pools, payloads))

    def _swap_out_copy(self, pairs) -> None:
        self._pages_to_host(pairs)
        self.swapped_out += 1

    def _swap_in_copy(self, pairs) -> None:
        self._pages_from_host(pairs)
        self.swapped_in += 1

    # -- cross-instance prefix sharing -------------------------------------------

    def export_page_payload(self, block: int):
        """KV contents of one physical page as host arrays (one per pool),
        tagged with the engine's :attr:`KVPageLayout.schema` — the payload
        a cluster router publishes to the distkv board so a peer engine
        (same arch + params) can adopt the page without recomputing it. An
        importer with a different layout refuses the payload loudly."""
        return (self.kv_layout.schema,
                *(np.asarray(a)[:, 0]
                  for a in _take_pages(self.pools, [block])))

    def import_page_payloads(self, blocks, payloads) -> None:
        """Materialize published pages into freshly adopted local blocks
        (counterpart of :meth:`export_page_payload`). Every payload's
        schema tag is validated against the local layout before any pool is
        touched — reinterpreting foreign-layout bytes would corrupt pages
        silently. Batched: one update per pool regardless of page count
        (:meth:`_put_pages`)."""
        if not blocks:
            return
        for p in payloads:
            check_schema(self.kv_layout.schema, p[0],
                         where="page-payload import")
        # host payloads (exported by a peer on any device) go straight to
        # this engine's device: (L, n, ps, *token_shape) per pool
        self._put_pages(list(blocks), [
            np.stack([p[1 + j] for p in payloads], axis=1).astype(pool.dtype)
            for j, pool in enumerate(self.pools)])

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
