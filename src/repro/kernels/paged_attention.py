"""Paged-attention decode kernel (vLLM PagedAttention, TPU-native).

GPU vLLM walks a block table per warp and gathers KV from scattered global
memory. The TPU adaptation: the K/V pools stay in HBM and each grid step
copies one *block* of several pages per pool into VMEM through
``BlockSpec`` index maps that read the physical page ids from a
**scalar-prefetch** work list; Mosaic's pipeline double-buffers those
copies, so the next block's DMA overlaps the current block's math. An
**online-softmax accumulator** in VMEM scratch (f32) merges the blocks of a
slot — the same math as flash-decoding, driven by the page table.

Each slot reads only its *live* pages: logical pages ``first .. last`` with
``last = (ctx_len - 1) // page_size`` and, under a sliding window,
``first = max(0, ctx_len - window) // page_size`` (else 0). The grid is
one-dimensional and dynamic: one step per live block of every slot, laid
out slot after slot by :func:`_work_list` from ``context_lens``, so DMA
traffic and grid steps scale with the real contexts, not with the block
table's width. A slot with ``ctx_len == 0`` has no step and returns zeros.
In a slot's last, partial block the pages past ``last`` repeat the
previous step's page id, so the pipeline starts no copy for them. Rows
outside ``[max(0, ctx_len - window), ctx_len)`` get zero weight; like the
reference's, their values must be finite, and every buffer holds a live
page of some slot, never a page no slot attends to.

Within a block all KV heads are handled at once: the block's keys are the
rows ``(token, kv_head)`` of each page's ``(page_size * Hkv, Dh)`` matrix,
every query head scores every row, and rows of another KV head are masked
like positions outside the context. That costs ``Hkv`` times the matmul
work of per-head dots but needs no strided loads; with one query token per
slot the matmuls stay far below the MXU's rate. Matmul operands are in the
pools' dtype (bf16 pools: bf16 operands, the models' own decode numerics)
with f32 accumulation.

Manual ``make_async_copy`` of a page cannot replace the pipeline here:
Mosaic refuses a DMA slice of a pool whose head_dim is not a multiple of
128 lanes (h2o-danube-1.8b's 80), while a ``BlockSpec`` block spanning the
page's whole trailing dims is accepted.

Outputs optionally include the ``(m, l)`` partials besides the normalized
value — that is the *Micro Attention* interface of InfiniteLLM's
DistAttention: shard-local partial results merged later with a stable
log-sum-exp (see ``repro.core.distkv.dist_attention``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# bytes of one pool's pages per block: large enough that a block's DMA
# outweighs a grid step's fixed cost, small enough to double-buffer K and
# V in VMEM
BLOCK_BYTES = 256 * 1024


def pages_per_block(page_size: int, kv_heads: int, head_dim: int,
                    itemsize: int, pages_per_seq: int) -> int:
    """Largest power of two of pages whose bytes stay within
    ``BLOCK_BYTES`` and which the block table can fill (8 for both
    h2o-danube-1.8b's 20 KB and Mistral-Large's 32 KB bf16 pages)."""
    page_bytes = page_size * kv_heads * head_dim * itemsize
    nb = 1
    while 2 * nb * page_bytes <= BLOCK_BYTES and 2 * nb <= pages_per_seq:
        nb *= 2
    return nb


def live_pages(context_lens, page_size: int, window: Optional[int]):
    """(first, count) of each slot's live logical pages: those holding a
    position in ``[max(0, ctx - window), ctx)``; count 0 where ctx is 0.
    Works on numpy and jax arrays alike."""
    lo = context_lens - context_lens if window is None else \
        (context_lens - window).clip(0)
    first = lo // page_size
    last = (context_lens - 1) // page_size
    return first, (last - first + 1) * (context_lens > 0)


def _work_list(block_tables, context_lens, *, page_size, window, nb,
               max_steps):
    """One row per grid step, slot after slot over each slot's live blocks:
    (number of steps, step -> slot, step -> block of the slot, step ->
    ``nb`` physical page ids flattened). A page past the slot's last live
    page takes the id the same block input had one step earlier (so the
    pipeline skips its copy), or the slot's last live page on a first step.
    """
    first, count = live_pages(context_lens, page_size, window)
    nblk = -(-count // nb)
    ends = jnp.cumsum(nblk)
    total = ends[-1]
    step = jnp.arange(max_steps, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, step, side="right"),
                       context_lens.shape[0] - 1).astype(jnp.int32)
    blk = (step - (ends[slot] - nblk[slot])).astype(jnp.int32)
    rel = blk[:, None] * nb + jnp.arange(nb, dtype=jnp.int32)  # (S, nb)
    live = (rel < count[slot][:, None]) & (step < total)[:, None]
    logical = first[slot][:, None] + jnp.minimum(
        rel, jnp.maximum(count[slot] - 1, 0)[:, None])
    phys = jnp.take_along_axis(block_tables[slot], logical, axis=1)
    # carry forward: the latest live step at or before each step, per input
    src = jax.lax.cummax(jnp.where(live, step[:, None], -1), axis=0)
    pages = jnp.where(src >= 0,
                      jnp.take_along_axis(phys, src.clip(0), axis=0), phys)
    return (jnp.maximum(total, 1).astype(jnp.int32), slot, blk,
            pages.reshape(-1).astype(jnp.int32))


def _paged_attn_kernel(
    # scalar prefetch
    step_slot_ref,     # (S,) int32
    step_blk_ref,      # (S,) int32
    step_pages_ref,    # (S * nb,) int32 (index maps only)
    context_lens_ref,  # (B,) int32
    # inputs: q, then nb K pages, then nb V pages
    q_ref,             # (1, H, Dh)
    *refs,             # (1, page_size, Hkv, Dh) each
    nb: int,
    window: Optional[int],
    scale: float,
):
    del step_pages_ref
    k_refs, v_refs = refs[:nb], refs[nb:2 * nb]
    o_ref, m_out_ref, l_out_ref, m_ref, l_ref, acc_ref = refs[2 * nb:]
    s_idx = pl.program_id(0)
    slot = step_slot_ref[s_idx]
    j = step_blk_ref[s_idx]
    _, ps, hkv, dh = k_refs[0].shape
    ctx = context_lens_ref[slot]
    first, count = live_pages(ctx, ps, window)
    lo = 0 if window is None else jnp.maximum(ctx - window, 0)
    h = q_ref.shape[1]
    g = h // hkv
    rows = ps * hkv  # one row per (token, kv head) of a page

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dt = k_refs[0].dtype
    q = q_ref[0].astype(dt)                                     # (H, Dh)
    col = jax.lax.broadcasted_iota(jnp.int32, (h, nb * rows), 1)
    row_head = jax.lax.broadcasted_iota(jnp.int32, (h, nb * rows), 0) // g
    base = (first + j * nb) * ps  # position of the block's first token
    pos = base + col // hkv
    valid = (row_head == col % hkv) & (pos < ctx) & (pos >= lo)

    @pl.when(ctx > 0)
    def _block():
        s = jnp.concatenate([
            jax.lax.dot_general(q, kr[0].reshape(rows, dh),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for kr in k_refs], axis=1)                          # (H, nb*rows)
        s = jnp.where(valid, s * scale, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = alpha * acc_ref[...]
        for i, vr in enumerate(v_refs):
            pv += jnp.dot(p[:, i * rows:(i + 1) * rows].astype(dt),
                          vr[0].reshape(rows, dh).astype(dt),
                          preferred_element_type=jnp.float32)
        acc_ref[...] = pv

    @pl.when(j == (count + nb - 1) // nb - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-9)).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "window", "return_partials", "interpret"))
def paged_attention(
    q,             # (B, H, Dh)
    k_pages,       # (num_pages, page_size, Hkv, Dh)
    v_pages,       # (num_pages, page_size, Hkv, Dh)
    block_tables,  # (B, pages_per_seq) int32 physical page ids
    context_lens,  # (B,) int32
    *,
    page_size: int,
    window: Optional[int] = None,
    return_partials: bool = False,
    interpret: bool = False,
):
    """Decode attention over a paged KV cache. Returns (B, H, Dh), or with
    ``return_partials`` the tuple ``(o, m, l)`` — ``o`` IS normalized;
    the partials additionally expose (m, l) so a DistAttention combiner can
    merge shards: o_merged = Σ l_i·exp(m_i−m)·o_i / Σ l_i·exp(m_i−m).
    Positions are relative to the table's first column; a slot with
    ``context_lens == 0`` gives o = 0, m = NEG_INF, l = 0.
    """
    b, h, dh = q.shape
    _, ps, hkv, _ = k_pages.shape
    assert ps == page_size
    pages_per_seq = block_tables.shape[1]
    nb = pages_per_block(ps, hkv, dh, k_pages.dtype.itemsize, pages_per_seq)
    max_live = pages_per_seq if window is None else \
        min(pages_per_seq, -(-(window - 1) // ps) + 1)
    context_lens = context_lens.astype(jnp.int32)
    n_steps, step_slot, step_blk, step_pages = _work_list(
        block_tables.astype(jnp.int32), context_lens, page_size=ps,
        window=window, nb=nb, max_steps=b * -(-max_live // nb))

    def page_spec(i):
        return pl.BlockSpec(
            (1, ps, hkv, dh),
            lambda s, slot, blk, pages, lens: (pages[s * nb + i], 0, 0, 0))

    def slot_spec(shape):
        return pl.BlockSpec(
            (1,) + shape, lambda s, slot, blk, pages, lens: (slot[s], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_steps,),
        in_specs=[slot_spec((h, dh))] + [page_spec(i) for i in range(nb)] * 2,
        out_specs=[slot_spec((h, dh)), slot_spec((h, 1)),
                   slot_spec((h, 1))],
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_attn_kernel, nb=nb, window=window,
                               scale=1.0 / (dh ** 0.5))
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, dh), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(step_slot, step_blk, step_pages, context_lens, q,
      *[k_pages] * nb, *[v_pages] * nb)
    # a slot without context has no grid step: its blocks were never
    # written
    active = (context_lens > 0)[:, None, None]
    out = jnp.where(active, out, 0).astype(q.dtype)
    if return_partials:
        return (out, jnp.where(active, m, NEG_INF)[..., 0],
                jnp.where(active, l, 0.0)[..., 0])
    return out
