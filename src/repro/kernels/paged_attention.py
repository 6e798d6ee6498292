"""Paged-attention decode kernel (vLLM PagedAttention, TPU-native).

GPU vLLM walks a block table per warp and gathers KV from scattered global
memory. The TPU adaptation (DESIGN.md §2.3): the block table is a
**scalar-prefetch operand**; each grid step DMAs one logical KV page
(``(page_size, kv_heads, head_dim)``) HBM→VMEM via the ``BlockSpec`` index_map,
and an **online-softmax accumulator** in VMEM scratch merges pages — the same
math as flash-decoding, driven by the page table.

Grid: ``(batch, pages_per_seq)``; the page axis is ``arbitrary`` (sequential)
so the scratch accumulator carries across pages of one sequence.

Outputs optionally include the ``(m, l)`` partials instead of the normalized
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


def _paged_attn_kernel(
    # scalar prefetch
    block_tables_ref,  # (B, pages_per_seq) int32
    context_lens_ref,  # (B,) int32
    # inputs
    q_ref,       # (1, Hkv, G, Dh)
    k_page_ref,  # (1, page_size, Hkv, Dh)
    v_page_ref,  # (1, page_size, Hkv, Dh)
    # outputs
    o_ref,       # (1, Hkv, G, Dh)
    m_out_ref,   # (1, Hkv, G)   running max   (partials)
    l_out_ref,   # (1, Hkv, G)   running sum-exp (partials)
    # scratch
    m_ref,   # (Hkv, G)
    l_ref,   # (Hkv, G)
    acc_ref,  # (Hkv, G, Dh)
    *,
    page_size: int,
    pages_per_seq: int,
    window: Optional[int],
    scale: float,
):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = context_lens_ref[b]
    # absolute token positions held by this logical page
    pos = i * page_size + jax.lax.iota(jnp.int32, page_size)
    valid = pos < ctx
    if window is not None:
        valid &= pos > ctx - 1 - window

    q = q_ref[0].astype(jnp.float32)         # (Hkv, G, Dh)
    k = k_page_ref[0].astype(jnp.float32)    # (P, Hkv, Dh)
    v = v_page_ref[0].astype(jnp.float32)

    s = jnp.einsum("hgd,phd->hgp", q, k) * scale  # (Hkv, G, P)
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(s, axis=-1)                     # (Hkv, G)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])               # (Hkv, G, P)
    p = jnp.where(valid[None, None, :], p, 0.0)
    l_new = l_prev * alpha + p.sum(-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
        "hgp,phd->hgd", p, v)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(i == pages_per_seq - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-9)[..., None]
                    ).astype(o_ref.dtype)
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
    ``return_partials`` the tuple ``(o_unnormalized?, m, l)`` — note ``o`` IS
    normalized here; partials additionally expose (m, l) so a DistAttention
    combiner can merge shards: o_merged = Σ l_i·exp(m_i−m)·o_i / Σ l_i·exp(m_i−m).
    """
    b, h, dh = q.shape
    _, ps, hkv, _ = k_pages.shape
    assert ps == page_size
    g = h // hkv
    pages_per_seq = block_tables.shape[1]
    scale = 1.0 / (dh ** 0.5)

    qg = q.reshape(b, hkv, g, dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, hkv, g, dh), lambda bb, i, bt, cl: (bb, 0, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, dh),
                         lambda bb, i, bt, cl: (bt[bb, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, dh),
                         lambda bb, i, bt, cl: (bt[bb, i], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, g, dh), lambda bb, i, bt, cl: (bb, 0, 0, 0)),
            pl.BlockSpec((1, hkv, g), lambda bb, i, bt, cl: (bb, 0, 0)),
            pl.BlockSpec((1, hkv, g), lambda bb, i, bt, cl: (bb, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_attn_kernel, page_size=page_size, pages_per_seq=pages_per_seq,
        window=window, scale=scale)
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, g), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, context_lens, qg, k_pages, v_pages)
    out = out.reshape(b, h, dh)
    if return_partials:
        return out, m.reshape(b, h), l.reshape(b, h)
    return out
