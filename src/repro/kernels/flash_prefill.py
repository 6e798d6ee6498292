"""Causal flash-attention prefill kernel (+ sliding window), TPU Pallas.

Grid ``(B, Hkv, nq, nk)`` with the KV axis sequential ("arbitrary") so the
online-softmax scratch accumulator carries across KV blocks of one query
block. Blocks are MXU-aligned where the head_dim allows (q/k blocks default
128x128 tiles). GQA is handled by blocking G query heads of the same KV group
together — one KV DMA serves all G query heads.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  q_block: int, kv_block: int, nk: int,
                  window: Optional[int], causal: bool, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, q_block, Dh)
    k = k_ref[0, 0].astype(jnp.float32)  # (kv_block, Dh)
    v = v_ref[0, 0].astype(jnp.float32)

    qpos = iq * q_block + jax.lax.iota(jnp.int32, q_block)
    kpos = ik * kv_block + jax.lax.iota(jnp.int32, kv_block)
    mask = jnp.ones((q_block, kv_block), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window

    s = jnp.einsum("gqd,kd->gqk", q, k) * scale
    s = jnp.where(mask[None], s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask[None], p, 0.0)
    l_new = l_prev * alpha + p.sum(-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
        "gqk,kd->gqd", p, v)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-9)[..., None]
                       ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_block", "kv_block", "interpret"))
def flash_prefill(
    q,  # (B, S, H, Dh)
    k,  # (B, Skv, Hkv, Dh)
    v,  # (B, Skv, Hkv, Dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool = False,
):
    b, s, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    q_block = min(q_block, s)
    kv_block = min(kv_block, skv)
    assert s % q_block == 0 and skv % kv_block == 0
    nq, nk = s // q_block, skv // kv_block
    scale = 1.0 / (dh ** 0.5)

    # (B, Hkv, G, S, Dh) so one KV block serves all G grouped query heads
    qg = q.reshape(b, s, hkv, g, dh).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, Skv, Dh)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, q_block=q_block, kv_block=kv_block, nk=nk,
        window=window, causal=causal, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, q_block, dh),
                         lambda bb, hh, iq, ik: (bb, hh, 0, iq, 0)),
            pl.BlockSpec((1, 1, kv_block, dh),
                         lambda bb, hh, iq, ik: (bb, hh, ik, 0)),
            pl.BlockSpec((1, 1, kv_block, dh),
                         lambda bb, hh, iq, ik: (bb, hh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, q_block, dh),
                               lambda bb, hh, iq, ik: (bb, hh, 0, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, q_block), jnp.float32),
            pltpu.VMEM((g, q_block), jnp.float32),
            pltpu.VMEM((g, q_block, dh), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, s, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qg, kt, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh)
