"""Mamba2 SSD chunked-scan Pallas kernel (beyond-paper extension).

Grid ``(B, H, num_chunks)`` with the chunk axis sequential ("arbitrary"):
the SSD state ``(P, N)`` lives in VMEM scratch and carries across chunks —
the inter-chunk recurrence runs inside the kernel, the intra-chunk quadratic
term uses MXU matmuls on ``(chunk x chunk)`` tiles. One grid step streams one
``(chunk, P)`` x-tile and ``(chunk, N)`` B/C-tiles HBM→VMEM.

Equivalent math to ``repro.models.ssm.ssd_chunked`` (the XLA path used by
the models) and to the sequential oracle ``ref.ssd_scan_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_ref, *, chunk: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)    # (L, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (L,)
    a = a_ref[0]                              # scalar A_h (negative)
    bmat = b_ref[0, 0, 0].astype(jnp.float32)  # (L, N)
    cmat = c_ref[0, 0, 0].astype(jnp.float32)  # (L, N)

    adt = dt * a                              # (L,)
    cum = jnp.cumsum(adt)                     # (L,)
    xdt = x * dt[:, None]                     # (L, P)

    # intra-chunk quadratic term: Lmat[i,j] = exp(cum_i - cum_j) for j<=i
    diff = cum[:, None] - cum[None, :]
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    lmat = jnp.where(mask, jnp.exp(diff), 0.0)
    scores = (cmat @ bmat.T) * lmat           # (L, L)
    y = scores @ xdt                          # (L, P)

    # contribution of the incoming inter-chunk state
    decay_in = jnp.exp(cum)[:, None]          # (L, 1)
    y += (cmat @ state_ref[...].T) * decay_in  # (L,N)@(N,P) -> (L,P)

    # state update: S' = S * exp(sum adt) + sum_j decay(end-j) B_j xdt_j
    decay_out = jnp.exp(cum[-1] - cum)[:, None]  # (L, 1)
    state_ref[...] = (state_ref[...] * jnp.exp(cum[-1]) +
                      (decay_out * xdt).T @ bmat)  # (P, N)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        state_out_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 64, interpret: bool = False):
    """x: (b,l,h,p); dt: (b,l,h) fp32 post-softplus; A: (h,); B,C: (b,l,g,n).
    Returns (y (b,l,h,p) fp32, final_state (b,h,p,n) fp32)."""
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    assert l % chunk == 0
    nc = l // chunk

    # (b, h, nc, L, ...) layouts so one grid step reads one chunk tile
    xh = x.transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, p)
    dth = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(b, h, nc, chunk)
    bh = B.transpose(0, 2, 1, 3).reshape(b, g, nc, chunk, n)
    ch = C.transpose(0, 2, 1, 3).reshape(b, g, nc, chunk, n)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, nc=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bb, hh, cc: (bb, hh, cc, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((1,), lambda bb, hh, cc, rep=rep: (hh,)),
            pl.BlockSpec((1, 1, 1, chunk, n),
                         lambda bb, hh, cc, rep=rep: (bb, hh // rep, cc, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, n),
                         lambda bb, hh, cc, rep=rep: (bb, hh // rep, cc, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bb, hh, cc: (bb, hh, cc, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bb, hh, cc: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, chunk, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dth, A.astype(jnp.float32), bh, ch)
    y = y.reshape(b, h, l, p).transpose(0, 2, 1, 3)
    return y, state
