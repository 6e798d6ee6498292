"""Plain float32 reference of a dense pre-norm GQA decoder (Llama/Mistral
layout): RMSNorm, rotary positions (half-split), grouped-query softmax
attention with an optional sliding window, SwiGLU MLP, final RMSNorm and
an unembedding tied to the embedding table. Every matmul runs at
``Precision.HIGHEST``, so on a TPU it is float32 and not bfloat16 passes.

It runs layer by layer over a list of token sequences, regenerating each
layer's weights from the seed, so that the whole model never sits on the
device at float32. ``control=True`` computes the same model with the
operands of every linear layer rounded to float8 (e4m3, scaled per row of
activations and per output channel of weights): the lower-precision path
that the benchmark's comparison has to refuse.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
PAD = 2048          # sequences are padded to a multiple of this
F8_MAX = 448.0      # largest finite float8_e4m3fn

LAYER_LEAVES = {"wq": "attn/wq/w", "wk": "attn/wk/w", "wv": "attn/wv/w",
                "wo": "attn/wo/w", "gate": "mlp/gate/w", "up": "mlp/up/w",
                "down": "mlp/down/w"}


def _fp8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, control: bool):
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None, None].astype(jnp.float32) * inv
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("m", "control"))
def _layer(x, w, *, m: Tuple, control: bool):
    """One layer over (T, d) activations of one sequence."""
    h, hkv, dh, eps, theta, window = m
    t = x.shape[0]
    pos = jnp.arange(t)
    hn = _rms(x, eps)
    q = _rope(_linear(hn, w["wq"], control).reshape(t, h, dh), pos, theta)
    k = _rope(_linear(hn, w["wk"], control).reshape(t, hkv, dh), pos, theta)
    v = _linear(hn, w["wv"], control).reshape(t, hkv, dh)
    g = h // hkv

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        qb = qb.reshape(Q_BLOCK, hkv, g, dh)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / np.sqrt(dh)
        mask = pos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= pos[None, :] > qpos[:, None] - window
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)
        return o.reshape(Q_BLOCK, h * dh)

    ctx = jax.lax.map(block, jnp.arange(t // Q_BLOCK)).reshape(t, h * dh)
    x = x + _linear(ctx, w["wo"], control)
    hn = _rms(x, eps)
    mlp = jax.nn.silu(_linear(hn, w["gate"], control)) * \
        _linear(hn, w["up"], control)
    return x + _linear(mlp, w["down"], control)


@partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, table, *, eps: float, control: bool):
    xn = _rms(x, eps)
    if control:
        xn, table = _fp8(xn, -1), _fp8(table, -1)
    return jnp.dot(xn, table.T, precision=HI)


@partial(jax.jit, static_argnames=("name", "rows", "cols"))
def _matrix(key, layer, *, name: str, rows: int, cols: int):
    return weights.matrix(key, name, layer, rows, cols, jnp.float32)


def _dims(model: Dict):
    d = model["hidden_size"]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = model.get("head_dim") or d // h
    return d, h, hkv, dh


def logits(model: Dict, seed: int, seqs: Sequence[np.ndarray],
           firsts: Sequence[int], *,
           control: bool = False) -> List[np.ndarray]:
    """Float32 logits over the vocabulary at positions ``[first, len(seq))``
    of each token sequence (the positions whose next token was served).
    Each sequence is padded to a multiple of ``PAD`` tokens; padding comes
    after the real tokens, so causal attention keeps it out of every row
    read."""
    d, h, hkv, dh = _dims(model)
    ff, vocab = model["intermediate_size"], model["vocab_size"]
    m = (h, hkv, dh, float(model["rms_norm_eps"]),
         float(model["rope_theta"]), model.get("sliding_window"))
    key = weights.base_key(seed)
    table = _matrix(key, 0, name="embed/table", rows=vocab, cols=d)
    xs = []
    for seq in seqs:
        toks = np.zeros(-(-len(seq) // PAD) * PAD, np.int32)
        toks[:len(seq)] = seq
        xs.append(jnp.take(table, jnp.asarray(toks), axis=0))
    shapes = {"wq": (d, h * dh), "wk": (d, hkv * dh), "wv": (d, hkv * dh),
              "wo": (h * dh, d), "gate": (d, ff), "up": (d, ff),
              "down": (ff, d)}
    for layer in range(model["num_hidden_layers"]):
        w = {k: _matrix(key, layer, name="segments/0/" + LAYER_LEAVES[k],
                        rows=r, cols=c) for k, (r, c) in shapes.items()}
        xs = [_layer(x, w, m=m, control=control) for x in xs]
        del w
    out = []
    for x, seq, first in zip(xs, seqs, firsts):
        rows = x[first:len(seq)]
        out.append(np.asarray(_head(rows, table, eps=m[3], control=control)))
    return out


def widest_gaps(ref_rows: Sequence[np.ndarray],
                tokens: Sequence[Sequence[int]]) -> List[float]:
    """For each sequence, the widest gap by which a chosen token's
    reference logit lies below the reference's best at that position."""
    gaps = []
    for rows, toks in zip(ref_rows, tokens):
        toks = np.asarray(toks)
        best = rows.max(-1)
        got = rows[np.arange(len(toks)), toks]
        gaps.append(float((best - got).max()) if len(toks) else 0.0)
    return gaps
