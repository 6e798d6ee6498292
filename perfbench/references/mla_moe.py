"""Plain float32 reference of DeepSeek-V2's decoder (latent attention and
sparse experts), as its published modelling code computes it: RMSNorm;
multi-head latent attention in its decompressed form (the query through
its low-rank path ``wq_a``, RMSNorm, ``wq_b``; the keys' and values'
latent through ``wkv_a`` and RMSNorm, expanded per head by ``wkv_b``, and
one rotary key shared by the heads); YaRN rotary frequencies and softmax
scale; a dense SwiGLU MLP in the first ``first_k_dense_replace`` layers
and a mixture of experts after them (``MoEGate``: a float32 softmax over
the router's experts, group-limited top-k, unnormalised weights times
``routed_scaling_factor``; SwiGLU experts; shared experts on every token);
a final RMSNorm and the output head. Every matmul runs at
``Precision.HIGHEST``.

It holds the same share of the experts as the configuration: the router
scores all ``router_experts`` experts, and only the ``n_routed_experts``
held here (experts ``[0, n_routed_experts)``) add their part. It runs
layer by layer over a list of token sequences, regenerating each layer's
weights from the seed (``perfbench/weights.py``: the program's leaf names,
expert ``i`` of a stack with ``i`` folded in), so that the whole model
never sits on the device at float32. ``control=True`` computes the same
model with the operands of every linear layer, the router's and the
experts' included, rounded to float8 (``references/gqa.py``).

Departures from the published code, none of which changes what is
computed on these weights:

- rotary layout: the published ``apply_rotary_pos_emb`` first moves the
  interleaved pairs of ``q_pe`` and ``k_pe`` to two halves, then rotates
  halves. Here the halves are rotated as the weights lay them out, which is
  the published computation on a fixed permutation of the rotary columns
  of ``wq_b`` and ``wkv_a``: random weights are no less random permuted;
- the untied head is a ``(hidden, vocab)`` matrix (the published
  ``lm_head.weight`` transposed), drawn at the program's padded width of
  ``vocab`` rounded up to 512 and cut to ``vocab``;
- norm scales are ones and are left out, as the weight rule makes them.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights
from perfbench.references.gqa import HI, PAD, Q_BLOCK, _fp8, _linear, _rms

__all__ = ["logits", "routing", "widest_gaps", "moe"]

HEAD_PAD = 512       # the program pads its head's vocabulary to this
# the quantile of a sequence's per-token gaps that ``widest_gaps`` reports
GAP_QUANTILE = 90


def yarn_inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` frequencies (plain
    RoPE where ``scaling`` is None), float32."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if scaling is None:
        return extra.astype(np.float32)
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(corr(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(scaling.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inter = extra / factor
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, pos, inv, cs):
    """x: (T, H, d) rotated by halves at positions ``pos``."""
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _route(x, router, n_held, *, m: Tuple, control: bool):
    """``MoEGate`` over (T, d) normed activations: (T, held experts) each
    token's weight for each expert held here, and per token its held
    assignments and its routing margin, the nearest tie that decides a
    held expert: between the k-th and (k+1)-th expert where either is
    held, or the last kept and the first dropped group where either holds
    one, as a share of the larger score (1 where no tie decides one)."""
    k, groups, top_groups, norm, scale, first = m
    t = x.shape[0]
    n_router = router.shape[1]
    scores = jax.nn.softmax(_linear(x, router, control), axis=-1)
    is_held = (jnp.arange(n_router) >= first) & \
        (jnp.arange(n_router) < first + n_held)
    margin = jnp.ones(t)
    if groups > 1:
        best = scores.reshape(t, groups, -1).max(-1)
        top, keep = jax.lax.top_k(best, top_groups + 1)
        holds = is_held.reshape(groups, -1).any(-1)[keep]
        near = (top[:, -2] - top[:, -1]) / top[:, -2]
        margin = jnp.where(holds[:, -2] | holds[:, -1], near, margin)
        gmask = jnp.zeros((t, groups), bool).at[
            jnp.arange(t)[:, None], keep[:, :-1]].set(True)
        scores = jnp.where(jnp.repeat(gmask, n_router // groups, -1),
                           scores, 0.0)
    wt, idx = jax.lax.top_k(scores, k + 1)
    near = (wt[:, -2] - wt[:, -1]) / wt[:, -2]
    margin = jnp.minimum(margin, jnp.where(
        is_held[idx[:, -2]] | is_held[idx[:, -1]], near, 1.0))
    wt, idx = wt[:, :k], idx[:, :k]
    if norm:
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
    else:
        wt = wt * scale
    on = idx[..., None] == first + jnp.arange(n_held)
    held = (on * wt[..., None]).sum(1)
    return held, on.sum((1, 2)), margin


def moe(x, w, *, m: Tuple, control: bool, stats: bool = False):
    """The held experts' part and the shared experts of one MoE layer over
    (T, d) normed activations. ``m`` = (top_k, n_group, topk_group,
    norm_topk_prob, routed_scaling_factor, first held expert). With
    ``stats``, also each token's held assignments and routing margin
    (:func:`_route`)."""
    held, assigned, margin = _route(x, w["router"], w["gate"].shape[0],
                                    m=m, control=control)

    def one(y, e):
        g, u, dn, we = e
        h = jax.nn.silu(_linear(x, g, control)) * _linear(x, u, control)
        return y + we[:, None] * _linear(h, dn, control), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["gate"], w["up"], w["down"], held.T))
    if "shared_gate" in w:
        y = y + _linear(jax.nn.silu(_linear(x, w["shared_gate"], control)) *
                        _linear(x, w["shared_up"], control),
                        w["shared_down"], control)
    return (y, (assigned, margin)) if stats else y


@partial(jax.jit, static_argnames=("m", "control"))
def _layer(x, w, *, m: Tuple, control: bool):
    """One layer over (T, d) activations of one sequence: (x, routing
    statistics of an MoE layer as :func:`moe` gives them, or None)."""
    (h, r, dn, dr, dv, eps, scale, inv, cs, moe_m) = m
    t = x.shape[0]
    pos = jnp.arange(t)
    inv = jnp.asarray(inv, jnp.float32)
    hn = _rms(x, eps)
    if "wq_a" in w:
        q = _linear(_rms(_linear(hn, w["wq_a"], control), eps), w["wq_b"],
                    control)
    else:
        q = _linear(hn, w["wq"], control)
    q = q.reshape(t, h, dn + dr)
    kv = _linear(hn, w["wkv_a"], control)
    ckv = _rms(kv[:, :r], eps)
    k_pe = _rope(kv[:, None, r:], pos, inv, cs)            # (T, 1, dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, inv, cs)], -1)
    kvb = _linear(ckv, w["wkv_b"], control).reshape(t, h, dn + dv)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_pe, (t, h, dr))], -1)
    v = kvb[..., dn:]

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,shd->hqs", qb, k, precision=HI) * scale
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("hqs,shd->qhd", p, v,
                          precision=HI).reshape(Q_BLOCK, h * dv)

    ctx = jax.lax.map(block, jnp.arange(t // Q_BLOCK)).reshape(t, h * dv)
    x = x + _linear(ctx, w["wo"], control)
    hn = _rms(x, eps)
    if moe_m is None:
        mlp = jax.nn.silu(_linear(hn, w["gate"], control)) * \
            _linear(hn, w["up"], control)
        return x + _linear(mlp, w["down"], control), None
    y, stats = moe(hn, w, m=moe_m, control=control, stats=True)
    return x + y, stats


@partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, head, *, eps: float, control: bool):
    xn = _rms(x, eps)
    if control:
        xn, head = _fp8(xn, -1), _fp8(head, 0)
    return jnp.dot(xn, head, precision=HI)


@partial(jax.jit, static_argnames=("name", "rows", "cols"))
def _matrix(key, layer, *, name: str, rows: int, cols: int):
    return weights.matrix(key, name, layer, rows, cols, jnp.float32)


@partial(jax.jit, static_argnames=("name", "shape"))
def _stack(key, layer, *, name: str, shape: Tuple[int, ...]):
    return weights.stack(key, name, layer, shape, jnp.float32)


def _layer_weights(model: Dict, key, layer: int) -> Dict:
    """The program's weights of ``layer`` (its leaf names: segment 0 holds
    the leading dense layers, segment 1 the MoE layers)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    r, qr = model["kv_lora_rank"], model.get("q_lora_rank") or 0
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    first_moe = model.get("first_k_dense_replace", 0) \
        if "n_routed_experts" in model else model["num_hidden_layers"]
    dense = layer < first_moe
    seg = "segments/1/" if 0 < first_moe <= layer else "segments/0/"
    shapes = {"attn/wkv_a/w": (d, r + dr), "attn/wkv_b/w": (r, h * (dn + dv)),
              "attn/wo/w": (h * dv, d)}
    if qr:
        shapes.update({"attn/wq_a/w": (d, qr),
                       "attn/wq_b/w": (qr, h * (dn + dr))})
    else:
        shapes["attn/wq/w"] = (d, h * (dn + dr))
    if dense:
        ff = model["intermediate_size"]
        shapes.update({"mlp/gate/w": (d, ff), "mlp/up/w": (d, ff),
                       "mlp/down/w": (ff, d)})
    else:
        ns = (model.get("n_shared_experts") or 0) * \
            model["moe_intermediate_size"]
        shapes["mlp/router/w"] = (d, model.get("router_experts",
                                               model["n_routed_experts"]))
        if ns:
            shapes.update({"mlp/shared/gate/w": (d, ns),
                           "mlp/shared/up/w": (d, ns),
                           "mlp/shared/down/w": (ns, d)})
    w = {name.split("/", 1)[1].replace("/w", "").replace("/", "_"):
         _matrix(key, layer, name=seg + name, rows=rc[0], cols=rc[1])
         for name, rc in shapes.items()}
    if not dense:
        n, f = model["n_routed_experts"], model["moe_intermediate_size"]
        for name, shape in (("gate", (n, d, f)), ("up", (n, d, f)),
                            ("down", (n, f, d))):
            w[name] = _stack(key, layer, name=seg + "mlp/" + name,
                             shape=shape)
    return w


def _statics(model: Dict, dense: bool) -> Tuple:
    r = model["kv_lora_rank"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    scaling = model.get("rope_scaling")
    inv = yarn_inv_freq(dr, float(model["rope_theta"]), scaling)
    scale = 1.0 / math.sqrt(dn + dr)
    cs = 1.0
    if scaling is not None:
        f = float(scaling["factor"])
        cs = mscale(f, scaling.get("mscale", 1)) / \
            mscale(f, scaling.get("mscale_all_dim", 0))
        if scaling.get("mscale_all_dim"):
            scale *= mscale(f, scaling["mscale_all_dim"]) ** 2
    moe_m = None
    if not dense:
        grouped = model.get("topk_method") == "group_limited_greedy"
        moe_m = (model["num_experts_per_tok"],
                 (model.get("n_group") or 1) if grouped else 1,
                 (model.get("topk_group") or 1) if grouped else 1,
                 bool(model.get("norm_topk_prob", True)),
                 float(model.get("routed_scaling_factor", 1.0)), 0)
    return (model["num_attention_heads"], r, dn, dr, dv,
            float(model["rms_norm_eps"]), scale, tuple(inv.tolist()), cs,
            moe_m)


def _forward(model: Dict, seed: int, seqs: Sequence[np.ndarray],
             control: bool):
    """The decoder over each sequence, padded to a multiple of ``PAD``
    tokens after its real ones (causal attention keeps the padding out of
    every real row): (embedding table, last activations per sequence,
    routing statistics per MoE layer and sequence)."""
    d, vocab = model["hidden_size"], model["vocab_size"]
    key = weights.base_key(seed)
    table = _matrix(key, 0, name="embed/table", rows=vocab, cols=d)
    xs = []
    for seq in seqs:
        toks = np.zeros(-(-len(seq) // PAD) * PAD, np.int32)
        toks[:len(seq)] = seq
        xs.append(jnp.take(table, jnp.asarray(toks), axis=0))
    stats = []
    for layer in range(model["num_hidden_layers"]):
        w = _layer_weights(model, key, layer)
        m = _statics(model, "router" not in w)
        out = [_layer(x, w, m=m, control=control) for x in xs]
        xs = [x for x, _ in out]
        if out and out[0][1] is not None:
            stats.append([tuple(np.asarray(a)[:len(seq)] for a in st)
                          for (_, st), seq in zip(out, seqs)])
        del w
    return table, xs, stats


def routing(model: Dict, seed: int, seqs: Sequence[np.ndarray], *,
            control: bool = False) -> List[List[Tuple[np.ndarray,
                                                      np.ndarray]]]:
    """Per MoE layer and sequence, at each of its positions: the token's
    (token, expert) assignments to the experts held here, and its routing
    margin (:func:`_route`: near 0 where a rounding can flip which held
    experts it reaches)."""
    return _forward(model, seed, seqs, control)[2]


def logits(model: Dict, seed: int, seqs: Sequence[np.ndarray],
           firsts: Sequence[int], *,
           control: bool = False) -> List[np.ndarray]:
    """Float32 logits over the vocabulary at positions ``[first, len(seq))``
    of each token sequence (the positions whose next token was served)."""
    d, vocab = model["hidden_size"], model["vocab_size"]
    key = weights.base_key(seed)
    table, xs, _ = _forward(model, seed, seqs, control)
    if model.get("tie_word_embeddings", False):
        head = table.T
    else:
        head = _matrix(key, 0, name="lm_head/w", rows=d,
                       cols=-(-vocab // HEAD_PAD) * HEAD_PAD)[:, :vocab]
    eps = float(model["rms_norm_eps"])
    return [np.asarray(_head(x[first:len(seq)], head, eps=eps,
                             control=control))
            for x, seq, first in zip(xs, seqs, firsts)]


def widest_gaps(ref_rows: Sequence[np.ndarray],
                tokens: Sequence[Sequence[int]]) -> List[float]:
    """For each sequence, the gap by which a chosen token's reference logit
    lies below the reference's best at that position, at the
    ``GAP_QUANTILE``-th percentile of the sequence's tokens (the dense
    family's ``references/gqa.py`` takes the largest).

    Under sparse experts and a vocabulary of 102,400 the largest gap is no
    measure of an implementation: a token whose routing lies within
    rounding of a tie (the 6th and 7th expert, the 3rd and 4th group) is
    routed either way by two correct implementations at different
    precisions, and at DeepSeek-V2's widths (weights times 16,
    unnormalised) the two routings move that position's logits by up to
    8. On a v5e the program's served tokens are the reference's best at
    75-84% of positions; the rest lie 0.04-0.35 below it at the 90th
    percentile of each sequence and 1.5-5.2 at the largest, while the
    float8 control lies 2.9-3.5 below at the 90th percentile (4 sequences
    in each of 8 runs, the control in 4). The program's large gaps sit at near ties: where
    its gap passes 1, the reference's routing margin (:func:`routing`) is
    0.015 at the median, against 0.094 over all positions (16 sequences
    of 4 runs); the control's large gaps sit at ordinary margins
    (0.06-0.10). A wrong equation, or a lower precision, moves most
    positions.
    """
    gaps = []
    for rows, toks in zip(ref_rows, tokens):
        toks = np.asarray(toks)
        if not len(toks):
            gaps.append(0.0)
            continue
        gap = rows.max(-1) - rows[np.arange(len(toks)), toks]
        gaps.append(float(np.percentile(gap, GAP_QUANTILE)))
    return gaps
