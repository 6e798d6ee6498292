"""Mixture-of-Experts: DeepSeek-V2's routing, an expert share, and a
dropless grouped computation over the routed (token, expert) pairs.

* **Routing** (:func:`route`) is DeepSeek-V2's ``MoEGate``: a float32
  softmax over the router's ``n_router`` logits; under group-limited
  routing each group's score is the best of its experts and only the best
  ``router_topk_groups`` groups stay; the top ``moe_top_k`` experts among
  those; their weights normalised, or left as scores times
  ``routed_scale``.
* **Expert share**: the layer holds experts ``[expert_offset,
  expert_offset + num_experts)`` of the router's ``n_router``. It routes
  over all of them and adds only its own experts' part; what the absent
  experts would add is left to the devices that hold them. The shared
  experts (a dense SwiGLU of width ``num_shared_experts x moe_d_ff``) are
  added on every device.
* **Dropless and grouped** (:func:`moe_forward`): the held assignments are
  sorted by expert and the experts run as grouped matmuls over the
  per-expert group sizes, so each expert computes only the tokens routed
  to it and no token is dropped, whatever its batch-mates. On a TPU the
  grouped matmul is the Pallas ``megablox`` kernel that ships with JAX
  (named ``gmm`` in a profile); elsewhere ``jax.lax.ragged_dot``.

The expert-parallel training mesh path (:meth:`MeshPolicy.moe_apply`)
replaces :func:`moe_forward` with a capacity dispatch of its own; it
routes with :func:`route` too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import NO_POLICY, ShardingPolicy, dense_init, mlp, mlp_init

# (rows, contraction, output) tile of the grouped matmul kernel: a row tile
# of 128 serves an expert's handful of decode tokens in one pass, and wide
# contraction and output tiles keep the grid short where the step is bound
# by reading the expert weights
GMM_TILE = (128, 512, 1024)


def moe_init(cfg, key, dtype):
    ks = jax.random.split(key, 5)
    d, e, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, cfg.n_router, jnp.float32),
        "gate": jax.random.normal(ks[1], (e, d, ff), dtype) * scale,
        "up": jax.random.normal(ks[2], (e, d, ff), dtype) * scale,
        "down": jax.random.normal(ks[3], (e, ff, d), dtype) * (1.0 / jnp.sqrt(ff)),
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.num_shared_experts * ff, dtype,
                               gated=True)
    return p


def route(cfg, router_w, x):
    """x: (..., D) -> (weights (..., k) f32, experts (..., k) int32 over the
    router's ``n_router``, scores (..., n_router) f32)."""
    scores = jax.nn.softmax(x.astype(jnp.float32) @ router_w, axis=-1)
    k, g = cfg.moe_top_k, cfg.router_groups
    picked = scores
    if g > 1:
        lead, e = scores.shape[:-1], scores.shape[-1]
        best = scores.reshape(*lead, g, e // g).max(-1)        # (..., G)
        _, top_groups = jax.lax.top_k(best, cfg.router_topk_groups)
        kept = jax.nn.one_hot(top_groups, g, dtype=jnp.bool_).any(-2)
        kept = jnp.repeat(kept, e // g, axis=-1)
        picked = jnp.where(kept, scores, 0.0)
    weights, experts = jax.lax.top_k(picked, k)
    if cfg.norm_topk:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    else:
        weights = weights * cfg.routed_scale
    return weights, experts, scores


def _tile(dim: int, cap: int) -> int:
    """The widest multiple of 128 up to ``cap`` that divides ``dim`` (the
    whole of a dimension narrower than one)."""
    for t in range(cap, 127, -128):
        if dim % t == 0:
            return t
    return min(dim, cap)


def _gmm(lhs, rhs, sizes):
    """Grouped matmul: rows ``[sum(sizes[:i]), sum(sizes[:i+1]))`` of
    ``lhs`` times ``rhs[i]``; rows past ``sum(sizes)`` are undefined."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        m = lhs.shape[0]
        tile = (GMM_TILE[0], _tile(lhs.shape[1], GMM_TILE[1]),
                _tile(rhs.shape[2], GMM_TILE[2]))
        pad = -m % tile[0]
        out = gmm(jnp.pad(lhs, ((0, pad), (0, 0))), rhs, sizes,
                  lhs.dtype, tile)
        return out[:m]
    return jax.lax.ragged_dot(lhs, rhs, sizes)


def grouped_experts(cfg, p, x, weights, experts, valid=None):
    """The held experts' part of the layer, dropless. x: (T, D); weights,
    experts: (T, k) from :func:`route`; valid: (T,) bool, the rows that are
    tokens (None: every row), the others (a chunk's padding, idle decode
    slots) neither computed nor counted and their output zero. Returns
    ((T, D), assigned, hit): the (token, expert) pairs routed to experts
    held here and how many distinct held experts they reached."""
    t, k = experts.shape
    n = cfg.num_experts
    local = experts.reshape(-1) - cfg.expert_offset
    held = (local >= 0) & (local < n)
    if valid is not None:
        held = held & jnp.repeat(valid, k)
    # held pairs sorted by expert, the others after them
    order = jnp.argsort(jnp.where(held, local, n), stable=True)
    sizes = jnp.bincount(jnp.where(held, local, n), length=n + 1)[:n]
    sizes = sizes.astype(jnp.int32)
    tok = order // k
    xs = x[tok]
    h = jax.nn.silu(_gmm(xs, p["gate"], sizes)) * _gmm(xs, p["up"], sizes)
    out = _gmm(h, p["down"], sizes)
    # rows past the held ones are no group's: zero, not 0 * undefined
    live = jnp.arange(t * k) < sizes.sum()
    out = jnp.where(live[:, None], out, 0).astype(jnp.float32)
    # back to (token, choice) order, weighted, summed over the choices
    out = out[jnp.argsort(order)].reshape(t, k, -1)
    w = jnp.where(held, weights.reshape(-1), 0.0).reshape(t, k)
    y = (out * w[..., None]).sum(1)
    return y.astype(x.dtype), sizes.sum(), jnp.count_nonzero(sizes)


def moe_layer(cfg, p, x, policy: ShardingPolicy = NO_POLICY, valid=None):
    """x: (B, S, D) -> (y (B, S, D), (assigned, hit) int32 scalars, (experts
    (B*S, k), scores (B*S, n_router))): the held routed experts' part plus
    the shared experts, dropless. ``valid``: (B, S) bool, the rows that are
    tokens, as :func:`grouped_experts` takes them."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    weights, experts, scores = route(cfg, p["router"]["w"], xt)
    y, assigned, hit = grouped_experts(
        cfg, p, xt, weights, experts,
        None if valid is None else valid.reshape(b * s))
    y = y.reshape(b, s, d)
    if "shared" in p:
        y = y + mlp(p["shared"], x, policy)
    return y, (assigned.astype(jnp.int32), hit.astype(jnp.int32)), \
        (experts, scores)


def moe_forward(cfg, p, x, *, policy: ShardingPolicy = NO_POLICY,
                return_aux: bool = False):
    """x: (B, S, D) -> (B, S, D) [+ aux load-balance loss]. Under a mesh
    policy the expert-parallel ``shard_map`` path
    (:meth:`MeshPolicy.moe_apply`) replaces this function."""
    if hasattr(policy, "moe_apply"):
        out = policy.moe_apply(cfg, p, x, return_aux)
        if out is not None:
            return out
    y, _, (experts, scores) = moe_layer(cfg, p, x, policy)
    if not return_aux:
        return y
    # Switch-style load-balance loss over the router's experts
    e = cfg.n_router
    frac_tokens = jnp.mean(jax.nn.one_hot(experts[:, 0], e,
                                          dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(frac_tokens * jnp.mean(scores, axis=0))
    return y, aux
