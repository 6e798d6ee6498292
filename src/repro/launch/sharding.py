"""Sharding rules: parameters, inputs, caches, and the activation policy.

Strategy (DESIGN.md §5):

* **weights** — 2-D sharded: penultimate dim over ``data`` (FSDP-style),
  last dim over ``model`` (tensor parallel); stacked-layer leading dims
  replicated. MoE expert stacks ``(E, d, ff)`` shard E over ``model``
  (expert parallelism) and d over ``data``.
* **train/prefill activations** — batch over (pod×)data; heads/ffn/vocab
  over ``model`` when divisible.
* **decode caches** — batch over data when divisible; the KV *sequence* axis
  over ``model`` (and over data too when batch==1, e.g. ``long_500k``) —
  this is DistAttention as the primary decode sharding mechanism.

Every rule checks divisibility and degrades to replication rather than
failing — heads counts like hymba's 25 do not divide 16 and simply stay
unsharded on that axis (GSPMD still partitions the surrounding matmuls).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ArchConfig, InputShape
from repro.launch.mesh import data_axes
from repro.models.layers import ShardingPolicy


def _div(n: int, size: int) -> bool:
    return size > 0 and n % size == 0


class MeshPolicy(ShardingPolicy):
    """Activation sharding constraints, divisibility-guarded."""

    def __init__(self, mesh, cfg: ArchConfig, *, decode: bool = False,
                 megatron: bool = True):
        """``megatron``: inter-block activations replicated in d_model +
        explicit transient FSDP weight gathers (perf iterations 2+3). False
        reverts to the paper-faithful baseline layout (activations d@model,
        weights resident 2-D sharded)."""
        self.mesh = mesh
        self.cfg = cfg
        self.decode = decode
        self.megatron = megatron
        self.dp = data_axes(mesh)
        self.dp_size = 1
        for a in self.dp:
            self.dp_size *= mesh.shape[a]
        self.mp = "model" if "model" in mesh.axis_names else None
        self.mp_size = mesh.shape["model"] if self.mp else 1
        # GShard grouped MoE dispatch: one group per data shard
        self.moe_groups = self.dp_size

    # -- expert-parallel MoE via shard_map -----------------------------------
    def moe_apply(self, cfg, p, x, return_aux: bool):
        """Expert-parallel MoE (InfiniteLLM-era standard mapping): tokens are
        data-sharded and replicated over ``model``; each model shard owns
        E/mp whole experts, scatters its tokens locally (masked, no cross-
        shard scatter), runs its experts, and the combine is a single psum
        over ``model`` — the jax-native equivalent of the all-to-all +
        expert-compute + all-to-all pipeline, with zero GSPMD guesswork."""
        from functools import partial
        from repro.models import moe as moe_mod
        from repro.models.layers import mlp

        if not _div(cfg.num_experts, self.mp_size) or self.mp is None:
            return None  # fall back to the jnp path
        b, s, d = x.shape
        e, k = cfg.num_experts, cfg.moe_top_k
        e_loc = e // self.mp_size
        t = b * s
        t_loc = max(t // self.dp_size, 1)
        if t % self.dp_size:
            return None
        cap = max(8, int(t_loc * k * cfg.capacity_factor / e + 8) // 8 * 8)
        dpa = tuple(self.dp)

        def local(xt, router_w, gate_w, up_w, down_w):
            # xt: (T_loc, d); expert weights come in (e_loc, d/dp, f) —
            # FSDP-gather the contraction dim (reduce-scatter in backward)
            gate_w = jax.lax.all_gather(gate_w, dpa, axis=1, tiled=True)
            up_w = jax.lax.all_gather(up_w, dpa, axis=1, tiled=True)
            down_w = jax.lax.all_gather(down_w, dpa, axis=1, tiled=True)
            midx = jax.lax.axis_index(self.mp)
            logits = xt.astype(jnp.float32) @ router_w  # (T_loc, E) full E
            probs = jax.nn.softmax(logits, axis=-1)
            topv, topi = jax.lax.top_k(probs, k)
            topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
            # position within each expert's capacity (over full E, so every
            # shard agrees on positions; cheap: (T_loc*k, E) local cumsum)
            onehot = jax.nn.one_hot(topi, e, dtype=jnp.int32)
            flat = onehot.reshape(t_loc * k, e)
            pos_e = (jnp.cumsum(flat, axis=0) - flat).reshape(t_loc, k, e)
            pos = (pos_e * onehot).sum(-1)
            keep = pos < cap
            # my experts: [midx*e_loc, (midx+1)*e_loc)
            local_e = topi - midx * e_loc
            mine = (local_e >= 0) & (local_e < e_loc) & keep
            eidx = jnp.where(mine, local_e, e_loc)  # ->drop
            pidx = jnp.where(mine, pos, cap)
            contrib = jnp.where(mine[..., None], xt[:, None, :], 0)
            disp = jnp.zeros((e_loc, cap, d), x.dtype).at[
                eidx, pidx].add(contrib, mode="drop")
            g_ = jnp.einsum("ecd,edf->ecf", disp, gate_w)
            u_ = jnp.einsum("ecd,edf->ecf", disp, up_w)
            h = jax.nn.silu(g_) * u_
            out = jnp.einsum("ecf,efd->ecd", h, down_w)
            gathered = out[jnp.where(mine, local_e, 0),
                           jnp.where(mine, pos, 0)]  # (T_loc, k, d)
            w = (topv * mine).astype(x.dtype)
            y_part = (gathered * w[..., None]).sum(1)  # (T_loc, d)
            y = jax.lax.psum(y_part, self.mp)
            # load-balance aux (identical across mp; per-dp-shard value)
            frac_tok = jnp.mean(jax.nn.one_hot(topi[:, 0], e,
                                               dtype=jnp.float32), axis=0)
            aux = e * jnp.sum(frac_tok * jnp.mean(probs, axis=0))
            return y, aux[None]

        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(dpa, None), P(), P("model", dpa, None),
                      P("model", dpa, None), P("model", dpa, None)),
            out_specs=(P(dpa, None), P(dpa)),
        )
        y, aux = fn(x.reshape(t, d), p["router"]["w"].astype(jnp.float32),
                    p["gate"], p["up"], p["down"])
        y = y.reshape(b, s, d)
        if "shared" in p:
            y = y + mlp(p["shared"], x, self)
        aux = jnp.mean(aux)
        return (y, aux) if return_aux else y

    def _c(self, x, spec):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def prefers_flat_heads(self, h: int, hkv: int) -> bool:
        """True when flat-H sharding works but grouped Hkv sharding doesn't
        (e.g. 96 heads / 8 kv heads on a 16-way model axis)."""
        return (self.megatron and _div(h, self.mp_size)
                and not _div(hkv, self.mp_size))

    def param(self, w, kind: str):
        """Explicit FSDP weight gather (perf iteration 3): weights are
        *stored* (d_in@data, d_out@model); before each matmul they are
        gathered over `data` to a transient (d_in, d_out@model) — the
        Megatron column/row-parallel layout. Autodiff turns the gather into
        the grad reduce-scatter. Decode keeps weights resident (gathering
        per generated token would swamp the step)."""
        if kind != "matmul_weight" or w.ndim < 2 or self.decode \
                or not self.megatron:
            return w
        if _div(w.shape[-1], self.mp_size):
            return self._c(w, P(*(None,) * (w.ndim - 1), self.mp))
        return self._c(w, P(*(None,) * w.ndim))

    def act(self, x, kind: str):
        cfg, dp, mp = self.cfg, self.dp, self.mp
        b = x.shape[0]
        batch_ax = dp if _div(b, self.dp_size) else None
        if kind == "act_bsd":
            # Megatron layout: the d_model axis of inter-block activations is
            # REPLICATED over `model` — sharding it (d@mp) made GSPMD gather
            # x before every matmul whose weight holds d_in@data (17 GB/layer
            # on mistral prefill). Per-layer FSDP weight gathers are ~6x
            # cheaper and transient under the layer scan. (Perf iteration 2.)
            if self.megatron:
                return self._c(x, P(batch_ax, *(None,) * (x.ndim - 1)))
            if _div(x.shape[-1], self.mp_size):
                return self._c(x, P(batch_ax, *(None,) * (x.ndim - 2), mp))
            return self._c(x, P(batch_ax, *(None,) * (x.ndim - 1)))
        if kind in ("ffn_bsf",):
            if _div(x.shape[-1], self.mp_size):
                return self._c(x, P(batch_ax, None, mp))
            return x
        if kind == "logits_bsv":
            if _div(x.shape[-1], self.mp_size):
                return self._c(x, P(batch_ax, *(None,) * (x.ndim - 2), mp))
            return x
        if kind == "heads_bshd":
            h = x.shape[2]
            if _div(h, self.mp_size):
                return self._c(x, P(batch_ax, None, mp, None))
            return self._c(x, P(batch_ax, None, None, None))
        if kind == "kv_bshd":
            h = x.shape[2]
            if _div(h, self.mp_size):
                return self._c(x, P(batch_ax, None, mp, None))
            if _div(x.shape[1], self.mp_size):
                # non-divisible KV heads: shard the KV sequence (micro-
                # attention); scores/probs inherit s@model coherently
                return self._c(x, P(batch_ax, mp, None, None))
            return self._c(x, P(batch_ax, None, None, None))
        if kind in ("kvcache_bskd", "mlacache_bsr"):
            # decode: sequence axis over model (DistAttention); over
            # data too when the batch axis cannot absorb it (B==1)
            seq_ax = mp if batch_ax else (tuple(dp) + (mp,) if mp else dp)
            sdim = x.shape[1]
            size = self.mp_size * (1 if batch_ax else self.dp_size)
            if not _div(sdim, size):
                seq_ax = mp if _div(sdim, self.mp_size) else None
            if x.ndim == 4:
                return self._c(x, P(batch_ax, seq_ax, None, None))
            return self._c(x, P(batch_ax, seq_ax, None))
        if kind in ("expert_gecd", "expert_gecf"):
            # grouped dispatch (G, E, cap, D): groups over data (they ARE the
            # data shards), experts over model (expert parallelism)
            gax = dp if _div(x.shape[0], self.dp_size) else None
            eax = mp if _div(x.shape[1], self.mp_size) else None
            return self._c(x, P(gax, eax, None, None))
        if kind == "kvrep_bshd":  # broadcast KV, flat heads (iteration 4)
            return self._c(x, P(batch_ax, None, mp, None))
        if kind == "scores_bchs":
            return self._c(x, P(batch_ax, None, mp, None))
        if kind == "scores_bchgs":
            # attention scores (B, C, Hkv, G, Skv): prefer KV-head sharding;
            # non-divisible head counts fall back to KV-sequence sharding
            # (micro-attention; measured better than query-chunk sharding —
            # see EXPERIMENTS.md §Perf iteration 1, refuted)
            if _div(x.shape[2], self.mp_size):
                return self._c(x, P(batch_ax, None, mp, None, None))
            if _div(x.shape[-1], self.mp_size):
                return self._c(x, P(batch_ax, None, None, None, mp))
            return self._c(x, P(batch_ax, None, None, None, None))
        if kind == "ssm_bshp":
            if x.ndim == 4 and _div(x.shape[2], self.mp_size):
                return self._c(x, P(batch_ax, None, mp, None))
            return x
        return x


# ---------------------------------------------------------------------------
# parameter / input / cache shardings
# ---------------------------------------------------------------------------

def param_spec(path_keys, leaf, mesh, cfg: ArchConfig) -> P:
    dp = data_axes(mesh)
    dsize = 1
    for a in dp:
        dsize *= mesh.shape[a]
    msize = mesh.shape["model"]
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path_keys]
    nd = leaf.ndim
    if nd <= 1:
        return P()
    # vocab-parallel embedding (Megatron): vocab over `model` so logits come
    # out (tokens@data, vocab@model) without materializing the full vocab dim
    if names[-1] == "table" and nd == 2:
        v, d = leaf.shape
        return P("model" if _div(v, msize) else None,
                 dp if _div(d, dsize) else None)
    # MoE expert stacks: [...]['mlp']['gate'|'up'|'down'] raw 3D/4D arrays.
    # 2-D sharded: experts over `model` (expert parallelism), the weight's
    # contraction dim over `data` (FSDP); the shard_map dispatch path
    # all-gathers the contraction dim per layer (reduce-scatter on backward).
    if names[-1] in ("gate", "up", "down") and nd >= 3 and cfg.is_moe:
        e, w_in = leaf.shape[-3], leaf.shape[-2]
        espec = "model" if _div(e, msize) else None
        wspec = dp if _div(w_in, dsize) else None
        return P(*(None,) * (nd - 3), espec, wspec, None)
    # generic matrices (possibly layer-stacked): shard last two dims
    d_in, d_out = leaf.shape[-2:]
    a = dp if _div(d_in, dsize) else None
    b = "model" if _div(d_out, msize) else None
    return P(*(None,) * (nd - 2), a, b)


def param_shardings(params_shape, mesh, cfg: ArchConfig):
    """Pytree of NamedShardings matching a params (or opt-state) shape tree."""
    def mk(path, leaf):
        return NamedSharding(mesh, param_spec(path, leaf, mesh, cfg))
    return jax.tree_util.tree_map_with_path(mk, params_shape)


def batch_shardings(specs, mesh, cfg: ArchConfig):
    """Input shardings for train/prefill token batches."""
    dp = data_axes(mesh)
    dsize = 1
    for a in dp:
        dsize *= mesh.shape[a]

    def mk(leaf):
        b = leaf.shape[0]
        ax = dp if _div(b, dsize) else None
        return NamedSharding(mesh, P(ax, *(None,) * (leaf.ndim - 1)))
    return jax.tree.map(mk, specs)


def cache_shardings(cache_specs, mesh, cfg: ArchConfig, batch: int):
    """Decode-cache shardings: batch over data; sequence over model
    (+ data when batch==1) — DistAttention layout."""
    dp = data_axes(mesh)
    dsize = 1
    for a in dp:
        dsize *= mesh.shape[a]
    msize = mesh.shape["model"]
    batch_ok = _div(batch, dsize)

    def seq_axis_for(sdim: int):
        if batch_ok:
            return "model" if _div(sdim, msize) else None
        full = tuple(dp) + ("model",)
        if _div(sdim, dsize * msize):
            return full
        return "model" if _div(sdim, msize) else None

    def mk(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", str(k)))
                 for k in path]
        nd = leaf.ndim
        shape = leaf.shape
        batch_ax = dp if batch_ok else None
        # identify which dim is batch: caches may carry a leading stacked-
        # layer dim; batch dim is where shape == `batch`
        lead = 1 if (nd >= 3 and shape[0] != batch and shape[1] == batch) \
            else 0
        spec = [None] * nd
        if shape[lead] == batch and batch_ok:
            spec[lead] = dp
        # sequence dim right after batch for kv/mla/pos leaves
        field = names[-1] if names else ""
        if field in ("k", "v", "ckv", "krope", "pos", "ck", "cv"):
            sdim_idx = lead + 1
            if sdim_idx < nd:
                spec[sdim_idx] = seq_axis_for(shape[sdim_idx])
        elif field == "state":  # SSM state (.., B, H, P, N): heads on model
            hidx = lead + 1
            if hidx < nd and _div(shape[hidx], msize):
                spec[hidx] = "model"
        elif field == "conv":  # (.., B, W-1, conv_dim)
            cidx = nd - 1
            if _div(shape[cidx], msize):
                spec[cidx] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(mk, cache_specs)


def replicated(tree, mesh):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
