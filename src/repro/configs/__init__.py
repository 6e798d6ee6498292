"""Architecture config registry.

Every assigned architecture is a module in this package exporting ``CONFIG``.
``get_config(arch_id)`` resolves dashed ids (``--arch deepseek-v2-236b``) to the
module name, and ``smoke_config(arch_id)`` returns the reduced variant used by
the per-arch smoke tests (2 layers, d_model<=512, <=4 experts).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rotary scaling as DeepSeek-V2 publishes it (``rope_scaling`` of
    type ``yarn``): the rotary frequencies blend the extrapolated and the
    interpolated ones over a ramp between the correction dimensions of
    ``beta_fast`` and ``beta_slow`` rotations, and the softmax scale of
    latent attention grows by ``mscale(factor, mscale_all_dim)`` squared."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """A composable architecture description covering all assigned families."""

    arch_id: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    source: str  # citation for the config numbers

    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0

    # --- attention variants -------------------------------------------------
    attention: str = "gqa"  # gqa | mla | none
    sliding_window: Optional[int] = None  # SWA width (tokens) or None
    # trained context limit; serving sizes per-sequence block tables from it
    # (None = unbounded, the engine falls back to its page supply)
    max_seq_len: Optional[int] = None
    # Hymba-style: every Nth layer uses global attention, others sliding window.
    global_attn_every: Optional[int] = None
    rope_theta: float = 10_000.0
    rope_scaling: Optional[YaRN] = None
    use_bias: bool = False

    # --- MLA (deepseek-v2) ---------------------------------------------------
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MoE -----------------------------------------------------------------
    num_experts: int = 0
    num_shared_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0  # per-expert hidden size (may differ from dense d_ff)
    first_k_dense: int = 0  # first K layers use the dense MLP (deepseek-v2: 1)
    # expert share: the router scores ``router_experts`` experts (0: as many
    # as are held) and this device holds ``num_experts`` of them, from
    # ``expert_offset`` on (DeepSeek-V2 on 8 chips: 20 of 160 each)
    router_experts: int = 0
    expert_offset: int = 0
    # DeepSeek-V2's MoEGate: top-k over the best ``router_topk_groups`` of
    # ``router_groups`` expert groups (1: plain top-k); normalised top-k
    # weights, or unnormalised ones times ``routed_scale``
    router_groups: int = 1
    router_topk_groups: int = 1
    norm_topk: bool = True
    routed_scale: float = 1.0
    # per-expert capacity of the expert-parallel training mesh path
    # (``MeshPolicy.moe_apply``); every other path is dropless
    capacity_factor: float = 1.25

    # --- SSM (mamba2 SSD) ----------------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    ssm_chunk: int = 256
    ssm_conv_width: int = 4

    # --- enc-dec (seamless) ----------------------------------------------------
    encoder_layers: int = 0  # >0 => encoder-decoder

    # --- modality frontend (stubbed: precomputed embeddings) ------------------
    frontend: str = "none"  # none | vision | audio
    num_media_tokens: int = 0  # patches / audio frames prepended to the text

    gated_mlp: bool = True  # SwiGLU (3 mats) vs plain GeLU MLP (2 mats)
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # compute the unembedding in float32. bf16 logits round near-ties onto
    # the same value, so greedy argmax can legitimately differ between two
    # correct implementations; fp32 logits make greedy decoding comparable
    # across engine/oracle (see tests/test_engine.py).
    logits_fp32: bool = False

    # ------------------------------------------------------------------------
    @property
    def attn_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def ssm_heads(self) -> int:
        d_inner = self.ssm_expand * self.d_model
        return d_inner // self.ssm_head_dim

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def n_router(self) -> int:
        """Experts the router scores: all of them, held here or not."""
        return self.router_experts or self.num_experts

    @property
    def is_ssm_only(self) -> bool:
        return self.family == "ssm"

    @property
    def is_hybrid(self) -> bool:
        return self.family == "hybrid"

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def supports_long_context(self) -> bool:
        """True when 524k decode is sub-quadratic / bounded-memory."""
        if self.family in ("ssm",):
            return True
        if self.is_hybrid:
            return True  # attention part is sliding-window (global layers excepted)
        return self.sliding_window is not None

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)

    def n_params(self) -> int:
        """Approximate total parameter count (for roofline MODEL_FLOPS)."""
        return _count(self, active_only=False)

    def n_active_params(self) -> int:
        """Parameters touched per token (MoE: shared + top_k experts)."""
        return _count(self, active_only=True)


def _count(cfg: ArchConfig, active_only: bool) -> int:
    d = cfg.d_model
    total = cfg.vocab_size * d  # embedding
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * d

    def attn_params() -> int:
        if cfg.attention == "mla":
            qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            p = d * cfg.kv_lora_rank + d * cfg.qk_rope_head_dim  # kv_a + k_rope
            if cfg.q_lora_rank:
                p += d * cfg.q_lora_rank + cfg.q_lora_rank * cfg.num_heads * qd
            else:
                p += d * cfg.num_heads * qd
            p += cfg.kv_lora_rank * cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            p += cfg.num_heads * cfg.v_head_dim * d  # o_proj
            return p
        if cfg.attention == "none":
            return 0
        q = d * cfg.num_heads * cfg.head_dim
        kv = 2 * d * cfg.num_kv_heads * cfg.head_dim
        o = cfg.num_heads * cfg.head_dim * d
        return q + kv + o

    def mlp_params(ff: int) -> int:
        return (3 if cfg.gated_mlp else 2) * d * ff

    def ssm_params() -> int:
        din = cfg.ssm_d_inner
        h = cfg.ssm_heads
        g = cfg.ssm_groups
        n = cfg.ssm_state
        in_proj = d * (2 * din + 2 * g * n + h)
        conv = cfg.ssm_conv_width * (din + 2 * g * n)
        out = din * d
        return in_proj + conv + out + 2 * h  # + A_log, D

    per_layer = 0
    for layer in range(cfg.num_layers):
        p = 0
        if cfg.family == "ssm":
            p += ssm_params()
        elif cfg.is_hybrid:
            p += attn_params() + ssm_params()
        else:
            p += attn_params()
        if cfg.is_moe and layer >= cfg.first_k_dense:
            e = (cfg.num_shared_experts + cfg.moe_top_k) if active_only else (
                cfg.num_shared_experts + cfg.num_experts)
            p += e * mlp_params(cfg.moe_d_ff)
            p += d * cfg.n_router  # router
        elif cfg.d_ff:
            p += mlp_params(cfg.d_ff)
        per_layer += p
    total += per_layer
    # encoder (dense attention + mlp), cross-attention in decoder
    if cfg.is_encdec:
        enc = cfg.encoder_layers * (
            4 * d * cfg.num_heads * cfg.head_dim + mlp_params(cfg.d_ff))
        cross = cfg.num_layers * 4 * d * cfg.num_heads * cfg.head_dim
        total += enc + cross
    return total


# ----------------------------------------------------------------------------
# Input shapes (assigned)
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


ARCH_IDS = [
    "hymba-1.5b",
    "deepseek-v2-236b",
    "llama4-scout-17b-a16e",
    "seamless-m4t-medium",
    "mamba2-1.3b",
    "granite-20b",
    "command-r-35b",
    "mistral-large-123b",
    "internvl2-26b",
    "h2o-danube-1.8b",
]


def _module_name(arch_id: str) -> str:
    return arch_id.replace("-", "_").replace(".", "p")


def get_config(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{_module_name(arch_id)}")
    return mod.CONFIG


def smoke_config(arch_id: str) -> ArchConfig:
    """Reduced variant of the same family: 2 layers, d_model<=512, <=4 experts."""
    cfg = get_config(arch_id)
    heads = min(cfg.num_heads, 4) or 0
    kv = min(cfg.num_kv_heads, heads) if cfg.num_kv_heads else 0
    updates = dict(
        num_layers=2,
        d_model=256,
        num_heads=heads,
        num_kv_heads=max(kv, 1) if cfg.attention != "none" else 0,
        head_dim=64 if cfg.attention != "none" else 0,
        d_ff=512 if cfg.d_ff else 0,
        vocab_size=512,
        encoder_layers=2 if cfg.is_encdec else 0,
        num_media_tokens=8 if cfg.frontend != "none" else 0,
    )
    if cfg.attention == "mla":
        updates.update(kv_lora_rank=64, q_lora_rank=96, qk_nope_head_dim=32,
                       qk_rope_head_dim=16, v_head_dim=32)
    if cfg.is_moe:
        # capacity_factor=E/topk => the expert-parallel mesh path drops no
        # token either, so it matches the dropless single-device path;
        # group-limited routing keeps two groups of two experts
        updates.update(num_experts=4, moe_top_k=min(cfg.moe_top_k, 2), moe_d_ff=128,
                       num_shared_experts=min(cfg.num_shared_experts, 1),
                       capacity_factor=4.0,
                       router_groups=min(cfg.router_groups, 2),
                       router_topk_groups=1)
    if cfg.ssm_state:
        updates.update(ssm_state=16, ssm_head_dim=32, ssm_chunk=32)
    if cfg.sliding_window:
        updates.update(sliding_window=64)
    return dataclasses.replace(cfg, **updates)


def input_specs(cfg: ArchConfig, shape: InputShape):
    """ShapeDtypeStruct stand-ins for every model input of ``shape``.

    No device allocation happens here; these feed ``jax.jit(...).lower``.
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    sd = jax.ShapeDtypeStruct
    media = {}
    if cfg.frontend != "none":
        media["media"] = sd((b, cfg.num_media_tokens, cfg.d_model), jnp.bfloat16)
    if shape.kind == "train":
        out = {"tokens": sd((b, s), i32), "labels": sd((b, s), i32)}
        if cfg.is_encdec:
            out["encoder_tokens"] = sd((b, s // 4), i32)
        out.update(media)
        return out
    if shape.kind == "prefill":
        out = {"tokens": sd((b, s), i32)}
        if cfg.is_encdec:
            out["encoder_tokens"] = sd((b, s // 4), i32)
        out.update(media)
        return out
    # decode: one new token against a cache of seq_len
    out = {
        "tokens": sd((b, 1), i32),
        "positions": sd((b,), i32),
    }
    return out


from repro.configs.published import from_published  # noqa: E402,F401
