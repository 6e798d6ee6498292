"""A model's published ``config.json`` keys, translated into an
:class:`~repro.configs.ArchConfig`: dense GQA, latent attention (MLA) and
sparse experts (MoE), as far as the program serves them.

Every key of the published ``model`` dict is either honoured or refused
with a ``ValueError`` that names it, as is a key that the file's family
needs and the file lacks; none is dropped.

Honoured:

- dense: ``hidden_size``, ``intermediate_size``, ``num_hidden_layers``,
  ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
  ``vocab_size``, ``max_position_embeddings``, ``sliding_window``,
  ``rope_theta``, ``rms_norm_eps``, ``torch_dtype``,
  ``tie_word_embeddings`` (false: an output head of its own);
  ``hidden_act`` silu, ``attention_bias`` false, ``rope_scaling`` null or
  of type ``yarn`` (``factor``, ``original_max_position_embeddings``,
  ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``);
- MLA: ``kv_lora_rank``, ``q_lora_rank`` (null: no query compression),
  ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, with
  ``num_key_value_heads`` equal to the heads;
- MoE: ``n_routed_experts`` (the experts held here), ``router_experts``
  (the router's published width, where the file holds a share of the
  experts: the first ``n_routed_experts`` of them),
  ``num_experts_per_tok``, ``moe_intermediate_size``, ``n_shared_experts``,
  ``first_k_dense_replace``, ``topk_method`` (``greedy`` or
  ``group_limited_greedy`` over ``n_group`` groups, keeping the best
  ``topk_group``), ``norm_topk_prob``, ``routed_scaling_factor`` (applied
  where the weights are not normalised, as DeepSeek-V2's ``MoEGate``
  does); ``moe_layer_freq`` 1, ``scoring_func`` softmax. Serving routes
  every token to its top experts and drops none; ``capacity_factor`` is
  set so that the expert-parallel training path drops none either;
- with no effect on a served forward pass: ``model_type``, ``seq_aux``,
  ``aux_loss_alpha`` (training losses).
"""

from __future__ import annotations

from typing import Dict

DENSE = {"hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "vocab_size", "max_position_embeddings", "sliding_window",
         "rope_theta", "rms_norm_eps", "torch_dtype", "hidden_act",
         "tie_word_embeddings", "attention_bias", "rope_scaling"}
MLA = {"kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
       "qk_rope_head_dim", "v_head_dim"}
MOE = {"n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
       "n_shared_experts", "first_k_dense_replace", "moe_layer_freq",
       "topk_method", "n_group", "topk_group", "scoring_func",
       "norm_topk_prob", "routed_scaling_factor", "router_experts"}
INERT = {"model_type", "seq_aux", "aux_loss_alpha"}
YARN = {"type", "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim"}
# keys a file of the family has to give
NEEDED = {
    "dense": {"hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "vocab_size",
              "max_position_embeddings", "rope_theta", "rms_norm_eps",
              "torch_dtype", "tie_word_embeddings"},
    "mla": MLA - {"q_lora_rank"},
    "moe": {"n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size"},
}

# key -> (the values the program serves, what it would need otherwise)
FIXED = {
    "hidden_act": (("silu",), "an MLP activation other than SiLU"),
    "attention_bias": ((False, None), "biases on the attention projections"),
    "moe_layer_freq": ((1,), "MoE layers interleaved with dense ones"),
    "topk_method": (("greedy", "group_limited_greedy"),
                    "an expert selection other than (group-limited) top-k"),
    "scoring_func": (("softmax",), "router scores other than a softmax"),
}


def from_published(name: str, model: Dict, source: str):
    """The ``ArchConfig`` for the published keys in ``model``. A
    ``ValueError`` names every key or value it does not serve."""
    from repro.configs import ArchConfig, YaRN
    refused = []

    def refuse(key, why):
        refused.append(f"{key}={model.get(key)!r}: the program does not "
                       f"serve {why}")

    for key in model:
        if key not in DENSE | MLA | MOE | INERT:
            refuse(key, "this key")
    for key, (ok, why) in FIXED.items():
        if key in model and model[key] not in ok:
            refuse(key, why)
    mla, moe = bool(MLA & set(model)), bool(MOE & set(model))
    needed = NEEDED["dense"] | (NEEDED["mla"] if mla else set()) | \
        (NEEDED["moe"] if moe else set())
    missing = sorted(needed - set(model))
    for key in missing:
        refuse(key, "a file without it")
    if mla and "head_dim" in model:
        refuse("head_dim", "a head size beside latent attention's own")
    if missing:
        raise ValueError(f"{name}: " + "; ".join(refused))
    h = model["num_attention_heads"]
    kw = dict(
        arch_id=name, family="dense", source=source,
        num_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        num_heads=h, num_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim") or model["hidden_size"] // h,
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        attention="gqa", sliding_window=model.get("sliding_window"),
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=model["torch_dtype"])
    scaling = model.get("rope_scaling")
    if scaling is not None:
        if not isinstance(scaling, dict) or scaling.get("type") != "yarn" \
                or not {"factor", "original_max_position_embeddings"} <= \
                set(scaling) or set(scaling) - YARN:
            refuse("rope_scaling", "rotary scaling other than YaRN with "
                   "its factor and original positions")
        else:
            kw["rope_scaling"] = YaRN(
                factor=float(scaling["factor"]),
                original_max_position=int(
                    scaling["original_max_position_embeddings"]),
                beta_fast=float(scaling.get("beta_fast", 32)),
                beta_slow=float(scaling.get("beta_slow", 1)),
                mscale=float(scaling.get("mscale", 1)),
                mscale_all_dim=float(scaling.get("mscale_all_dim", 0)))
    if mla:
        if kw["num_kv_heads"] != h:
            refuse("num_key_value_heads",
                   "latent attention with fewer key heads than query heads")
        if kw["sliding_window"]:
            refuse("sliding_window", "latent attention over a window")
        kw.update(attention="mla", kv_lora_rank=model["kv_lora_rank"],
                  q_lora_rank=model.get("q_lora_rank") or 0,
                  qk_nope_head_dim=model["qk_nope_head_dim"],
                  qk_rope_head_dim=model["qk_rope_head_dim"],
                  v_head_dim=model["v_head_dim"],
                  head_dim=model["v_head_dim"])
    if moe:
        experts, top_k = model["n_routed_experts"], \
            model["num_experts_per_tok"]
        router = model.get("router_experts", experts)
        if router < experts:
            refuse("router_experts", f"a router narrower than the "
                   f"{experts} experts held here")
        groups, top_groups = 1, 1
        if model.get("topk_method") == "group_limited_greedy":
            groups = model.get("n_group") or 1
            top_groups = model.get("topk_group") or 1
            if router % groups or not 1 <= top_groups <= groups or \
                    top_k > top_groups * (router // groups):
                refuse("n_group", f"{groups} groups, {top_groups} kept, of "
                       f"{router} experts for the top {top_k}")
        elif (model.get("n_group") or 1) > 1 or \
                (model.get("topk_group") or 1) > 1:
            refuse("n_group", "expert groups under plain top-k routing")
        kw.update(family="moe", num_experts=experts, moe_top_k=top_k,
                  moe_d_ff=model["moe_intermediate_size"],
                  num_shared_experts=model.get("n_shared_experts") or 0,
                  first_k_dense=model.get("first_k_dense_replace", 0),
                  router_experts=0 if router == experts else router,
                  router_groups=groups, router_topk_groups=top_groups,
                  norm_topk=bool(model.get("norm_topk_prob", True)),
                  routed_scale=float(model.get("routed_scaling_factor", 1)),
                  capacity_factor=experts / top_k)
    if refused:
        raise ValueError(f"{name}: " + "; ".join(refused))
    return ArchConfig(**kw)
