"""A configuration file's published keys, translated into the program's
``ArchConfig``: dense GQA, latent attention (MLA) and sparse experts (MoE),
as far as the program serves them today.

Every key of the published ``model`` dict is either honoured or refused
with a ``ValueError`` that names it, as is a key that the file's family
needs and the file lacks; none is dropped. Where the program
itself defines ``repro.configs.from_published(name, model, source)``,
``driver.arch_config`` calls that one instead, so that the program owns the
translation once it has one.

Honoured:

- dense: ``hidden_size``, ``intermediate_size``, ``num_hidden_layers``,
  ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
  ``vocab_size``, ``max_position_embeddings``, ``sliding_window``,
  ``rope_theta``, ``rms_norm_eps``, ``torch_dtype``; ``hidden_act``
  silu, ``tie_word_embeddings`` true, ``attention_bias`` false,
  ``rope_scaling`` null;
- MLA: ``kv_lora_rank``, ``q_lora_rank`` (null: no query compression),
  ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, with
  ``num_key_value_heads`` equal to the heads;
- MoE: ``n_routed_experts``, ``num_experts_per_tok``,
  ``moe_intermediate_size``, ``n_shared_experts``,
  ``first_k_dense_replace``; ``moe_layer_freq`` 1, ``topk_method``
  greedy (``n_group`` and ``topk_group`` 1 at most: one group is no
  grouping), ``scoring_func`` softmax, ``norm_topk_prob`` true,
  ``routed_scaling_factor`` 1, ``router_experts`` (the router's published
  width, where a file holds a share of the experts) equal to
  ``n_routed_experts``. The published models route every token to its
  top experts, so the capacity is set so that none is dropped;
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
    "tie_word_embeddings": (
        (True,), "an untied head: the program ties its unembedding to the "
        "embedding"),
    "attention_bias": ((False, None), "biases on the attention projections"),
    "rope_scaling": ((None,), "scaled rotary positions (YaRN and others)"),
    "moe_layer_freq": ((1,), "MoE layers interleaved with dense ones"),
    "topk_method": (("greedy",), "group-limited or other expert selection"),
    "n_group": ((None, 1), "group-limited routing"),
    "topk_group": ((None, 1), "group-limited routing"),
    "scoring_func": (("softmax",), "router scores other than a softmax"),
    "norm_topk_prob": (
        (True,), "unnormalised top-k weights: the program normalises them"),
    "routed_scaling_factor": ((1, 1.0), "a scale on the routed experts"),
}


def from_published(name: str, model: Dict, source: str):
    """The program's ``ArchConfig`` for the published keys in ``model``.
    A ``ValueError`` names every key or value it does not serve."""
    from repro.configs import ArchConfig
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
        norm_eps=float(model["rms_norm_eps"]), tie_embeddings=True,
        dtype=model["torch_dtype"])
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
        if model.get("router_experts", experts) != experts:
            refuse("router_experts", f"a share of the experts "
                   f"({experts} held here)")
        kw.update(family="moe", num_experts=experts, moe_top_k=top_k,
                  moe_d_ff=model["moe_intermediate_size"],
                  num_shared_experts=model.get("n_shared_experts") or 0,
                  first_k_dense=model.get("first_k_dense_replace", 0),
                  capacity_factor=experts / top_k)
    if refused:
        raise ValueError(f"{name}: " + "; ".join(refused))
    return ArchConfig(**kw)
