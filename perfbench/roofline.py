"""Peaks of each chip, and the operations and bytes that the model's steps
need, computed from shapes: the yardstick of the roofline and MFU metrics.

The counts are of the algorithm, not of what the program happens to move:
a decode step reads the weights once and each active sequence's real
context (the window's worth under sliding-window attention), and writes
one token of KV per sequence. Padding slots, padding pages, copies of the
whole KV pool and gathers past the context are not counted, so that any
implementation of the same step is held to the same least time.

The counts follow the configuration file's keys: dense GQA; latent
attention (MLA, where ``kv_lora_rank`` is given: a latent of
``kv_lora_rank + qk_rope_head_dim`` per token and layer, decode attention
in the absorbed form, a prefill chunk in whichever published form needs
fewer operations); sparse experts (MoE, where ``n_routed_experts`` is
given: the experts held here, under a router of ``router_experts``
outputs, the published width, which defaults to ``n_routed_experts``; the
first ``first_k_dense_replace`` layers are dense).

Which routed experts a step uses, the counts cannot know from shapes.
They read it from the program's telemetry: one ring event ``engine`` /
``moe_route`` per program call (a decode step or a prefill chunk), in the
order of the calls, with the arguments ``program`` (``"decode"`` or
``"prefill"``), ``assigned`` (per MoE layer, the (token, expert)
assignments to experts held here) and ``hit`` (per MoE layer, the
distinct experts held here that a token reached). Routed FLOPs are
counted per assignment, routed bytes per expert hit. Without that record
the counts of an MoE step are None, and so is every share made from
them.
"""

from __future__ import annotations

from typing import Dict, Sequence

# per chip; "TPU v5 lite" is what JAX reports as device_kind for a v5e.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GiB HBM2 at 819 GB/s per chip).
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown chip is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add it to perfbench/roofline.py with its source")


def dims(model: Dict):
    d = model["hidden_size"]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = model.get("head_dim") or d // h
    return d, h, hkv, dh, model["num_hidden_layers"]


def is_mla(model: Dict) -> bool:
    return "kv_lora_rank" in model


def is_moe(model: Dict) -> bool:
    return "n_routed_experts" in model


def _mla(model: Dict):
    return (model["num_attention_heads"], model["kv_lora_rank"],
            model.get("q_lora_rank") or 0, model["qk_nope_head_dim"],
            model["qk_rope_head_dim"], model["v_head_dim"])


def _wkv_b(model: Dict) -> int:
    """MLA: parameters of the latent's up-projection to keys and values."""
    h, r, _, dn, _, dv = _mla(model)
    return r * h * (dn + dv)


def attn_params(model: Dict) -> int:
    """Matmul parameters of one layer's attention."""
    d = model["hidden_size"]
    if is_mla(model):
        h, r, qr, dn, dr, dv = _mla(model)
        q = d * qr + qr * h * (dn + dr) if qr else d * h * (dn + dr)
        return q + d * (r + dr) + _wkv_b(model) + h * dv * d
    _, h, hkv, dh, _ = dims(model)
    return d * h * dh + 2 * d * hkv * dh + h * dh * d


def layer_matmul_params(model: Dict) -> int:
    """Matmul parameters of one dense layer: attention and MLP."""
    return attn_params(model) + \
        3 * model["hidden_size"] * model["intermediate_size"]


def expert_params(model: Dict) -> int:
    """Matmul parameters of one routed or shared expert."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def token_params(model: Dict) -> int:
    """Matmul parameters every token passes through in the layers: the
    attention, the dense MLPs and, in MoE layers, the router and the shared
    experts; not the routed experts, which the routing decides."""
    layers = model["num_hidden_layers"]
    if not is_moe(model):
        return layers * layer_matmul_params(model)
    n = layers - model.get("first_k_dense_replace", 0)
    d = model["hidden_size"]
    moe = d * model.get("router_experts", model["n_routed_experts"]) + \
        (model.get("n_shared_experts") or 0) * expert_params(model)
    return layers * attn_params(model) + \
        (layers - n) * 3 * d * model["intermediate_size"] + n * moe


def matmul_params(model: Dict) -> int:
    """Parameters in the matmuls one token passes through, the
    unembedding included (the embedding lookup is no matmul); for MoE
    without the routed experts."""
    return token_params(model) + model["vocab_size"] * model["hidden_size"]


def _norm_params(model: Dict) -> int:
    n = 2 * model["hidden_size"]
    if is_mla(model):
        _, r, qr, _, _, _ = _mla(model)
        n += r + qr
    return n


def weight_bytes(model: Dict, bytes_per: int = 2) -> int:
    """Every weight that each step reads once: layers (an MoE layer without
    its routed experts), norms and one vocabulary table (tied)."""
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    n = token_params(model) + layers * _norm_params(model) + d + \
        model["vocab_size"] * d
    if not model.get("tie_word_embeddings", False):
        n += model["vocab_size"] * d
    return n * bytes_per


def kv_bytes_per_token(model: Dict, bytes_per: int = 2) -> int:
    if is_mla(model):
        _, r, _, _, dr, _ = _mla(model)
        return model["num_hidden_layers"] * (r + dr) * bytes_per
    _, _, hkv, dh, layers = dims(model)
    return layers * 2 * hkv * dh * bytes_per


def attended(model: Dict, ctx: int) -> int:
    """Keys one query at context length ``ctx`` attends to."""
    w = model.get("sliding_window")
    return min(ctx, w) if w else ctx


def _attn_flops_per_key(model: Dict) -> int:
    """Per attended key, all layers: q.k and p.v; under MLA in the absorbed
    form, over the latent and the rotary part."""
    if is_mla(model):
        h, r, _, _, dr, _ = _mla(model)
        return model["num_hidden_layers"] * 2 * h * (2 * r + dr)
    _, h, _, dh, layers = dims(model)
    return layers * 4 * h * dh


def routes(model: Dict, events, program: str, n: int):
    """The routing record of each of ``n`` calls of ``program`` from the
    ring ``events`` of the same iterations: ``[None] * n`` for a model
    without experts, None where the records and the calls do not pair."""
    if not is_moe(model):
        return [None] * n
    got = [e.args for e in events if e.cat == "engine"
           and e.name == "moe_route" and e.args.get("program") == program]
    return got if len(got) == n else None


def _with_routed(model: Dict, total: float, route, key: str):
    """``total`` plus the routed experts' part, 2 x their parameters per
    ``route[key]`` (assignments for FLOPs, experts hit for bytes); None for
    an MoE call without its routing record."""
    if not is_moe(model):
        return total
    if route is None:
        return None
    return total + 2.0 * expert_params(model) * sum(route[key])


def decode_flops(model: Dict, ctxs: Sequence[int], route=None):
    """One decode step of sequences at context lengths ``ctxs`` (each
    counts the token being decoded); an MoE step needs its ``route``."""
    total = sum(2.0 * matmul_params(model)
                + _attn_flops_per_key(model) * attended(model, c)
                for c in ctxs)
    return _with_routed(model, total, route, "assigned")


def decode_bytes(model: Dict, ctxs: Sequence[int], route=None):
    kv = kv_bytes_per_token(model)
    total = float(weight_bytes(model)) + \
        sum(kv * attended(model, c) for c in ctxs) + kv * len(ctxs)
    return _with_routed(model, total, route, "hit")


def least_time(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def _sum_attended(model: Dict, start: int, length: int) -> int:
    """Sum over query positions i in [start, start+length) of the keys
    position i attends to (min(i + 1, window))."""
    w = model.get("sliding_window")
    lo, hi = start + 1, start + length          # contexts lo..hi
    if not w or hi <= w:
        return (lo + hi) * length // 2
    if lo > w:
        return w * length
    return (lo + w) * (w - lo + 1) // 2 + w * (hi - w)


def _chunk_keys(model: Dict, start: int, length: int) -> int:
    """Keys that some query of the chunk attends to."""
    w = model.get("sliding_window")
    return min(start + length, length + w - 1) if w else start + length


def prefill_flops(model: Dict, start: int, length: int, route=None):
    """One prefill chunk of ``length`` tokens after ``start`` cached ones;
    logits only at its last position. Under MLA the lesser of the absorbed
    form (as decode) and the decompressed one (every key the chunk attends
    to is decompressed once, and each query attends over ``qk_nope +
    qk_rope`` and ``v_head_dim`` per head); an MoE chunk needs its
    ``route``."""
    d, v = model["hidden_size"], model["vocab_size"]
    body = matmul_params(model) - v * d
    att = _sum_attended(model, start, length)
    total = 2.0 * body * length + 2.0 * v * d + \
        _attn_flops_per_key(model) * att
    if is_mla(model):
        h, _, _, dn, dr, dv = _mla(model)
        layers, up = model["num_hidden_layers"], _wkv_b(model)
        total = min(total, 2.0 * (body - layers * up) * length + 2.0 * v * d
                    + layers * 2 * h * (dn + dr + dv) * att
                    + 2.0 * layers * up * _chunk_keys(model, start, length))
    return _with_routed(model, total, route, "assigned")
