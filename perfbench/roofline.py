"""Peaks of each chip, and the operations and bytes that the model's steps
need, computed from shapes: the yardstick of the roofline and MFU metrics.

The counts are of the algorithm, not of what the program happens to move:
a decode step reads the weights once and each active sequence's real
context (the window's worth under sliding-window attention), and writes
one token of KV per sequence. Padding slots, padding pages, copies of the
whole KV pool and gathers past the context are not counted, so that any
implementation of the same step is held to the same least time.
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


def layer_matmul_params(model: Dict) -> int:
    d, h, hkv, dh, _ = dims(model)
    ff = model["intermediate_size"]
    return d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * ff


def matmul_params(model: Dict) -> int:
    """Parameters in the matmuls one token passes through, the
    unembedding included (the embedding lookup is no matmul)."""
    return model["num_hidden_layers"] * layer_matmul_params(model) + \
        model["vocab_size"] * model["hidden_size"]


def weight_bytes(model: Dict, bytes_per: int = 2) -> int:
    """Every weight once: layers, norms and one vocabulary table (tied)."""
    d, _, _, _, layers = dims(model)
    n = layers * (layer_matmul_params(model) + 2 * d) + d + \
        model["vocab_size"] * d
    if not model.get("tie_word_embeddings", False):
        n += model["vocab_size"] * d
    return n * bytes_per


def kv_bytes_per_token(model: Dict, bytes_per: int = 2) -> int:
    _, _, hkv, dh, layers = dims(model)
    return layers * 2 * hkv * dh * bytes_per


def attended(model: Dict, ctx: int) -> int:
    """Keys one query at context length ``ctx`` attends to."""
    w = model.get("sliding_window")
    return min(ctx, w) if w else ctx


def _attn_flops_per_key(model: Dict) -> int:
    _, h, _, dh, layers = dims(model)
    return layers * 4 * h * dh          # q.k and p.v, per key, all layers


def decode_flops(model: Dict, ctxs: Sequence[int]) -> float:
    """One decode step of sequences at context lengths ``ctxs`` (each
    counts the token being decoded)."""
    return sum(2.0 * matmul_params(model)
               + _attn_flops_per_key(model) * attended(model, c)
               for c in ctxs)


def decode_bytes(model: Dict, ctxs: Sequence[int]) -> float:
    kv = kv_bytes_per_token(model)
    return float(weight_bytes(model)) + \
        sum(kv * attended(model, c) for c in ctxs) + kv * len(ctxs)


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


def prefill_flops(model: Dict, start: int, length: int) -> float:
    """One prefill chunk of ``length`` tokens after ``start`` cached ones;
    logits only at its last position."""
    d, v = model["hidden_size"], model["vocab_size"]
    body = matmul_params(model) - v * d
    return 2.0 * body * length + 2.0 * v * d + \
        _attn_flops_per_key(model) * _sum_attended(model, start, length)
