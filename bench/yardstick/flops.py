"""Operations and bytes the work needs, counted from shapes.

Model FLOPs count each multiply-add as 2 operations: the projections, the
MLP, causal attention (each query against the keys before it) and the tied
output head. Training counts forward + backward as 3 x forward; recomputation
and the gossip do not count. Serving counts the output head once per token the
server must emit: the last prompt position and every decoded token.
"""
from __future__ import annotations

from yardstick.weights import dims, param_count


def _layer_matmul_flops(g: dict) -> int:
    d, H, KV, hd, f = g["d"], g["H"], g["KV"], g["hd"], g["f"]
    return 2 * (d * (H + 2 * KV) * hd + H * hd * d + 3 * d * f)


def _attn_flops(g: dict, keys: float) -> float:
    """Scores and weighted values of one query against ``keys`` keys, all layers."""
    return 4.0 * g["H"] * g["hd"] * keys * g["L"]


def train_flops_per_token(model: dict, seq_len: int) -> float:
    g = dims(model)
    fwd = (g["L"] * _layer_matmul_flops(g) + _attn_flops(g, (seq_len + 1) / 2)
           + 2 * g["d"] * g["V"])
    return 3.0 * fwd


def prefill_flops(model: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` real tokens (padding does not count)."""
    g = dims(model)
    return (prompt_len * g["L"] * _layer_matmul_flops(g)
            + _attn_flops(g, prompt_len * (prompt_len + 1) / 2) + 2 * g["d"] * g["V"])


def decode_flops(model: dict, context: int) -> float:
    """One decoded token that attends ``context`` keys (itself included)."""
    g = dims(model)
    return g["L"] * _layer_matmul_flops(g) + _attn_flops(g, context) + 2 * g["d"] * g["V"]


def weight_bytes(model: dict, itemsize: int = 2) -> int:
    return param_count(model) * itemsize


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> int:
    g = dims(model)
    return g["L"] * 2 * g["KV"] * g["hd"] * itemsize


def decode_tick_bytes(model: dict, contexts) -> float:
    """Bytes one decode tick must read: every weight once, and the live keys
    and values of each active slot (its context length)."""
    return weight_bytes(model) + kv_bytes_per_token(model) * sum(contexts)
