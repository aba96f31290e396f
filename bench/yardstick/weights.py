"""Seeded random weights for a Qwen3-style dense decoder, made on the device.

The same function makes the weights the program is handed and the weights the
reference recomputes, so the two start from the same numbers without the
reference reading anything the program made. The tree follows the layout the
program's model takes (``embed.table``, one stacked block of all layers,
``final_norm``); matrices are N(0, 1/fan_in), norm scales are ones, in the
dtype the configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(model: dict) -> dict:
    return {
        "d": model["hidden_size"], "H": model["num_attention_heads"],
        "KV": model["num_key_value_heads"], "hd": model["head_dim"],
        "f": model["intermediate_size"], "V": model["vocab_size"],
        "L": model["num_hidden_layers"],
    }


def layout(model: dict) -> dict:
    """Leaf path -> (shape, fan_in); fan_in None marks a norm scale (ones)."""
    g = dims(model)
    d, H, KV, hd, f, V, L = (g[k] for k in ("d", "H", "KV", "hd", "f", "V", "L"))
    return {
        ("embed", "table"): ((V, d), d),
        ("blocks", 0, "norm1", "scale"): ((L, d), None),
        ("blocks", 0, "mixer", "wq"): ((L, d, H, hd), d),
        ("blocks", 0, "mixer", "wk"): ((L, d, KV, hd), d),
        ("blocks", 0, "mixer", "wv"): ((L, d, KV, hd), d),
        ("blocks", 0, "mixer", "wo"): ((L, H, hd, d), H * hd),
        ("blocks", 0, "mixer", "q_norm"): ((L, hd), None),
        ("blocks", 0, "mixer", "k_norm"): ((L, hd), None),
        ("blocks", 0, "norm2", "scale"): ((L, d), None),
        ("blocks", 0, "ffn", "w_gate"): ((L, d, f), d),
        ("blocks", 0, "ffn", "w_up"): ((L, d, f), d),
        ("blocks", 0, "ffn", "w_down"): ((L, f, d), f),
        ("final_norm", "scale"): ((d,), None),
    }


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for k in path[:-1]:
            if k == 0:  # "blocks" holds a list of one stacked block
                continue
            node = node.setdefault(k, [{}] if k == "blocks" else {})
            if isinstance(node, list):
                node = node[0]
        node[path[-1]] = leaf
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, also one wider than 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, shape, fan_in, dtype):
    if fan_in is None:
        return jnp.ones(shape, dtype)
    scale = 1.0 / math.sqrt(fan_in)
    if len(shape) > 2:
        # one layer at a time: the f32 draw never exceeds one layer's size
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape[1:]) * scale).astype(dtype), keys
        )
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def make(model: dict, key: jax.Array) -> dict:
    """The weights for ``key`` (trace it inside ``jax.jit``)."""
    dtype = jnp.dtype(model["torch_dtype"])
    flat = {}
    for i, (path, (shape, fan_in)) in enumerate(sorted(layout(model).items(), key=str)):
        flat[path] = _leaf(jax.random.fold_in(key, i), shape, fan_in, dtype)
    return _nest(flat)


def param_count(model: dict) -> int:
    return sum(math.prod(s) for s, _ in layout(model).values())
