"""Plain reference of a Qwen3-style dense decoder, in float32.

Straight ``jax.numpy`` after the published description (Qwen3: RMSNorm before
attention and MLP, per-head RMSNorm of queries and keys, rotary positions by
half-split rotation, grouped-query causal attention, SwiGLU MLP, final RMSNorm,
tied embedding as the output head). No kernel, cache, batching trick or
program code: it imports nothing of the system under test.

``prec`` picks the arithmetic of every matrix product:

* ``"f32"``  -- float32 inputs at ``Precision.HIGHEST`` (the reference);
* ``"fp8"``  -- inputs rounded to float8 e4m3 with one scale per tensor,
  products accumulated in float32: the control, one precision step below the
  bfloat16 the configurations state.

Weights arrive in their stored dtype and are widened inside each layer, so a
whole model's float32 copy never exists at once.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0


def _fp8(x):
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32), s


def mm(eq: str, a, b, prec: str):
    if prec == "f32":
        return jnp.einsum(eq, a.astype(F32), b.astype(F32),
                          precision=jax.lax.Precision.HIGHEST)
    if prec == "fp8":
        (a8, sa), (b8, sb) = _fp8(a), _fp8(b)
        return jnp.einsum(eq, a8, b8, precision=jax.lax.Precision.HIGHEST) * (sa * sb)
    raise ValueError(f"unknown precision {prec!r}")


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def rope(x, theta: float):
    """x [S, heads, hd] at positions 0..S-1; rotate the two halves."""
    S, _, hd = x.shape
    half = hd // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p, x, model: dict, prec: str):
    """One decoder layer on one sequence x [S, d] (f32)."""
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    H, KV, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    S = x.shape[0]
    a = p["mixer"]
    h = rms_norm(x, p["norm1"]["scale"], eps)
    q = rms_norm(mm("sd,dhk->shk", h, a["wq"], prec), a["q_norm"], eps)
    k = rms_norm(mm("sd,dhk->shk", h, a["wk"], prec), a["k_norm"], eps)
    v = mm("sd,dhk->shk", h, a["wv"], prec)
    q, k = rope(q, theta), rope(k, theta)
    q = q.reshape(S, KV, H // KV, hd)  # query head j*G+g reads kv head j
    scores = mm("sjgk,tjk->jgst", q, k, prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm("jgst,tjk->sjgk", probs, v, prec).reshape(S, H, hd)
    x = x + mm("shk,hkd->sd", o, a["wo"], prec)
    f = p["ffn"]
    h = rms_norm(x, p["norm2"]["scale"], eps)
    up = jax.nn.silu(mm("sd,df->sf", h, f["w_gate"], prec)) * mm("sd,df->sf", h, f["w_up"], prec)
    return x + mm("sf,fd->sd", up, f["w_down"], prec)


def hidden(params, tokens, model: dict, prec: str):
    """Final normed hidden states [S, d] of one sequence."""
    x = params["embed"]["table"][tokens].astype(F32)

    def body(x, p):
        return layer(p, x, model, prec), None

    x, _ = jax.lax.scan(body, x, params["blocks"][0])
    return rms_norm(x, params["final_norm"]["scale"], model["rms_norm_eps"])


def logits(params, tokens, model: dict, prec: str = "f32"):
    """[S, V] next-token logits of one sequence (tied output head)."""
    return mm("sd,vd->sv", hidden(params, tokens, model, prec),
              params["embed"]["table"], prec)


def seq_loss(params, tokens, model: dict, prec: str = "f32"):
    """Mean next-token cross entropy of one sequence [S]."""
    lg = logits(params, tokens, model, prec)[:-1]
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


def batch_loss(params, rows, model: dict, prec: str = "f32"):
    """Mean of the sequence losses of rows [b, S]: equal-length rows, so this
    is the mean over every predicted token."""
    return jnp.mean(jax.lax.map(lambda r: seq_loss(params, r, model, prec), rows))
