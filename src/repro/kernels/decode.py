"""Fused decode-attention kernel for the serving engine.

Per-tick decode is the serving fleet's hottest loop and it is memory-bound:
one query token attends over the whole KV cache, so the arithmetic intensity
is O(1) FLOPs per cache byte and throughput is set by how many bytes the
cache read moves.  The XLA path today (a) materializes ``_repeat_kv`` —
re-reading the kv heads G times for grouped-query attention — and (b) reads
the cache at f32/bf16 width.

This kernel fixes both:

* each (batch, kv-head) grid cell handles that pair's G query heads at
  once, so k/v stream through VMEM exactly once — no repeat.
* opt-in int8 quantized-KV mode (``k_scale``/``v_scale`` per (position,
  kv-head), built by ``ref.quantize_kv_ref`` at cache-store time): dequant
  is fused into the contractions — scores scale by ``k_scale`` *after* the
  int8 QK matmul and probabilities by ``v_scale`` *before* the int8 PV
  matmul — so the cache is read once at 1/4 the f32 bytes and no dequantized
  copy is ever materialized.

A ``valid`` row mask handles both linear caches (slots beyond ``pos``) and
ring-buffer windowed caches (wrapped slot ages); masked slots use the same
finite -1e30 sentinel as the other attention kernels.

TPU layout: the grid is (B, KV, L / block_l) and the cache streams through
it one ``block_l`` slab at a time, so VMEM holds O(block_l) whatever the
cache length; the online-softmax carry lives in VMEM scratch across the
sequential innermost axis (the ``sliding_window.py`` pattern).  The cache is
viewed as [B, L, KV * hd] (a free reshape) so one kv head's slab is a
(block_l, hd) block on the lane axis, which the TPU's (8, 128) tiling
accepts when ``hd % 128 == 0``; the live mask and the int8 scales are laid
out with the cache length on the lanes for the same reason.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, *rest, scale, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [G, hd]
    k = k_ref[0].astype(jnp.float32)  # [block_l, hd]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, block_l]
    if quantized:
        s = s * ks_ref[0, 0]
    s = jnp.where(valid_ref[0] != 0, s, NEG_INF)

    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_cur = jnp.maximum(m_prev, s.max(-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_cur = l_prev * alpha + p.sum(-1)
    if quantized:
        p = p * vs_ref[0, 0]
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        l_fin = l_ref[:, 0]
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_fin, 1e-30)[:, None]
        ).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,  # [B, KV, G, hd]
    k: jax.Array,  # [B, L, KV, hd]  (int8 when k_scale given)
    v: jax.Array,  # [B, L, KV, hd]
    valid: jax.Array,  # [B, L] bool/int — live cache slots
    *,
    scale: float | None = None,
    k_scale: jax.Array | None = None,  # [B, L, KV] f32
    v_scale: jax.Array | None = None,
    block_l: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, kv, g, hd = q.shape
    length = k.shape[1]
    assert k.shape == v.shape == (b, length, kv, hd), (q.shape, k.shape)
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    block_l = min(block_l, length)
    assert length % block_l == 0, (length, block_l)

    kv_spec = pl.BlockSpec((1, block_l, hd), lambda bi, h, j: (bi, j, h))
    in_specs = [
        pl.BlockSpec((1, 1, g, hd), lambda bi, h, j: (bi, h, 0, 0)),
        kv_spec,
        kv_spec,
        pl.BlockSpec((1, 1, block_l), lambda bi, h, j: (bi, 0, j)),
    ]
    operands = [
        q,
        k.reshape(b, length, kv * hd),
        v.reshape(b, length, kv * hd),
        valid.astype(jnp.int32).reshape(b, 1, length),
    ]
    if quantized:
        sc_spec = pl.BlockSpec((1, 1, 1, block_l), lambda bi, h, j: (bi, h, 0, j))
        in_specs += [sc_spec, sc_spec]
        operands += [
            x.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
            for x in (k_scale, v_scale)
        ]

    kernel = functools.partial(_decode_kernel, scale=scale, quantized=quantized)
    return pl.pallas_call(
        kernel,
        grid=(b, kv, length // block_l),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda bi, h, j: (bi, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)


def decode_attention_fused_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    valid: jax.Array,
    *,
    scale: float | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """XLA twin of the decode kernel (same fused-dequant math, no Pallas).

    Off-TPU the Pallas path would run under ``interpret=True`` — correct but
    slow — so the CPU serving engine dispatches here instead: grouped heads
    without a materialized ``_repeat_kv`` and int8 dequant fused into the
    einsums.  Identical contraction order to the kernel's per-slab loop up
    to the online-softmax reassociation.
    """
    from repro.kernels.ref import decode_attention_ref

    return decode_attention_ref(
        q, k, v, valid, scale=scale, k_scale=k_scale, v_scale=v_scale
    )
