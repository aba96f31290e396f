"""Flash attention (online softmax) as a Pallas TPU kernel.

The roofline baseline (EXPERIMENTS §Roofline) shows every attention arch is
memory-bound in training, dominated by the f32 [Sq, Sk] score/softmax chain
hitting HBM ~6x per layer.  Flash attention keeps the running max / sum /
accumulator in VMEM and never materializes the score matrix: HBM traffic
drops to the Q/K/V/O tensors themselves.

TPU adaptation (vs. the CUDA original):
  * block shapes are (block_q, head_dim) x (block_k, head_dim) with
    head_dim padded to the 128-lane register width; the q @ k^T and p @ v
    contractions are MXU-shaped matmuls per block;
  * the kv loop is a ``jax.lax.fori_loop`` *inside* the kernel over VMEM
    slices (grid iteration is reserved for the embarrassingly parallel
    (batch*heads, q-block) dimensions);
  * causal/windowed masking is computed from block indices — fully masked
    kv blocks are skipped by clamping the loop bounds (a warp-divergence-free
    analogue of the CUDA early-exit).

Validated in interpret mode against ``ref.flash_attention_ref`` (pure jnp)
over shape/dtype/mask sweeps — see tests/test_flash_attention.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, window,
                  block_q, block_k, seq_k, skip_blocks):
    """One (batch*head, q-block) grid cell: stream kv blocks in VMEM."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # [block_q, hd]
    hd = q.shape[-1]
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    num_kv = seq_k // block_k
    if causal:
        # kv blocks strictly after the last query position are fully masked
        last_q = (qi + 1) * block_q - 1
        num_live = jnp.minimum((last_q // block_k) + 1, num_kv)
    else:
        num_live = num_kv
    if window is not None and skip_blocks:
        first_q = qi * block_q
        first_live = jnp.maximum((first_q - window + 1) // block_k, 0)
    else:
        first_live = 0

    def body(kj, carry):
        acc, m_prev, l_prev = carry
        kv_rows = pl.ds(pl.multiple_of(kj * block_k, block_k), block_k)
        k = k_ref[0, kv_rows, :].astype(jnp.float32)
        v = v_ref[0, kv_rows, :].astype(jnp.float32)
        s = q @ k.T  # [block_q, block_k] — MXU matmul
        k_pos = kj * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, s.max(-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + p.sum(-1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_cur, l_cur

    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(first_live, num_live, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # [BH, Sq, hd]
    k: jax.Array,  # [BH, Sk, hd]
    v: jax.Array,  # [BH, Sk, hd]
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    skip_blocks: bool = True,
) -> jax.Array:
    """skip_blocks=False disables the window's leading-block loop clamp so
    window masking still applies but every kv block is visited — the honest
    mask-only baseline the sliding-window kernel is benchmarked against."""
    bh, sq, hd = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        seq_k=sk,
        skip_blocks=skip_blocks,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),  # q tile
            pl.BlockSpec((1, sk, hd), lambda b, i: (b, 0, 0)),  # k stream
            pl.BlockSpec((1, sk, hd), lambda b, i: (b, 0, 0)),  # v stream
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
