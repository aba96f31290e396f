"""Block-sparse attention as a Pallas TPU kernel.

The sparsity pattern is a per-(q-block, kv-block) bitmap with three states:

    0 — skip: the kv block is never loaded or computed,
    1 — partial: compute, then apply the element-level causal/window mask,
    2 — full: compute with no element mask (every pair is live).

``BlockSparsePattern`` builds the bitmap host-side (numpy) for the common
patterns — causal, causal+windowed, and strided (local blocks + every
``stride``-th earlier block, the Sparse-Transformer layout) — and
pre-compacts it into per-q-block index lists so the kernel's inner loop
has a *data-dependent but bounded* trip count: ``fori_loop(0, count[qi])``
over ``kv_index[qi, :]``.  Density is whatever the pattern says; the kernel
does O(density · S²) work instead of O(S²).

Patterns must keep the diagonal block live (all constructors do): the
online-softmax carry uses the finite -1e30 sentinel, and a q row with no
live key in *any* visited block would emit a spurious uniform average
rather than the reference's all-masked softmax.  ``from_bitmap`` checks.

Like ``flash_attention.py``, whole K/V rides in VMEM per (bh, q-block)
grid cell — fine at training sequence lengths; the index lists are
scalar-prefetch operands held in SMEM, read per q block by the kernel.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF

SKIP, PARTIAL, FULL = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class BlockSparsePattern:
    """Host-side block bitmap + compacted per-q-block kv index lists."""

    seq_q: int
    seq_k: int
    block_q: int
    block_k: int
    bitmap: np.ndarray  # [num_q, num_kv] int32 in {SKIP, PARTIAL, FULL}
    causal: bool
    window: int | None

    @staticmethod
    def _pool(seq_q: int, seq_k: int, block_q: int, block_k: int,
              causal: bool, window: int | None) -> np.ndarray:
        """Pool the element-level (causal ∧ window) mask into block states."""
        qp = np.arange(seq_q)[:, None]
        kp = np.arange(seq_k)[None, :]
        live = np.ones((seq_q, seq_k), bool)
        if causal:
            live &= qp >= kp
        if window is not None:
            live &= (qp - kp) < window
        nq, nk = seq_q // block_q, seq_k // block_k
        blocks = live.reshape(nq, block_q, nk, block_k)
        frac = blocks.sum(axis=(1, 3))
        full = frac == block_q * block_k
        return np.where(full, FULL, np.where(frac > 0, PARTIAL, SKIP)).astype(
            np.int32
        )

    @classmethod
    def causal_pattern(cls, seq_q: int, seq_k: int,
                       block_q: int = 128, block_k: int = 128
                       ) -> "BlockSparsePattern":
        bm = cls._pool(seq_q, seq_k, block_q, block_k, True, None)
        return cls(seq_q, seq_k, block_q, block_k, bm, True, None)

    @classmethod
    def windowed(cls, seq_q: int, seq_k: int, window: int,
                 block_q: int = 128, block_k: int = 128
                 ) -> "BlockSparsePattern":
        bm = cls._pool(seq_q, seq_k, block_q, block_k, True, window)
        return cls(seq_q, seq_k, block_q, block_k, bm, True, window)

    @classmethod
    def strided(cls, seq_q: int, seq_k: int, *, local_blocks: int,
                stride: int, block_q: int = 128, block_k: int = 128
                ) -> "BlockSparsePattern":
        """Sparse-Transformer layout: each q block attends to the nearest
        ``local_blocks`` kv blocks plus every ``stride``-th block before."""
        pool = cls._pool(seq_q, seq_k, block_q, block_k, True, None)
        nq, nk = pool.shape
        qi = np.arange(nq)[:, None]
        kj = np.arange(nk)[None, :]
        allowed = (qi - kj < local_blocks) | (kj % stride == 0)
        bm = np.where(allowed, pool, SKIP).astype(np.int32)
        return cls(seq_q, seq_k, block_q, block_k, bm, True, None)

    @classmethod
    def from_bitmap(cls, bitmap: np.ndarray, *, block_q: int, block_k: int,
                    causal: bool = True, window: int | None = None
                    ) -> "BlockSparsePattern":
        bitmap = np.asarray(bitmap, np.int32)
        nq, nk = bitmap.shape
        pool = cls._pool(nq * block_q, nk * block_k, block_q, block_k,
                         causal, window)
        if np.any((bitmap != SKIP) & (pool == SKIP)):
            raise ValueError("bitmap marks blocks live that the causal/window "
                             "mask fully excludes")
        diag = np.array([((i + 1) * block_q - 1) // block_k for i in range(nq)])
        if np.any(bitmap[np.arange(nq), np.minimum(diag, nk - 1)] == SKIP):
            raise ValueError("diagonal block must stay live (softmax carry "
                             "needs >= 1 live key per row)")
        return cls(nq * block_q, nk * block_k, block_q, block_k, bitmap,
                   causal, window)

    def density(self) -> float:
        """Fraction of kv blocks computed (vs. a dense S x S sweep)."""
        return float((self.bitmap != SKIP).mean())

    def compact(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """Per-q-block (kv_index, kv_state, count, max_count) int32 arrays."""
        nq, nk = self.bitmap.shape
        counts = (self.bitmap != SKIP).sum(axis=1).astype(np.int32)
        width = max(int(counts.max()), 1)
        idx = np.zeros((nq, width), np.int32)
        state = np.zeros((nq, width), np.int32)
        for i in range(nq):
            live = np.nonzero(self.bitmap[i] != SKIP)[0]
            idx[i, : live.size] = live
            state[i, : live.size] = self.bitmap[i, live]
        return idx, state, counts, width


def _block_sparse_kernel(idx_ref, state_ref, cnt_ref, q_ref, k_ref, v_ref,
                         o_ref, *, scale, causal, window, block_q, block_k,
                         width):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # [block_q, hd]
    hd = q.shape[-1]
    count = cnt_ref[qi]

    def body(j, carry):
        acc, m_prev, l_prev = carry
        kb = idx_ref[qi * width + j]
        st = state_ref[qi * width + j]
        kv_rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        k = k_ref[0, kv_rows, :].astype(jnp.float32)
        v = v_ref[0, kv_rows, :].astype(jnp.float32)
        s = q @ k.T

        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        live = jnp.ones((block_q, block_k), bool)
        if causal:
            live &= q_pos >= k_pos
        if window is not None:
            live &= q_pos - k_pos < window
        # FULL blocks skip the element mask entirely.
        s = jnp.where((st == FULL) | live, s, NEG_INF)

        m_cur = jnp.maximum(m_prev, s.max(-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + p.sum(-1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_cur, l_cur

    acc = jnp.zeros((block_q, hd), jnp.float32)
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, count, body, (acc, m, l))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def block_sparse_attention_pallas(
    q: jax.Array,  # [BH, Sq, hd]
    k: jax.Array,  # [BH, Sk, hd]
    v: jax.Array,  # [BH, Sk, hd]
    pattern: BlockSparsePattern,
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, hd = q.shape
    sk = k.shape[1]
    assert (sq, sk) == (pattern.seq_q, pattern.seq_k), (
        (sq, sk), (pattern.seq_q, pattern.seq_k))
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    block_q, block_k = pattern.block_q, pattern.block_k
    idx, state, counts, width = pattern.compact()
    nq = sq // block_q

    kernel = functools.partial(
        _block_sparse_kernel,
        scale=scale,
        causal=pattern.causal,
        window=pattern.window,
        block_q=block_q,
        block_k=block_k,
        width=width,
    )
    # the index lists ride in SMEM as scalar-prefetch operands (flattened:
    # 1-D SMEM arrays pad least); every index map also receives them
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, *_: (b, i, 0)),
            pl.BlockSpec((1, sk, hd), lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec((1, sk, hd), lambda b, i, *_: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, *_: (b, i, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        interpret=interpret,
    )(
        jnp.asarray(idx.reshape(-1)),
        jnp.asarray(state.reshape(-1)),
        jnp.asarray(counts),
        q,
        k,
        v,
    )
