"""Jit'd public wrappers around the Pallas compression kernels.

These handle padding/reshaping from arbitrary flat vectors to the kernels'
[rows, 128] lane-aligned layout, select interpret mode automatically
(interpret=True off-TPU so the kernel body runs as the correctness oracle on
CPU), and expose compressor classes plugging into the CHOCO gossip layer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import Compressor
from repro.kernels import quantize as qk
from repro.kernels import topk as tk
from repro.kernels.ref import LANES, ordered_norm, tau_for


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to_rows(flat: jax.Array, row_unit: int) -> jax.Array:
    d = flat.shape[0]
    unit = row_unit * LANES
    pad = (-d) % unit
    return jnp.pad(flat, (0, pad)).reshape(-1, LANES)


def quantize(x: jax.Array, key: jax.Array, bits: int = 4, interpret: bool | None = None):
    """Stochastically quantize a tensor; returns the packed wire payload."""
    if interpret is None:
        interpret = _interpret_default()
    flat = x.reshape(-1).astype(jnp.float32)
    pack = 8 // bits
    grid = _pad_to_rows(flat, 8 * pack)
    norm = ordered_norm(flat)
    xi = jax.random.uniform(key, grid.shape)
    lvl, sign = qk.quantize_pallas(grid, xi, norm, bits, interpret=interpret)
    return {"levels": lvl, "signs": sign, "norm": norm}


def dequantize(payload, shape, dtype, bits: int = 4, interpret: bool | None = None):
    if interpret is None:
        interpret = _interpret_default()
    d = int(np.prod(shape)) if shape else 1
    scale = payload["norm"] / ((1 << bits) * tau_for(d, bits))
    out = qk.dequantize_pallas(payload["levels"], payload["signs"], scale, bits, interpret=interpret)
    return out.reshape(-1)[:d].reshape(shape).astype(dtype)


def fused_choco_round_leaf(leaf, hat, s, key, topology, gamma, bits: int,
                           interpret: bool | None = None, *,
                           roll_fn=None, node_keys=None):
    """One fused-kernel CHOCO round for a stacked leaf [m, ...] — see
    kernels/choco_fused.py.  Returns (theta_new, hat_new, s_new).

    ``topology`` is anything with a circulant ``.shifts`` decomposition (a
    :class:`~repro.core.topology.Topology` or ``PermutePlan``); ``roll_fn``/
    ``node_keys`` are the SPMD backend's injection points (the kernels then
    operate on the device-local node block)."""
    from repro.kernels.choco_fused import fused_round_leaf

    if interpret is None:
        interpret = _interpret_default()
    return fused_round_leaf(leaf, hat, s, key, topology.shifts, gamma, bits,
                            interpret=interpret, roll_fn=roll_fn,
                            node_keys=node_keys)


def block_topk(x: jax.Array, fraction: float = 0.25, block: int = 1024, interpret: bool | None = None):
    """Dense blockwise top-k sparsification of a tensor (any shape)."""
    if interpret is None:
        interpret = _interpret_default()
    flat = x.reshape(-1).astype(jnp.float32)
    d = flat.shape[0]
    pad = (-d) % block
    rows = jnp.pad(flat, (0, pad)).reshape(-1, block)
    k = max(1, int(round(fraction * block)))
    out = tk.block_topk_pallas(rows, k, interpret=interpret)
    return out.reshape(-1)[:d].reshape(x.shape).astype(x.dtype)


# ------------------------------------------------------------ gossip plugins
@dataclasses.dataclass(frozen=True)
class KernelQuantization(Compressor):
    """RandomQuantization backed by the Pallas kernel (packed wire format).

    The payload that crosses the gossip collective is the *packed* uint8
    levels + uint8 sign bitmask: (bits + 1)/8 bytes per element instead of 4.

    Supports the single-pass fused gossip round (``fused_round``): the whole
    CHOCO averaging + encode + multi-shift dequant-accumulate runs in two
    Pallas kernels instead of ~8+deg full-tensor HBM passes.
    """

    bits: int = 4
    interpret: bool | None = None

    # capability flag checked by gossip.choco_round's fused dispatch
    supports_fused_round = True

    def fused_round(self, leaf, hat, s, key, topology, gamma):
        """Fused-kernel round for one stacked leaf; see choco_fused.py."""
        return fused_choco_round_leaf(
            leaf, hat, s, key, topology, gamma, self.bits, self.interpret
        )

    @property
    def delta(self):
        return 0.0  # see delta_for

    def delta_for(self, d: int) -> float:
        lvl = float(2**self.bits)
        return 1.0 / (1.0 + min(d / lvl**2, (d**0.5) / lvl))

    def encode(self, x, key=None):
        if key is None:
            key = jax.random.PRNGKey(0)
        return quantize(x, key, self.bits, self.interpret)

    def decode(self, payload, shape, dtype):
        return dequantize(payload, shape, dtype, self.bits, self.interpret)

    def bits_per_element(self, d):
        return self.bits + 1 + 32.0 / max(d, 1)


@dataclasses.dataclass(frozen=True)
class KernelBlockTopK(Compressor):
    """BlockTopK backed by the Pallas bisection kernel.

    encode returns the dense masked residual (the sparse gather to
    values+indices wire format is a separate XLA gather, exercised by the
    core BlockTopK class); contraction factor matches fraction.
    """

    fraction: float = 0.25
    block: int = 1024
    interpret: bool | None = None

    @property
    def delta(self):
        return self.fraction

    def encode(self, x, key=None):
        return block_topk(x, self.fraction, self.block, self.interpret)

    def decode(self, payload, shape, dtype):
        return payload.reshape(shape).astype(dtype)

    def bits_per_element(self, d):
        import math

        return (32.0 + math.log2(self.block)) * self.fraction


# ---------------------------------------------------------- flash attention
def flash_attention(
    q: jax.Array,  # [B, Sq, H, hd]
    k: jax.Array,  # [B, Sk, H, hd] (kv heads already repeated to H)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention over [B, S, H, hd] layouts (the model's convention).

    Folds (B, H) into the grid's parallel dimension, pads Sq/Sk to block
    multiples, and unpads the output.  On TPU this replaces the XLA
    attention path (layers.ATTENTION_IMPL = "flash"); on CPU it runs the
    Pallas interpreter and serves as the correctness oracle.
    """
    from repro.kernels.flash_attention import flash_attention_pallas

    if interpret is None:
        interpret = _interpret_default()
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Sk))
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], hd)

    qf, kf, vf = fold(q), fold(k), fold(v)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
        # padded keys must never win the max: causal masking handles pad_q
        # rows, but pad_k columns need masking via the window/causal path —
        # padded positions are beyond every real query position, so causal
        # masking already excludes them.  For non-causal, exclude by window.
        assert causal or window is not None, "non-causal flash requires exact Sk blocks"
    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, window=window, block_q=bq, block_k=bk,
        interpret=interpret,
    )
    if pad_q:
        out = out[:, :Sq]
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


def sliding_window_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, S, H, hd] (kv heads already repeated to H)
    v: jax.Array,
    *,
    window: int,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal sliding-window attention over the model's [B, S, H, hd] layout.

    Unlike ``flash_attention`` this grids over the kv window band, so VMEM
    stays O(window) instead of O(S) and fully-out-of-window kv blocks are
    never fetched from HBM — the long-context local_attn fast path.
    """
    from repro.kernels.sliding_window import sliding_window_attention_pallas

    if interpret is None:
        interpret = _interpret_default()
    B, S, H, hd = q.shape
    bq = min(block_q, max(8, S))
    bk = min(block_k, max(8, S))
    pad = (-S) % max(bq, bk)

    def fold(x):
        x = x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    out = sliding_window_attention_pallas(
        fold(q), fold(k), fold(v), window=window, block_q=bq, block_k=bk,
        interpret=interpret,
    )
    if pad:
        out = out[:, :S]
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def block_sparse_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,
    v: jax.Array,
    pattern,  # BlockSparsePattern — must match the padded sequence length
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Block-sparse attention over [B, S, H, hd]; the pattern's bitmap picks
    which (q-block, kv-block) tiles are computed (see kernels/block_sparse.py).
    """
    from repro.kernels.block_sparse import block_sparse_attention_pallas

    if interpret is None:
        interpret = _interpret_default()
    B, S, H, hd = q.shape
    assert pattern.seq_q == pattern.seq_k == S, (pattern.seq_q, S)

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    out = block_sparse_attention_pallas(
        fold(q), fold(k), fold(v), pattern, interpret=interpret
    )
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def decode_attention_kernel(
    q: jax.Array,  # [B, 1, H, hd] — single decode-step query
    k: jax.Array,  # [B, L, KV, hd] cache (int8 when k_scale given)
    v: jax.Array,
    valid: jax.Array,  # [B, L] live cache slots
    *,
    k_scale: jax.Array | None = None,  # [B, L, KV] f32
    v_scale: jax.Array | None = None,
    impl: str | None = None,  # "pallas" | "xla_fused" | None (auto)
    interpret: bool | None = None,
) -> jax.Array:
    """Fused decode attention over the serving cache layout -> [B, 1, H, hd].

    Grouped-query heads are handled inside the kernel (no materialized
    ``_repeat_kv``); with ``k_scale``/``v_scale`` the cache is int8 and
    dequant fuses into the contractions.  ``impl`` auto-resolves to the
    Pallas kernel on TPU when ``hd`` is a multiple of 128 and to the
    fused-XLA twin otherwise (interpret-mode Pallas is a correctness
    oracle, not a serving fast path).
    """
    from repro.kernels.decode import (
        decode_attention_fused_xla,
        decode_attention_pallas,
    )

    B, one, H, hd = q.shape
    assert one == 1, q.shape
    KV = k.shape[2]
    G = H // KV
    # model convention (see layers._repeat_kv): q heads are kv-major — head
    # j*G+g belongs to kv head j — so [B,1,H,hd] reshapes straight to groups
    qg = q.reshape(B, KV, G, hd)
    if impl is None:
        # the kernel's per-head lane blocks need hd % 128 == 0 on the chip
        on_chip = not _interpret_default() and hd % LANES == 0
        impl = "pallas" if on_chip else "xla_fused"
    if impl == "pallas":
        if interpret is None:
            interpret = _interpret_default()
        L = k.shape[1]
        # a lane-dense slab, or the whole cache when L has no such divisor:
        # both are legal TPU blocks
        block_l = next((b for b in (512, 256, 128) if L % b == 0), L)
        out = decode_attention_pallas(
            qg, k, v, valid, k_scale=k_scale, v_scale=v_scale,
            block_l=block_l, interpret=interpret,
        )
    else:
        out = decode_attention_fused_xla(
            qg, k, v, valid, k_scale=k_scale, v_scale=v_scale
        )
    return out.reshape(B, 1, H, hd)


def quantize_kv(x: jax.Array):
    """Per-(position, kv-head) int8 symmetric KV quantization; see
    ref.quantize_kv_ref.  x: [..., hd] -> (int8 [..., hd], f32 scales [...])."""
    from repro.kernels.ref import quantize_kv_ref

    return quantize_kv_ref(x)
