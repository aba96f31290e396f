"""Pure-jnp oracles for the Pallas compression kernels.

These are the ground truth the kernels are tested against (allclose across a
shape/dtype sweep with ``interpret=True``).  They implement *exactly* the same
algorithm as the kernels:

* ``quantize_ref`` / ``dequantize_ref`` — stochastic b-bit quantization with
  2^b levels {0..2^b-1} (one fewer than paper eq. (2), so levels pack into
  b bits exactly; the contraction delta changes by O(2^-b), negligible),
  plus bit-packing: ``8/bits`` levels per uint8 and 8 sign bits per uint8.
* ``block_topk_ref`` — per-block top-k selection via N-iteration threshold
  bisection (the TPU-native form of top-k: vector compares + row reductions,
  no sort).  Keeps all entries with |x| >= tau where tau is the bisection
  threshold whose kept-count is <= k; ties below may drop extra elements,
  exactly as the kernel does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
BISECT_ITERS = 20


# ----------------------------------------------------------------- quantize
def pack_bits(vals: jax.Array, width: int) -> jax.Array:
    """Fold ``8 // width`` consecutive rows of ``width``-bit int32 values
    [..., rows, 128] into one uint8 row -> [..., rows * width / 8, 128].

    The lanes stay int32 until the final narrowing cast: the TPU compiler
    has no float<->uint32 conversion, so the kernels and this oracle share
    one int32 path (values are < 2^8, so int32 and uint32 agree bit for bit).
    """
    per_byte = 8 // width
    *lead, rows, lanes = vals.shape
    v = vals.reshape(*lead, rows // per_byte, per_byte, lanes)
    shifts = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 2) * width
    return (v << shifts).sum(axis=-2).astype(jnp.uint8)


def unpack_bits(packed: jax.Array, width: int) -> jax.Array:
    """Inverse of :func:`pack_bits`: uint8 [..., prows, 128] -> int32
    [..., prows * 8 / width, 128] in {0, ..., 2^width - 1}."""
    per_byte = 8 // width
    *lead, prows, lanes = packed.shape
    p = jnp.broadcast_to(
        packed.astype(jnp.int32)[..., None, :], (*lead, prows, per_byte, lanes)
    )
    shifts = jax.lax.broadcasted_iota(jnp.int32, p.shape, p.ndim - 2) * width
    return ((p >> shifts) & ((1 << width) - 1)).reshape(*lead, prows * per_byte, lanes)


def ordered_norm(x: jax.Array) -> jax.Array:
    """L2 norm over the last axis of f32 ``x`` [..., d] with a fixed
    summation order: pad to [..., rows, 128] lanes, then halve rows and then
    lanes with elementwise adds.  XLA never reassociates those adds, so the
    norm (and with it every quantization scale) is the same bits in any
    program the round is compiled into, whatever the batch shape around it
    or the backend.  ``jnp.linalg.norm`` leaves the order to the compiler,
    which picks it per program."""
    d = x.shape[-1]
    pad = (-d) % LANES
    sq = x.astype(jnp.float32) ** 2
    if pad:
        sq = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    sq = sq.reshape(*x.shape[:-1], -1, LANES)
    rows = lambda a, lo, hi: jax.lax.slice_in_dim(a, lo, hi, axis=-2)
    while sq.shape[-2] > 1:
        if sq.shape[-2] % 2:  # adding a zero row is exact
            sq = jnp.concatenate([sq, jnp.zeros_like(rows(sq, 0, 1))], axis=-2)
        half = sq.shape[-2] // 2
        sq = rows(sq, 0, half) + rows(sq, half, 2 * half)
    sq = sq[..., 0, :]
    while sq.shape[-1] > 1:
        half = sq.shape[-1] // 2
        sq = sq[..., :half] + sq[..., half:]
    return jnp.sqrt(sq[..., 0])


def quantize_ref(x: jax.Array, xi: jax.Array, norm: jax.Array, bits: int):
    """Quantize a [rows, 128] f32 array (pre-padded, pre-scaled noise xi in [0,1)).

    Returns (packed_levels [rows/pack, 128] uint8, packed_signs [rows/8, 128] uint8).
    """
    assert x.ndim == 2 and x.shape[1] == LANES
    maxlvl = (1 << bits) - 1
    scale = (1 << bits) / jnp.maximum(norm, 1e-30)
    q = jnp.floor(jnp.abs(x) * scale + xi)
    lvl = jnp.clip(q, 0, maxlvl).astype(jnp.int32)
    sign = (x < 0).astype(jnp.int32)
    return pack_bits(lvl, bits), pack_bits(sign, 1)


def tau_for(d: int, bits: int) -> float:
    """Paper eq. (2) normalizer: tau = 1 + min(d/2^2b, sqrt(d)/2^b)."""
    lvl = float(1 << bits)
    return 1.0 + min(d / lvl**2, (d**0.5) / lvl)


def dequantize_ref(packed_lvl: jax.Array, packed_sign: jax.Array, scale: jax.Array, bits: int):
    """Inverse of quantize_ref -> [rows, 128] f32 reconstruction.

    ``scale`` = norm / (2^b * tau): the paper's 1/tau shrinkage makes the
    roundtrip a delta = 1/tau contraction (without it the unbiased decode has
    variance (tau-1)||x||^2, which explodes for small b / large d).
    """
    lvl = unpack_bits(packed_lvl, bits).astype(jnp.float32)
    sign = unpack_bits(packed_sign, 1)
    mag = lvl * scale
    return jnp.where(sign == 1, -mag, mag)


# ---------------------------------------------------- fused CHOCO round oracles
def fused_encode_ref(theta_new, hat, xi, scales, bits: int):
    """Oracle for choco_fused.fused_encode_pallas.

    theta_new/hat: [m, rows, 128], xi: [m, rows, 128] f32, scales: [m, 2]
    (encode scale 2^b/||resid||, dequant scale ||resid||/(2^b tau)).
    Returns (packed_lvl [m, rows/pack, 128] u8, packed_sign [m, rows/8, 128]
    u8, hat_new [m, rows, 128]).
    """
    resid = (theta_new - hat).astype(jnp.float32)
    q = jnp.floor(jnp.abs(resid) * scales[:, 0, None, None] + xi)
    lvlf = jnp.clip(q, 0, (1 << bits) - 1)
    neg = resid < 0

    packed_lvl = pack_bits(lvlf.astype(jnp.int32), bits)
    packed_sign = pack_bits(neg.astype(jnp.int32), 1)
    mag = lvlf * scales[:, 1, None, None]
    hat_new = (hat.astype(jnp.float32) + jnp.where(neg, -mag, mag)).astype(hat.dtype)
    return packed_lvl, packed_sign, hat_new


def fused_mix_ref(rolled_lvl, rolled_sign, s, wscale, bits: int):
    """Oracle for choco_fused.fused_mix_pallas.

    rolled_lvl: [K, m, rows/pack, 128] u8, rolled_sign: [K, m, rows/8, 128]
    u8, s: [m, rows, 128], wscale: [K, m] f32.  Returns s_new [m, rows, 128]:
    s + sum_k deq(payload_k) * wscale[k].
    """
    K, m = rolled_lvl.shape[:2]
    acc = jnp.zeros(s.shape, jnp.float32)
    for k in range(K):
        lvl = jax.vmap(lambda pl_, ps_: dequantize_ref(pl_, ps_, 1.0, bits))(
            rolled_lvl[k], rolled_sign[k]
        )
        acc = acc + lvl * wscale[k, :, None, None]
    return (s.astype(jnp.float32) + acc).astype(s.dtype)


# ---------------------------------------------------------------- block top-k
def block_topk_ref(x: jax.Array, k: int, iters: int = BISECT_ITERS) -> jax.Array:
    """Per-row top-k masking via threshold bisection; x: [nb, block] f32.

    Returns x masked to (approximately, ties aside) its k largest-|.| entries
    per row.
    """
    assert x.ndim == 2
    mag = jnp.abs(x)
    hi = mag.max(axis=1, keepdims=True)
    lo = jnp.zeros_like(hi)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        cnt = (mag >= mid).sum(axis=1, keepdims=True)
        too_many = cnt > k
        lo = jnp.where(too_many, mid, lo)
        hi = jnp.where(too_many, hi, mid)
    mask = mag >= hi
    return x * mask.astype(x.dtype)


# -------------------------------------------------------- flash attention
def flash_attention_ref(q, k, v, *, causal=True, window=None, scale=None):
    """Pure-jnp oracle for the flash attention kernel.

    q, k, v: [BH, S, hd].  Plain materialized-softmax attention with the
    same causal/sliding-window masking.
    """
    import math

    hd = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqk,bsk->bqs", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qpos = jnp.arange(q.shape[1])
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqs,bsk->bqk", p, v.astype(jnp.float32)).astype(q.dtype)


# --------------------------------------------------- block-sparse attention
def block_sparse_attention_ref(q, k, v, pattern, *, scale=None):
    """Oracle for block_sparse.block_sparse_attention_pallas.

    Expands the pattern's block bitmap to an element mask (block-live AND
    causal/window for PARTIAL blocks) and runs materialized-softmax
    attention.  Patterns keep the diagonal live, so every q row has >= 1
    live key and the softmax is well-defined.
    """
    import math

    hd = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    sq, sk = q.shape[1], k.shape[1]
    bq, bk = pattern.block_q, pattern.block_k
    block = jnp.asarray(pattern.bitmap)  # [nq, nk]
    block_live = jnp.repeat(jnp.repeat(block != 0, bq, axis=0), bk, axis=1)
    block_full = jnp.repeat(jnp.repeat(block == 2, bq, axis=0), bk, axis=1)
    qpos = jnp.arange(sq)
    kpos = jnp.arange(sk)
    elem = jnp.ones((sq, sk), bool)
    if pattern.causal:
        elem &= qpos[:, None] >= kpos[None, :]
    if pattern.window is not None:
        elem &= qpos[:, None] - kpos[None, :] < pattern.window
    mask = block_live & (block_full | elem)
    s = (
        jnp.einsum("bqk,bsk->bqs", q.astype(jnp.float32), k.astype(jnp.float32))
        * scale
    )
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqs,bsk->bqk", p, v.astype(jnp.float32)).astype(q.dtype)


# ------------------------------------------------------------- decode attention
def quantize_kv_ref(x: jax.Array):
    """Per-(position, kv-head) int8 symmetric quantization of a KV tensor.

    x: [..., hd] -> (int8 values [..., hd], f32 scales [...]).  scale =
    absmax/127 so dequant is ``values * scale``; all-zero rows get scale 0
    and dequant back to exact zeros.
    """
    absmax = jnp.abs(x.astype(jnp.float32)).max(axis=-1)
    scale = absmax / 127.0
    inv = jnp.where(absmax > 0, 127.0 / absmax, 0.0)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) * inv[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def decode_attention_ref(q, k, v, valid, *, scale=None, k_scale=None,
                         v_scale=None):
    """Oracle for decode.decode_attention_pallas.

    Single-query attention over a KV cache with grouped-query heads:
      q: [B, KV, G, hd]           (G = query heads per kv head)
      k, v: [B, L, KV, hd]        (f32/bf16, or int8 when *_scale given)
      valid: [B, L] bool          live cache slots
      k_scale, v_scale: [B, L, KV] f32 — when given, k/v are int8 and
        dequant is fused into the contractions (the kernel's quantized-KV
        mode: the cache is read once at 1/4 the bytes).
    Returns [B, KV, G, hd] f32-accumulated in q.dtype.
    """
    import math

    hd = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bngd,blnd->bngl", q.astype(jnp.float32) * scale, kf)
    if k_scale is not None:
        s = s * k_scale.transpose(0, 2, 1)[:, :, None, :]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * v_scale.transpose(0, 2, 1)[:, :, None, :]
    return jnp.einsum("bngl,blnd->bngd", p, vf).astype(q.dtype)
