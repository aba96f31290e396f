"""Fused single-pass Pallas kernels for one CHOCO gossip round.

The unfused CHOCO round (``core/gossip._round_leaf`` + ``_mix_payload``)
executes ~8+deg full-tensor HBM round trips per leaf: the averaging step, an
f32 residual, the quantize encode, a full dequantize for ``q_self``, one more
full dequantize per topology shift (each materializing a d-element f32
tensor), then separate ``hat`` and ``s`` update passes.  The two kernels here
collapse that to ~3 full-tensor passes plus wire-sized (packed) traffic:

* ``fused_encode_pallas`` — recompute the residual ``theta_new - hat_old`` in
  VMEM, stochastically quantize, bit-pack levels and signs, AND apply the
  ``hat <- hat + Q(resid)`` update, all in one pass.  The full-size f32
  residual and the dense ``q_self`` reconstruction never touch HBM.
* ``fused_mix_pallas`` — multi-shift dequantize-accumulate: decode each
  rolled packed payload tile and accumulate ``sum_k w_k * deq(payload_k)``
  directly into the ``s`` update.  Per-neighbor f32 tensors never
  materialize; the per-(shift, node) dequant scales ride alongside as a
  lane-broadcast row per node.

Both kernels grid over row-blocks only and keep the full node axis inside
each tile ([m, block, 128]): the stacked node axis is small (nodes) while d
is huge, so folding it into the tile amortizes per-step overhead m-fold and
keeps the grid identical in shape to the unfused quantize kernel's.  The
per-operand VMEM footprint is held at ~2 MiB by shrinking the row-block as m
grows (``_pick_block``).

``fused_round_leaf`` stitches them into a full round for one stacked leaf
[m, ...].  The averaging step and the residual norm stay in plain XLA (they
fuse into a read-only reduction) so the payload is bit-identical to the
``packed=False`` oracle path: the same per-node keys, the same uniform noise,
the same norm reduction, the same floor/clip arithmetic.

The packed payload is rolled along the node axis *outside* the kernels
(wire-sized traffic only).  Under the production mesh those rolls lower to
collective-permutes of the compressed payload, exactly like the unfused
packed path — the fused kernels only change the per-device compute.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.ref import LANES, ordered_norm, pack_bits, tau_for, unpack_bits

# target total VMEM footprint per grid step (inputs + outputs + accumulator)
VMEM_BUDGET = 8 << 20

# max circulant shifts decoded per fused_mix_pallas call: bounds both the
# live rolled-payload copies in HBM (<= SHIFT_BATCH x wire size, vs K = m
# copies on a mesh) and the static unroll inside the kernel
SHIFT_BATCH = 8


def _pick_block(rows: int, unit: int, m: int, f32_operands: float) -> int:
    """Largest multiple of ``unit`` dividing ``rows`` such that the tile's
    f32-equivalent footprint m * block * 128 * 4B * f32_operands stays within
    the VMEM budget.  ``f32_operands`` counts every live buffer in f32 units
    (u8 payload tiles count 1/4 per byte-per-element) — the mix kernel's K
    payload tiles make this K-dependent, not a constant."""
    cap_rows = int(VMEM_BUDGET / (max(m, 1) * LANES * 4 * f32_operands))
    cap = max(unit, cap_rows // unit * unit)
    best = unit
    b = unit
    while b <= min(rows, cap):
        if rows % b == 0:
            best = b
        b += unit
    return best


# ------------------------------------------------------------- fused encode
def _fused_encode_kernel(enc_ref, deq_ref, tn_ref, hat_ref, xi_ref,
                         lvl_ref, sign_ref, hat_new_ref, *maybe_dig,
                         bits: int, with_digest: bool = False):
    """One row-block across all m nodes: residual -> quantize -> pack -> hat
    update.  enc_ref/deq_ref: [m, 128] lane-broadcast per-node scales.

    With ``with_digest`` a per-node int32 wraparound digest of the stored
    ``hat_new`` accumulates in an extra [m, 128] output whose constant index
    map revisits the same tile every grid step (TPU grids are sequential, so
    the read-modify-write accumulation is well-defined)."""
    maxlvl = (1 << bits) - 1

    hat = hat_ref[...]
    resid = (tn_ref[...] - hat).astype(jnp.float32)

    # stochastic round (same arithmetic as quantize_ref, bit-identical)
    q = jnp.floor(jnp.abs(resid) * enc_ref[...][:, None, :] + xi_ref[...])
    lvlf = jnp.clip(q, 0, maxlvl)
    neg = resid < 0

    lvl_ref[...] = pack_bits(lvlf.astype(jnp.int32), bits)
    sign_ref[...] = pack_bits(neg.astype(jnp.int32), 1)

    # hat <- hat + deq(payload), without re-reading the packed payload
    mag = lvlf * deq_ref[...][:, None, :]
    q_self = jnp.where(neg, -mag, mag)
    stored = (hat.astype(jnp.float32) + q_self).astype(hat_new_ref.dtype)
    hat_new_ref[...] = stored

    if with_digest:
        (dig_ref,) = maybe_dig
        # same arithmetic as core.faults.digest: bitcast to same-width int,
        # widen to int32, wraparound-sum — int32 addition commutes, so the
        # per-block accumulation order doesn't matter
        nbits = stored.dtype.itemsize * 8
        part = (
            jax.lax.bitcast_convert_type(stored, jnp.dtype(f"int{nbits}"))
            .astype(jnp.int32)
            .sum(axis=1)
        )  # [m, 128]

        @pl.when(pl.program_id(0) == 0)
        def _zero():
            dig_ref[...] = jnp.zeros_like(dig_ref)

        dig_ref[...] += part


@functools.partial(jax.jit, static_argnames=("bits", "interpret", "with_digest"))
def fused_encode_pallas(theta_new, hat, xi, scales, bits: int,
                        interpret: bool, with_digest: bool = False):
    """theta_new/hat: [m, R, 128] (leaf dtype), xi: [m, R, 128] f32,
    scales: [m, 2] f32 — per-node (encode scale, dequant scale).

    Returns (packed_levels [m, R/pack, 128] u8, packed_signs [m, R/8, 128] u8,
    hat_new [m, R, 128] in hat.dtype), plus a per-node int32 digest [m] equal
    to ``core.faults.digest(hat_new)`` when ``with_digest`` — the fault lane
    rides the encode pass for free instead of a separate XLA reduction.
    """
    m, rows, lanes = theta_new.shape
    assert lanes == LANES
    pack = 8 // bits
    assert rows % (8 * pack) == 0
    # live buffers: tn, hat, xi, f32 resid, hat_new + packed outputs (~1/4)
    block = _pick_block(rows, 8 * pack, m, f32_operands=5.5)
    grid = (rows // block,)
    # lane-broadcast the per-node scales so the tile is (m, 128)-shaped
    enc = jnp.broadcast_to(scales[:, 0:1], (m, LANES)).astype(jnp.float32)
    deq = jnp.broadcast_to(scales[:, 1:2], (m, LANES)).astype(jnp.float32)
    row_spec = lambda div: pl.BlockSpec((m, block // div, LANES), lambda r: (0, r, 0))
    out_specs = [row_spec(pack), row_spec(8), row_spec(1)]
    out_shape = [
        jax.ShapeDtypeStruct((m, rows // pack, LANES), jnp.uint8),
        jax.ShapeDtypeStruct((m, rows // 8, LANES), jnp.uint8),
        jax.ShapeDtypeStruct((m, rows, LANES), hat.dtype),
    ]
    if with_digest:
        # constant index map: the digest tile is revisited (and accumulated
        # into) on every sequential grid step
        out_specs.append(pl.BlockSpec((m, LANES), lambda r: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((m, LANES), jnp.int32))
    out = pl.pallas_call(
        functools.partial(_fused_encode_kernel, bits=bits, with_digest=with_digest),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, LANES), lambda r: (0, 0)),
            pl.BlockSpec((m, LANES), lambda r: (0, 0)),
            row_spec(1),
            row_spec(1),
            row_spec(1),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(enc, deq, theta_new, hat, xi)
    if with_digest:
        lvl, sign, hat_new, dig = out
        return lvl, sign, hat_new, dig.sum(axis=1)
    return out


# --------------------------------------------------------------- fused mix
def _fused_mix_kernel(wscale_ref, lvl_ref, sign_ref, s_ref, s_new_ref,
                      *, bits: int, nshifts: int):
    """One row-block across all m nodes: decode every rolled payload tile and
    accumulate the weighted sum straight into the s update."""
    s_blk = s_ref[...]
    wscale = wscale_ref[...]
    acc = jnp.zeros(s_blk.shape, jnp.float32)
    for k in range(nshifts):  # static unroll over the circulant decomposition
        lvl = unpack_bits(lvl_ref[k], bits)
        sign = unpack_bits(sign_ref[k], 1)
        mag = lvl.astype(jnp.float32) * wscale[k][:, None, :]
        acc = acc + jnp.where(sign == 1, -mag, mag)
    s_new_ref[...] = (s_blk.astype(jnp.float32) + acc).astype(s_new_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def fused_mix_pallas(rolled_lvl, rolled_sign, s, wscale, bits: int, interpret: bool):
    """rolled_lvl: [K, m, R/pack, 128] u8, rolled_sign: [K, m, R/8, 128] u8,
    s: [m, R, 128] (leaf dtype), wscale: [K, m] f32 with
    wscale[k, i] = w_k * deq_scale[(i - shift_k) mod m].

    Returns s_new [m, R, 128]: s + sum_k w_k * deq(rolled payload_k).
    """
    K, m, prows, lanes = rolled_lvl.shape
    assert lanes == LANES and K == wscale.shape[0]
    pack = 8 // bits
    rows = prows * pack
    assert s.shape == (m, rows, LANES)
    # live f32-sized buffers: s and s_new (double-buffered), the f32
    # accumulator and the in-kernel temporaries, plus per unrolled shift its
    # unpacked int32 levels and signs and (double-buffered) u8 payload tiles
    # of (1/pack + 1/8) bytes per element — K-dependent (mesh has K = m
    # shifts).  Sized from the v5e compiler's scoped-VMEM report at
    # qwen3-1.7b widths (tests/test_tpu_compile.py).
    payload_f32 = 2 * K * (1.0 / pack + 0.125) / 4.0
    block = _pick_block(rows, 8 * pack, m, f32_operands=8.0 + 2.0 * K + payload_f32)
    grid = (rows // block,)
    ws = jnp.broadcast_to(wscale[..., None], (K, m, LANES)).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_fused_mix_kernel, bits=bits, nshifts=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, m, LANES), lambda r: (0, 0, 0)),
            pl.BlockSpec((K, m, block // pack, LANES), lambda r: (0, 0, r, 0)),
            pl.BlockSpec((K, m, block // 8, LANES), lambda r: (0, 0, r, 0)),
            pl.BlockSpec((m, block, LANES), lambda r: (0, r, 0)),
        ],
        out_specs=pl.BlockSpec((m, block, LANES), lambda r: (0, r, 0)),
        out_shape=jax.ShapeDtypeStruct((m, rows, LANES), s.dtype),
        interpret=interpret,
    )(ws, rolled_lvl, rolled_sign, s)


# ------------------------------------------------------------- leaf round
def fused_round_leaf(leaf, hat, s, key, shifts: Sequence[tuple[int, float]],
                     gamma, bits: int, interpret: bool, *,
                     roll_fn=None, node_keys=None, with_digest: bool = False):
    """One CHOCO round for a stacked leaf [m, ...] on the fused fast path.

    Matches ``gossip._round_leaf`` with a ``KernelQuantization(bits)``
    compressor bit-for-bit on the payload (same keys, noise, norms and
    floor/clip arithmetic); ``s_new`` agrees to f32 rounding (the weighted
    accumulation is reassociated inside the kernel).

    ``roll_fn(x, shift)`` overrides how the packed payload travels the node
    axis — the SPMD neighbor-exchange backend (core/exchange.py) substitutes
    sharded boundary permutes while the kernels run unchanged on the local
    node block; ``node_keys`` then carries that block's slice of the global
    per-node key array (the default is the full ``split(key, m)``).

    Returns (theta_new, hat_new, s_new), all shaped like ``leaf``; with
    ``with_digest`` a fourth element — the per-node int32 wraparound digest of
    ``hat_new``, equal to ``core.faults.digest(hat_new)`` (the zero padding
    rows quantize to exact zeros, so the padded-grid digest matches the
    unpadded one) — computed inside the encode pass at no extra HBM traffic.
    """
    m = leaf.shape[0]
    inner_shape, dtype = leaf.shape[1:], leaf.dtype
    d = int(np.prod(inner_shape)) if len(inner_shape) else 1

    # averaging step + residual norm stay in XLA: one fused read-only
    # reduction, and bit-identical numerics with the unfused oracle
    theta_new = leaf + jnp.asarray(gamma, dtype) * (s - hat).astype(dtype)
    flat_tn = theta_new.reshape(m, -1)
    flat_hat = hat.reshape(m, -1)
    norms = ordered_norm((flat_tn - flat_hat).astype(jnp.float32))

    pack = 8 // bits
    unit = 8 * pack * LANES
    pad = (-d) % unit
    rows = (d + pad) // LANES

    def grid3(x):
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        return x.reshape(m, rows, LANES)

    if node_keys is None:
        node_keys = jax.random.split(key, m)
    xi = jax.vmap(lambda k: jax.random.uniform(k, (rows, LANES)))(node_keys)

    scale_enc = (1 << bits) / jnp.maximum(norms, 1e-30)
    scale_deq = norms / ((1 << bits) * tau_for(d, bits))
    scales = jnp.stack([scale_enc, scale_deq], axis=1).astype(jnp.float32)

    enc_out = fused_encode_pallas(
        grid3(flat_tn), grid3(flat_hat), xi, scales, bits,
        interpret=interpret, with_digest=with_digest,
    )
    if with_digest:
        lvl, sign, hat_new_g, dig = enc_out
    else:
        lvl, sign, hat_new_g = enc_out

    # roll the *packed* payload along the node axis (wire-sized traffic;
    # lowers to collective-permute under a sharded node axis).  Shifts are
    # processed in batches of SHIFT_BATCH so a mesh (K = m shifts) never
    # materializes more than SHIFT_BATCH rolled payload copies at once.
    if roll_fn is None:
        roll0 = lambda x, sh: x if sh == 0 else jnp.roll(x, sh, axis=0)
    else:
        roll0 = lambda x, sh: x if sh == 0 else roll_fn(x, sh)
    # the accumulator stays f32 across batches (cast to the leaf dtype once
    # at the end), so multi-batch topologies match the oracle's
    # accumulate-everything-then-cast semantics for low-precision leaves too
    s_new_g = grid3(s.reshape(m, -1).astype(jnp.float32))
    shifts = tuple(shifts)
    for lo in range(0, len(shifts), SHIFT_BATCH):
        batch = shifts[lo:lo + SHIFT_BATCH]
        rolled_lvl = jnp.stack([roll0(lvl, sh) for sh, _ in batch])
        rolled_sign = jnp.stack([roll0(sign, sh) for sh, _ in batch])
        wscale = jnp.stack(
            [w * roll0(scale_deq, sh) for sh, w in batch]
        ).astype(jnp.float32)
        s_new_g = fused_mix_pallas(
            rolled_lvl, rolled_sign, s_new_g, wscale, bits, interpret=interpret
        )

    unpad = lambda x: x.reshape(m, -1)[:, :d].reshape((m,) + inner_shape)
    if with_digest:
        return theta_new, unpad(hat_new_g), unpad(s_new_g).astype(dtype), dig
    return theta_new, unpad(hat_new_g), unpad(s_new_g).astype(dtype)
