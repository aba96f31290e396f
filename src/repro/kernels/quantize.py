"""Pallas-TPU stochastic b-bit quantization kernel (fused quantize + bit-pack).

The compression operator is AD-GDA's per-step hot spot: it touches every
parameter every round (d ~ 1e9 for the large assigned archs).  The kernel
fuses scale -> stochastic round -> clip -> bit-pack (levels) -> bit-pack
(signs) in one VMEM pass, so HBM traffic is read 4B/elem + write
(bits+1)/8 B/elem instead of several full-size round trips.

Layout: the flat vector is reshaped to [rows, 128] (lane-aligned) and tiled
over the grid in row-blocks of ``BLOCK_ROWS`` (VMEM footprint per step:
BLOCK_ROWS * 128 * 4B * 2 inputs ~= 1 MiB).  The per-tensor scale rides
in a lane-broadcast row.  Packing is a sublane reshape: ``pack = 8 // bits``
level rows fold into one uint8 row; 8 sign rows fold into one bitmask row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import LANES, pack_bits, unpack_bits

BLOCK_ROWS = 512  # f32 VMEM tile: 512*128*4B = 256 KiB per operand


def _lane_row(scalar):
    return jnp.broadcast_to(jnp.asarray(scalar, jnp.float32).reshape(1, 1), (1, LANES))


def _quantize_kernel(scale_ref, x_ref, xi_ref, lvl_ref, sign_ref, *, bits: int):
    maxlvl = (1 << bits) - 1
    x = x_ref[...]
    xi = xi_ref[...]

    q = jnp.floor(jnp.abs(x) * scale_ref[...] + xi)
    lvl_ref[...] = pack_bits(jnp.clip(q, 0, maxlvl).astype(jnp.int32), bits)
    sign_ref[...] = pack_bits((x < 0).astype(jnp.int32), 1)


def _dequantize_kernel(scale_ref, lvl_ref, sign_ref, out_ref, *, bits: int):
    lvl = unpack_bits(lvl_ref[...], bits).astype(jnp.float32)
    sign = unpack_bits(sign_ref[...], 1)
    mag = lvl * scale_ref[...]
    out_ref[...] = jnp.where(sign == 1, -mag, mag)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_pallas(x: jax.Array, xi: jax.Array, norm: jax.Array, bits: int, interpret: bool):
    """x, xi: [rows, 128] f32 (rows % (8*pack*BLOCK alignment) handled by caller).

    Returns (packed_levels [rows/pack, 128] u8, packed_signs [rows/8, 128] u8).
    """
    assert x.shape[1] == LANES and x.shape[0] % (8 * (8 // bits)) == 0
    rows = x.shape[0]
    pack = 8 // bits
    block = min(BLOCK_ROWS, rows)
    assert rows % block == 0 and block % (8 * pack) == 0
    grid = (rows // block,)
    # the encode scale is computed here, in XLA, exactly as the fused round
    # computes it, and rides as a lane-broadcast (1, 128) row: a VMEM row
    # stays a legal block when vmap adds a node axis (an SMEM scalar would
    # become a (1,)-block over [m, 1])
    scale = _lane_row((1 << bits) / jnp.maximum(norm.astype(jnp.float32), 1e-30))
    return pl.pallas_call(
        functools.partial(_quantize_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
            pl.BlockSpec((block, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block // pack, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block // 8, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows // pack, LANES), jnp.uint8),
            jax.ShapeDtypeStruct((rows // 8, LANES), jnp.uint8),
        ],
        interpret=interpret,
    )(scale, x, xi)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def dequantize_pallas(packed_lvl, packed_sign, scale, bits: int, interpret: bool):
    """scale = norm / (2^b * tau) — see ref.tau_for."""
    pack = 8 // bits
    prows = packed_lvl.shape[0]
    rows = prows * pack
    block = min(BLOCK_ROWS // pack, prows)
    assert prows % block == 0
    grid = (prows // block,)
    scale_row = _lane_row(scale)
    return pl.pallas_call(
        functools.partial(_dequantize_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
            pl.BlockSpec((block, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block * pack // 8, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block * pack, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(scale_row, packed_lvl, packed_sign)
