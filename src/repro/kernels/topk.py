"""Pallas-TPU blockwise top-k sparsification kernel.

TPU adaptation of the paper's top-K compression: selection happens per
compression block (default 1024 elements) via **threshold bisection** —
``BISECT_ITERS`` rounds of (compare + row-sum), all VPU-friendly vector ops,
instead of a global sort/top-k which TPUs execute poorly.  The contraction
guarantee is preserved blockwise: keeping the top k_b = fraction*B entries of
every block removes at most (1-fraction) of every block's energy, hence
delta = K/d overall (see ``repro.core.compression.BlockTopK``).

Grid layout: x is reshaped to [num_blocks, block] and tiled in groups of
``TILE_BLOCKS`` rows; block (the compression block, lane dim) must be a
multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import BISECT_ITERS

TILE_BLOCKS = 256  # rows per grid step: 256 * 1024 * 4B = 1 MiB VMEM


def _block_topk_kernel(x_ref, out_ref, *, k: int, iters: int):
    x = x_ref[...]
    mag = jnp.abs(x)
    hi = mag.max(axis=1, keepdims=True)
    lo = jnp.zeros_like(hi)
    for _ in range(iters):  # static unroll: pure vector compare + row reduce
        mid = 0.5 * (lo + hi)
        cnt = (mag >= mid).sum(axis=1, keepdims=True)
        too_many = cnt > k
        lo = jnp.where(too_many, mid, lo)
        hi = jnp.where(too_many, hi, mid)
    mask = mag >= hi
    out_ref[...] = x * mask.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("k", "iters", "interpret"))
def block_topk_pallas(x: jax.Array, k: int, iters: int = BISECT_ITERS, *, interpret: bool):
    """x: [num_blocks, block] f32; returns same shape, masked to ~top-k per row."""
    assert x.ndim == 2 and x.shape[1] % 128 == 0
    nb, block = x.shape
    tile = min(TILE_BLOCKS, nb)
    while nb % tile != 0:
        tile //= 2
    tile = max(tile, 1)
    grid = (nb // tile,)
    return pl.pallas_call(
        functools.partial(_block_topk_kernel, k=k, iters=iters),
        grid=grid,
        in_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), x.dtype),
        interpret=interpret,
    )(x)
