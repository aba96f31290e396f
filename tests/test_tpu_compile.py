"""The main path's Pallas kernels compile for a described TPU v5e chip.

Each test lowers one kernel at qwen3-1.7b widths with ``interpret=False``
and compiles it for a v5e chip that is described, not attached: the TPU
compiler installed with JAX refuses what the chip would refuse (unsupported
casts, blocks not aligned to the (8, 128) tiling, VMEM over the scoped
limit).  Nothing runs, so these tests say nothing about results or times;
the interpret-mode tests pin the numerics.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, so a description made while
pytest-xdist workers import this file would fail in all but one of them.
The persistent compilation cache is off around these compiles (an entry
compiled for a described chip cannot be read back without one).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import topology
from repro.kernels import ops
from repro.kernels.block_sparse import BlockSparsePattern
from repro.kernels.choco_fused import fused_round_leaf

# qwen3-1.7b widths
D_MODEL, D_FF, HEADS, KV_HEADS, HD = 2048, 6144, 16, 8, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler installed
            pytest.skip(f"no v5e topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in the program"
    return compiled


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _key(sharding):
    return _spec(sharding, (2,), jnp.uint32)


def test_quantize_dequantize_compile(one_chip):
    leaf = (D_MODEL, D_FF)
    payload = jax.eval_shape(
        lambda x, k: ops.quantize(x, k, 4, interpret=True),
        jax.ShapeDtypeStruct(leaf, jnp.float32), jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    _compile(
        lambda x, k: ops.quantize(x, k, 4, interpret=False),
        _spec(one_chip, leaf, jnp.float32), _key(one_chip),
    )
    _compile(
        lambda p: ops.dequantize(p, leaf, jnp.float32, 4, interpret=False),
        jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), payload),
    )


@pytest.mark.parametrize("m", [2, 4])
def test_fused_choco_round_compile(one_chip, m):
    """Fused encode + multi-shift mix on a stacked bf16 leaf of m nodes."""
    shape = (m, D_MODEL, D_FF)
    leaf = _spec(one_chip, shape, jnp.bfloat16)
    shifts = topology.ring(m).shifts
    compiled = _compile(
        lambda t, h, s, k: fused_round_leaf(t, h, s, k, shifts, 0.5, 4, interpret=False),
        leaf, leaf, leaf, _key(one_chip),
    )
    assert compiled.as_text().count("tpu_custom_call") >= 2  # encode and mix


def test_packed_choco_round_compile(one_chip, monkeypatch):
    """The packed kq4b round vmaps quantize/dequantize over the node axis,
    which adds a grid axis to every operand's block."""
    from repro.core import gossip

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    m = 2
    leaf = {"w": _spec(one_chip, (m, D_MODEL, D_FF), jnp.float32)}
    state = gossip.CHOCOState(theta_hat=leaf, s=leaf)
    comp = ops.KernelQuantization(bits=4)
    _compile(
        lambda t, s, k: gossip.choco_round(t, s, topology.ring(m), 0.5, comp, k, packed=True),
        leaf, state, _key(one_chip),
    )


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_attention_compile(one_chip, quantized):
    B, L = 8, 4096
    q = _spec(one_chip, (B, 1, HEADS, HD), jnp.bfloat16)
    valid = _spec(one_chip, (B, L), jnp.bool_)
    if quantized:
        kv = _spec(one_chip, (B, L, KV_HEADS, HD), jnp.int8)
        sc = _spec(one_chip, (B, L, KV_HEADS), jnp.float32)
        fn = lambda q, k, v, m, ks, vs: ops.decode_attention_kernel(
            q, k, v, m, k_scale=ks, v_scale=vs, impl="pallas", interpret=False)
        _compile(fn, q, kv, kv, valid, sc, sc)
    else:
        kv = _spec(one_chip, (B, L, KV_HEADS, HD), jnp.bfloat16)
        fn = lambda q, k, v, m: ops.decode_attention_kernel(
            q, k, v, m, impl="pallas", interpret=False)
        _compile(fn, q, kv, kv, valid)


def test_flash_attention_compile(one_chip):
    x = _spec(one_chip, (1, 4096, HEADS, HD), jnp.bfloat16)
    _compile(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True, interpret=False),
        x, x, x,
    )


def test_sliding_window_attention_compile(one_chip):
    x = _spec(one_chip, (1, 4096, HEADS, HD), jnp.bfloat16)
    _compile(
        lambda q, k, v: ops.sliding_window_attention(q, k, v, window=1024, interpret=False),
        x, x, x,
    )


def test_block_sparse_attention_compile(one_chip):
    S = 4096
    pattern = BlockSparsePattern.causal_pattern(S, S)
    x = _spec(one_chip, (1, S, HEADS, HD), jnp.bfloat16)
    _compile(
        lambda q, k, v: ops.block_sparse_attention(q, k, v, pattern, interpret=False),
        x, x, x,
    )
