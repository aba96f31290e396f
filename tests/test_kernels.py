"""Pallas kernels vs pure-jnp oracles: shape/dtype sweep, interpret=True."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.quantize import dequantize_pallas, quantize_pallas
from repro.kernels.topk import block_topk_pallas

KEY = jax.random.PRNGKey(42)


# ----------------------------------------------------------------- quantize
@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("rows", [64, 512, 1536])
def test_quantize_kernel_matches_ref(bits, rows):
    x = jax.random.normal(KEY, (rows, 128), jnp.float32)
    xi = jax.random.uniform(jax.random.PRNGKey(1), x.shape)
    norm = jnp.linalg.norm(x)
    lvl_k, sign_k = quantize_pallas(x, xi, norm, bits, interpret=True)
    lvl_r, sign_r = ref.quantize_ref(x, xi, norm, bits)
    np.testing.assert_array_equal(np.asarray(lvl_k), np.asarray(lvl_r))
    np.testing.assert_array_equal(np.asarray(sign_k), np.asarray(sign_r))


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_dequantize_kernel_matches_ref(bits):
    x = jax.random.normal(KEY, (512, 128), jnp.float32)
    xi = jax.random.uniform(jax.random.PRNGKey(1), x.shape)
    norm = jnp.linalg.norm(x)
    lvl, sign = ref.quantize_ref(x, xi, norm, bits)
    scale = norm / ((1 << bits) * ref.tau_for(x.size, bits))
    out_k = dequantize_pallas(lvl, sign, scale, bits, interpret=True)
    out_r = ref.dequantize_ref(lvl, sign, scale, bits)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-6)


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_quantize_roundtrip_contraction(bits):
    """Kernel roundtrip must satisfy the Assumption-3.2 style error bound."""
    x = jax.random.normal(KEY, (1024, 128), jnp.float32)
    xi = jax.random.uniform(jax.random.PRNGKey(1), x.shape)
    norm = jnp.linalg.norm(x)
    lvl, sign = quantize_pallas(x, xi, norm, bits, interpret=True)
    tau = ref.tau_for(x.size, bits)
    scale = norm / ((1 << bits) * tau)
    xhat = dequantize_pallas(lvl, sign, scale, bits, interpret=True)
    err = float(jnp.sum((xhat - x) ** 2) / jnp.sum(x**2))
    assert err <= (1 - 1 / tau) + 0.1


def test_quantize_wire_size():
    """Packed payload is (bits+1)/8 bytes per element."""
    x = jax.random.normal(KEY, (512, 128), jnp.float32)
    xi = jax.random.uniform(KEY, x.shape)
    lvl, sign = quantize_pallas(x, xi, jnp.linalg.norm(x), 4, interpret=True)
    assert lvl.size == x.size // 2  # 2 levels / byte
    assert sign.size == x.size // 8  # 8 signs / byte


# -------------------------------------------------------------------- top-k
@pytest.mark.parametrize("block", [128, 512, 1024])
@pytest.mark.parametrize("nb", [4, 64, 300])
def test_topk_kernel_matches_ref(block, nb):
    x = jax.random.normal(KEY, (nb, block), jnp.float32)
    k = max(1, block // 4)
    out_k = block_topk_pallas(x, k, interpret=True)
    out_r = ref.block_topk_ref(x, k)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_kernel_dtypes(dtype):
    x = jax.random.normal(KEY, (16, 256)).astype(dtype)
    out = block_topk_pallas(x, 64, interpret=True)
    assert out.dtype == dtype
    # kept entries match original values
    mask = np.asarray(out, np.float32) != 0
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[mask], np.asarray(x, np.float32)[mask], rtol=1e-3
    )


@pytest.mark.parametrize("frac", [0.1, 0.25, 0.5])
def test_topk_kernel_count_and_energy(frac):
    block = 1024
    x = jax.random.normal(KEY, (32, block), jnp.float32)
    k = int(frac * block)
    out = np.asarray(block_topk_pallas(x, k, interpret=True))
    counts = (out != 0).sum(axis=1)
    assert (counts <= k).all()
    assert (counts >= k - 8).all()  # bisection converges to within ties
    # contraction: per-row residual energy <= (1 - frac) * energy + tolerance
    xn = np.asarray(x)
    res = ((xn - out) ** 2).sum(1)
    tot = (xn**2).sum(1)
    assert (res <= (1 - frac) * tot * 1.05 + 1e-6).all()


def test_topk_keeps_largest_entries():
    x = jnp.zeros((1, 128)).at[0, 7].set(10.0).at[0, 100].set(-9.0).at[0, 55].set(0.01)
    out = np.asarray(block_topk_pallas(x, 2, interpret=True))[0]
    assert out[7] == 10.0 and out[100] == -9.0
    assert (out != 0).sum() == 2


# ---------------------------------------------------------------- ops layer
@pytest.mark.parametrize("shape", [(1000,), (33, 77), (8, 16, 25)])
@pytest.mark.parametrize("bits", [4, 8])
def test_ops_quantize_roundtrip_arbitrary_shapes(shape, bits):
    x = jax.random.normal(KEY, shape, jnp.float32)
    payload = ops.quantize(x, KEY, bits=bits)
    xhat = ops.dequantize(payload, shape, jnp.float32, bits=bits)
    assert xhat.shape == shape
    err = float(jnp.sum((xhat - x) ** 2) / jnp.sum(x**2))
    assert err < 0.9


@pytest.mark.parametrize("shape", [(4096,), (100, 41)])
def test_ops_block_topk_arbitrary_shapes(shape):
    x = jax.random.normal(KEY, shape, jnp.float32)
    out = ops.block_topk(x, fraction=0.25, block=512)
    assert out.shape == shape
    err = float(jnp.sum((out - x) ** 2) / jnp.sum(x**2))
    assert err <= 0.75 * 1.1


def test_kernel_compressors_plug_into_gossip():
    from repro.core import gossip, topology

    topo = topology.ring(4)
    comp = ops.KernelQuantization(bits=4, interpret=True)
    theta = {"w": jax.random.normal(KEY, (4, 640))}
    state = gossip.choco_init(theta)
    t, s = gossip.choco_round(theta, state, topo, 0.3, comp, KEY)
    assert t["w"].shape == (4, 640)
    # average preservation still holds with the kernel compressor
    np.testing.assert_allclose(
        np.asarray(t["w"].mean(0)), np.asarray(theta["w"].mean(0)), atol=1e-4
    )


def test_kernel_vs_core_block_topk_equivalence():
    """Kernel bisection selection ~= exact top-k from the core compressor."""
    from repro.core.compression import BlockTopK

    x = jax.random.normal(KEY, (2048,), jnp.float32)
    exact = BlockTopK(fraction=0.25, block=512)(x)
    kern = ops.block_topk(x, fraction=0.25, block=512)
    # selections may differ at the threshold boundary; energies must agree
    e_exact = float(jnp.sum(exact**2))
    e_kern = float(jnp.sum(kern**2))
    assert abs(e_exact - e_kern) / e_exact < 0.02


# ------------------------------------------------------- payload primitives
@pytest.mark.parametrize("width", [1, 2, 4, 8])
def test_pack_bits_roundtrip_and_layout(width):
    """int32 packing folds 8 // width consecutive rows into one uint8 row,
    low bits first (the wire format the uint32 formulation produced)."""
    per_byte = 8 // width
    vals = jax.random.randint(KEY, (3, 8 * per_byte, 128), 0, 1 << width, jnp.int32)
    packed = ref.pack_bits(vals, width)
    assert packed.dtype == jnp.uint8 and packed.shape == (3, 8, 128)
    v = np.asarray(vals).reshape(3, 8, per_byte, 128).astype(np.uint32)
    want = sum(v[:, :, j] << (j * width) for j in range(per_byte)).astype(np.uint8)
    np.testing.assert_array_equal(np.asarray(packed), want)
    np.testing.assert_array_equal(np.asarray(ref.unpack_bits(packed, width)), np.asarray(vals))


@pytest.mark.parametrize("d", [7, 300, 128 * 37 + 5])
def test_ordered_norm_is_program_independent(d):
    """The quantization norm has one value per row whatever program computes
    it: batched or vmapped, inside a bigger batch or a slice of it."""
    x = jax.random.normal(KEY, (8, d), jnp.float32)
    full = np.asarray(jax.jit(ref.ordered_norm)(x))
    sliced = np.asarray(jax.jit(ref.ordered_norm)(x[:2]))
    vmapped = np.asarray(jax.jit(jax.vmap(ref.ordered_norm))(x))
    eager = np.asarray(ref.ordered_norm(x[5]))
    np.testing.assert_array_equal(full[:2], sliced)
    np.testing.assert_array_equal(full, vmapped)
    assert full[5] == eager
    np.testing.assert_allclose(full, np.linalg.norm(np.asarray(x, np.float64), axis=1), rtol=1e-6)
