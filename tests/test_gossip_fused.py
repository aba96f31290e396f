"""Fused CHOCO gossip round: kernel-vs-ref oracles and bit-compatibility of
the fused choco_round fast path against the packed/unpacked reference paths."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gossip, topology
from repro.core.compression import make_compressor
from repro.kernels import choco_fused, ref
from repro.kernels.ops import KernelQuantization

KEY = jax.random.PRNGKey(0)


def _allclose_trees(a, b, atol):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol, rtol=0)


# ------------------------------------------------------- kernel-vs-ref oracles
@pytest.mark.parametrize("bits", [8, 4, 2])
def test_fused_encode_kernel_matches_ref(bits):
    m, rows = 4, 64
    k1, k2, k3 = jax.random.split(KEY, 3)
    tn = jax.random.normal(k1, (m, rows, ref.LANES))
    hat = 0.5 * jax.random.normal(k2, (m, rows, ref.LANES))
    xi = jax.random.uniform(k3, (m, rows, ref.LANES))
    norms = jnp.linalg.norm((tn - hat).reshape(m, -1), axis=1)
    scales = jnp.stack(
        [(1 << bits) / norms, norms / ((1 << bits) * ref.tau_for(rows * ref.LANES, bits))],
        axis=1,
    )
    lvl_k, sign_k, hat_k = choco_fused.fused_encode_pallas(
        tn, hat, xi, scales, bits, interpret=True
    )
    lvl_r, sign_r, hat_r = ref.fused_encode_ref(tn, hat, xi, scales, bits)
    np.testing.assert_array_equal(np.asarray(lvl_k), np.asarray(lvl_r))
    np.testing.assert_array_equal(np.asarray(sign_k), np.asarray(sign_r))
    np.testing.assert_allclose(np.asarray(hat_k), np.asarray(hat_r), atol=1e-6)


def _bits16(x):
    return np.asarray(x).view(np.uint16)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("bits", [8, 4])
def test_fused_mix_kernel_matches_ref(bits, dtype):
    """s in f32 (the multi-batch carry) or in the leaf dtype: the kernel
    returns s.dtype(f32(s) + acc), cast once after the f32 accumulation."""
    m, rows, K = 6, 32, 3
    pack = 8 // bits
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    lvl = jax.random.randint(k1, (K, m, rows // pack, ref.LANES), 0, 256, jnp.uint8)
    sign = jax.random.randint(k2, (K, m, rows // 8, ref.LANES), 0, 256, jnp.uint8)
    s = jax.random.normal(k3, (m, rows, ref.LANES)).astype(dtype)
    wscale = jax.random.uniform(k4, (K, m), minval=0.0, maxval=0.1)
    out_k = choco_fused.fused_mix_pallas(lvl, sign, s, wscale, bits, interpret=True)
    out_r = ref.fused_mix_ref(lvl, sign, s, wscale, bits)
    assert out_k.dtype == out_r.dtype == dtype
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)
    else:
        np.testing.assert_array_equal(_bits16(out_k), _bits16(out_r))


# ------------------------------------------- s in the leaf dtype through the mix
def _staging_mix(real, seen, tag, stage):
    """``real`` (fused_mix_pallas) recording its payload operands; with
    ``stage`` it takes s through f32 in HBM around the kernel, as the round
    used to."""

    def mix(lvl, sign, s_g, wscale, bits, interpret):
        seen[tag] = (lvl, sign, s_g.dtype)
        if stage:
            out = real(lvl, sign, s_g.astype(jnp.float32), wscale, bits, interpret=interpret)
            return out.astype(s_g.dtype)
        return real(lvl, sign, s_g, wscale, bits, interpret=interpret)

    return mix


@pytest.mark.parametrize("inner", [(32, 128), (151, 64)], ids=["layer-chunk", "padded-chunk"])
def test_fused_round_bf16_ring_leaf_dtype_bit_identical(monkeypatch, inner):
    """bf16 ring(2) kq4b leaf, shaped like one scan chunk: with s kept in bf16
    through the mix, theta_new, hat_new, s_new and the payload are bitwise
    those of the f32-staged composition."""
    m, bits = 2, 4
    ks = jax.random.split(KEY, 4)
    leaf, hat, s = (jax.random.normal(k, (m,) + inner).astype(jnp.bfloat16) for k in ks[:3])
    hat, s = 0.5 * hat, 0.1 * s
    shifts = topology.ring(m).shifts
    seen, outs, real = {}, {}, choco_fused.fused_mix_pallas
    for tag, stage in (("leaf_dtype", False), ("f32_staged", True)):
        monkeypatch.setattr(choco_fused, "fused_mix_pallas", _staging_mix(real, seen, tag, stage))
        outs[tag] = choco_fused.fused_round_leaf(
            leaf, hat, s, ks[3], shifts, 0.3, bits, interpret=True)
    assert seen["leaf_dtype"][2] == jnp.bfloat16
    for a, b in zip(outs["leaf_dtype"], outs["f32_staged"]):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(_bits16(a), _bits16(b))
    for a, b in zip(seen["leaf_dtype"][:2], seen["f32_staged"][:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the mix did move s
    assert not np.array_equal(_bits16(outs["leaf_dtype"][2]), _bits16(s))


_SHAPE_OPS = {"reshape", "slice", "squeeze", "pad", "concatenate", "broadcast_in_dim", "copy"}


def _follow_s(jaxpr, s_vars, scope, found):
    """Walk a jaxpr from the vars holding s (through shape-only ops, converts,
    nested jits and the mix kernel), recording the converts of s, the dtypes
    of the kernel call that takes it, and every Pallas call with the named
    scopes around it."""
    from jax.extend.core import Var

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        where = f"{scope}/{eqn.source_info.name_stack}"
        hit = [isinstance(v, Var) and v in s_vars for v in eqn.invars]
        if name == "pallas_call":
            found["pallas"].append(where)
            if any(hit):
                found["s_kernel"].append((
                    [v.aval.dtype for v, h in zip(eqn.invars, hit) if h],
                    [v.aval.dtype for v in eqn.outvars]))
                s_vars.update(eqn.outvars)
        elif "jaxpr" in eqn.params:
            inner = eqn.params["jaxpr"]
            inner = getattr(inner, "jaxpr", inner)
            sub = {iv for iv, h in zip(inner.invars, hit) if h}
            _follow_s(inner, sub, f"{where}/{eqn.params.get('name', name)}", found)
            s_vars.update(ov for ov, iv in zip(eqn.outvars, inner.outvars)
                          if isinstance(iv, Var) and iv in sub)
        elif any(hit) and name in _SHAPE_OPS | {"convert_element_type"}:
            if name == "convert_element_type":
                found["convert"].append((eqn.invars[0].aval.dtype, eqn.outvars[0].aval.dtype))
            s_vars.update(eqn.outvars)


@pytest.mark.parametrize("topo", [topology.ring(2), topology.mesh(16)], ids=["ring2", "mesh16"])
def test_fused_round_mix_path_engaged(topo):
    """The traced round of a bf16 leaf: on a ring no f32 convert of s exists
    outside the kernel and every mix call is scoped ``choco.mix.leaf_dtype``;
    on mesh(16) (two shift batches) every one is ``choco.mix.f32_carry``."""
    m, bits = topo.num_nodes, 4
    x = jax.ShapeDtypeStruct((m, 24, 160), jnp.bfloat16)
    closed = jax.make_jaxpr(
        lambda t, h, s, k: choco_fused.fused_round_leaf(
            t, h, s, k, topo.shifts, 0.3, bits, interpret=True)
    )(x, x, x, jax.ShapeDtypeStruct((2,), jnp.uint32))
    found = {"pallas": [], "s_kernel": [], "convert": []}
    _follow_s(closed.jaxpr, {closed.jaxpr.invars[2]}, "", found)
    mix = [where for where in found["pallas"] if "/fused_mix_pallas" in where]
    calls = -(-len(topo.shifts) // choco_fused.SHIFT_BATCH)
    path = "leaf_dtype" if calls == 1 else "f32_carry"
    assert [re.findall(r"choco\.mix\.\w+", w) for w in mix] == [[f"choco.mix.{path}"]] * calls
    if path == "leaf_dtype":
        assert found["convert"] == []
        assert found["s_kernel"] == [([jnp.bfloat16], [jnp.bfloat16])]
    else:  # f32 in before the first call, carried, bf16 out after the last
        assert found["convert"] == [(jnp.bfloat16, jnp.float32), (jnp.float32, jnp.bfloat16)]
        assert found["s_kernel"] == [([jnp.float32], [jnp.float32])] * len(mix)


# --------------------------------------------- fused choco_round vs the oracles
@pytest.mark.parametrize("bits", [8, 4], ids=["q8b", "q4b"])
@pytest.mark.parametrize(
    "topo", [topology.ring(8), topology.torus_2d(16)], ids=["ring", "torus"]
)
def test_fused_round_matches_unpacked_oracle(topo, bits):
    """Acceptance: fused path bit-compatible (1e-5 f32) with packed=False."""
    m = topo.num_nodes
    comp = KernelQuantization(bits=bits)
    theta = {
        "w": jax.random.normal(KEY, (m, 1000)),
        "blk": jax.random.normal(jax.random.PRNGKey(1), (m, 3, 260)),
    }
    state = gossip.choco_init(theta)
    k = jax.random.PRNGKey(7)
    t_f, s_f = gossip.choco_round(theta, state, topo, 0.2, comp, k, fused=True)
    t_o, s_o = gossip.choco_round(theta, state, topo, 0.2, comp, k, packed=False)
    _allclose_trees((t_f, s_f), (t_o, s_o), atol=1e-5)


@pytest.mark.parametrize("bits", [8, 4], ids=["q8b", "q4b"])
def test_fused_round_matches_packed_oracle(bits):
    comp = KernelQuantization(bits=bits)
    topo = topology.ring(8)
    theta = {"w": jax.random.normal(KEY, (8, 512))}
    state = gossip.choco_init(theta)
    k = jax.random.PRNGKey(3)
    t_f, s_f = gossip.choco_round(theta, state, topo, 0.3, comp, k, fused=True)
    t_p, s_p = gossip.choco_round(theta, state, topo, 0.3, comp, k, packed=True)
    _allclose_trees((t_f, s_f), (t_p, s_p), atol=1e-5)


@pytest.mark.parametrize("m", [6, 16], ids=["single-batch", "multi-batch"])
def test_fused_round_mesh_topology(m):
    """Mesh is circulant with m shifts — the K-way mix must handle it, both
    within one SHIFT_BATCH (m=6) and across several batched calls (m=16)."""
    topo = topology.mesh(m)
    comp = KernelQuantization(bits=8)
    theta = {"w": jax.random.normal(KEY, (m, 300))}
    state = gossip.choco_init(theta)
    k = jax.random.PRNGKey(5)
    t_f, s_f = gossip.choco_round(theta, state, topo, 0.2, comp, k, fused=True)
    t_o, s_o = gossip.choco_round(theta, state, topo, 0.2, comp, k, packed=False)
    _allclose_trees((t_f, s_f), (t_o, s_o), atol=1e-5)


def test_fused_round_bf16_multi_batch_matches_oracle():
    """bf16 leaves across >SHIFT_BATCH shifts: the mix accumulator must stay
    f32 between batches (one final cast), like the oracle."""
    m = 16  # mesh(16): K = 16 shifts = two SHIFT_BATCH batches
    topo = topology.mesh(m)
    comp = KernelQuantization(bits=8)
    theta = {"w": jax.random.normal(KEY, (m, 300)).astype(jnp.bfloat16)}
    state = gossip.choco_init(theta)
    k = jax.random.PRNGKey(9)
    t_f, s_f = gossip.choco_round(theta, state, topo, 0.2, comp, k, fused=True)
    t_o, s_o = gossip.choco_round(theta, state, topo, 0.2, comp, k, packed=False)
    for a, b in zip(jax.tree_util.tree_leaves((t_f, s_f)), jax.tree_util.tree_leaves((t_o, s_o))):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-5, rtol=0
        )


def test_step_without_init_resolves_gamma_from_state():
    """A step() traced without init() must not bake the placeholder gamma:
    the consensus re-resolves it from the state's own leaf shapes."""
    from repro.core import ADGDAConfig, TrainerState, adgda_trainer
    from repro.core.gossip import choco_init
    from repro.core.trainer import ChocoConsensus

    m, d = 4, 1 << 16
    cfg = ADGDAConfig(num_nodes=m, topology="ring", compressor="q8b",
                      eta_theta=0.01, eta_lambda=0.01)

    def loss_fn(params, batch, rng):
        return jnp.mean((params["w"] - batch) ** 2)

    trainer = adgda_trainer(cfg, loss_fn)
    placeholder_gamma = trainer.gamma  # resolved with the 4096-element stub
    # hand-rolled state, bypassing init() entirely (a checkpoint restore)
    theta = {"w": jnp.zeros((m, d))}
    state = TrainerState(
        step=jnp.zeros((), jnp.int32),
        theta=theta,
        lam=jnp.full((m, m), 1.0 / m),
        opt=trainer.local.init(theta),
        consensus=choco_init(theta),
        theta_avg={"w": jnp.zeros((d,), jnp.float32)},
        rng=jax.random.PRNGKey(0),
    )
    assert trainer.consensus._resolve_gamma(d) < placeholder_gamma  # larger d, smaller delta
    assert ChocoConsensus._encode_dim(theta) == d
    state2, aux = trainer.step(state, jnp.zeros((m, d)))
    assert np.isfinite(float(aux["mean_loss"]))


def test_fused_round_preserves_global_average():
    """CHOCO invariant: the gossip round preserves mean(theta) + mean(s-hat)."""
    topo = topology.ring(8)
    comp = KernelQuantization(bits=4)
    theta = {"w": jax.random.normal(KEY, (8, 640))}
    state = gossip.choco_init(theta)
    mean0 = theta["w"].mean(0)
    t, s = theta, state
    for i in range(5):
        t, s = gossip.choco_round(t, s, topo, 0.3, comp, jax.random.PRNGKey(i), fused=True)
    np.testing.assert_allclose(np.asarray(t["w"].mean(0)), np.asarray(mean0), atol=1e-4)


def test_fused_round_composes_with_scan_plan():
    """Chunk-scanned large leaves must route each chunk through the fused path."""
    topo = topology.ring(4)
    comp = KernelQuantization(bits=8)
    theta = {"blocks": jax.random.normal(KEY, (4, 6, 256))}
    state = gossip.choco_init(theta)
    k = jax.random.PRNGKey(11)
    # block_scan_elems=8 forces the scan plan (6 chunks along axis 1)
    t_f, s_f = gossip.choco_round(
        theta, state, topo, 0.3, comp, k, fused=True, block_scan_elems=8
    )
    t_o, s_o = gossip.choco_round(
        theta, state, topo, 0.3, comp, k, packed=False, block_scan_elems=8
    )
    _allclose_trees((t_f, s_f), (t_o, s_o), atol=1e-5)
    assert t_f["blocks"].shape == (4, 6, 256)


def test_fused_round_jits():
    topo = topology.ring(4)
    comp = KernelQuantization(bits=4)
    theta = {"w": jax.random.normal(KEY, (4, 128))}
    state = gossip.choco_init(theta)

    @jax.jit
    def step(t, s, k):
        return gossip.choco_round(t, s, topo, 0.3, comp, k, fused=True)

    t, s = step(theta, state, KEY)
    assert t["w"].shape == (4, 128)


def test_fused_flag_falls_back_for_unsupported_compressor():
    """fused=True with a non-fused compressor must silently use the oracle."""
    topo = topology.ring(4)
    comp = make_compressor("q8b")  # pure-jnp, no fused capability
    theta = {"w": jax.random.normal(KEY, (4, 64))}
    state = gossip.choco_init(theta)
    k = jax.random.PRNGKey(2)
    t_f, s_f = gossip.choco_round(theta, state, topo, 0.3, comp, k, fused=True)
    t_o, s_o = gossip.choco_round(theta, state, topo, 0.3, comp, k, packed=True)
    _allclose_trees((t_f, s_f), (t_o, s_o), atol=0.0)


def test_adgda_trainer_with_fused_gossip():
    """End-to-end: ADGDAConfig(fused_gossip=True, compressor='kq8b') trains."""
    from repro.core import ADGDAConfig, adgda_trainer

    m = 4
    cfg = ADGDAConfig(
        num_nodes=m, topology="ring", compressor="kq8b", fused_gossip=True,
        eta_theta=0.05, eta_lambda=0.05,
    )

    def loss_fn(params, batch, rng):
        return jnp.mean((params["w"] - batch) ** 2)

    trainer = adgda_trainer(cfg, loss_fn)
    batch = jnp.arange(m, dtype=jnp.float32).reshape(m, 1)
    state = trainer.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    for _ in range(3):
        state, aux = trainer.step(state, batch)
    assert np.isfinite(float(aux["mean_loss"]))
