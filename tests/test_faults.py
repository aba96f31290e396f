"""Wire-fault injection + self-healing NeighborCache (core/faults.py and the
faulted paths of core/exchange.py / core/trainer.py).

What must hold for the faulted wire to be trustworthy:

* **spec parsing** — the CLI syntax round-trips; "no faults" and "faults at
  rate zero" are the same program (both parse to None);
* **digest/garble** — the detection primitive catches every garble (the XOR
  is never the identity) and never fires on bit-identical content;
* **detection ground truth** — one faulted round's divergence verdicts match
  an independent reconstruction from the same fault key: every injected
  drop/corrupt/delay on a live edge is detected the round it happens, and
  nothing else is;
* **synced-mirror invariant** — whenever the state machine claims an edge is
  synced, its mirror IS bit-identical to the sender's theta_hat (the PR 5
  invariant, now conditional on the fault state), and resyncs do fire and
  restore divergent edges;
* **backend parity** — the rolled and ppermute backends produce bit-identical
  faulted trajectories (same _cached_round_body, structural);
* **determinism** — same seed + same spec => bit-identical runs (the
  kill-and-resume half of this lives in test_checkpoint.py);
* **billing** — dropped deliveries are not billed: under 50% drop the
  trainer's aux["bits_realized"] equals bits_per_round(mode="realized"),
  both reading the exchange's delivered-bits meter.

Hypothesis is used when the container has it; otherwise the property tests
run as a seeded sweep (same assertions, fixed draw set — no skipped
coverage, and no new dependency).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import gossip, topology
from repro.core.compression import Identity, RandomQuantization
from repro.core.exchange import (
    choco_round_cached_local,
    mix_stacked_faulted_local,
)
from repro.core.faults import (
    FaultSpec,
    digest,
    garble,
    parse_fault_spec,
    sample_events,
)
from repro.core.topology import compile_schedule_plans, make_topology
from repro.core.wire import compile_union_wire
from repro.launch.mesh import make_cpu_mesh

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hst

    HAVE_HYPOTHESIS = True
except ImportError:  # container image has no hypothesis; seeded sweep below
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------- spec parse
def test_parse_fault_spec_roundtrip():
    spec = parse_fault_spec("drop:0.05,corrupt:0.01,stale:2")
    assert spec == FaultSpec(drop=0.05, corrupt=0.01, stale=2)
    assert parse_fault_spec(str(spec)) == spec  # __str__ round-trips
    full = parse_fault_spec("drop:0.1,dup:0.02,delay:0.03,backoff:3,backoff_cap:16")
    assert full.dup == 0.02 and full.delay == 0.03
    assert full.backoff_base == 3 and full.backoff_cap == 16


def test_parse_fault_spec_zero_is_none():
    """'no faults configured' and 'faults at rate 0' are the same program."""
    assert parse_fault_spec(None) is None
    assert parse_fault_spec("") is None
    assert parse_fault_spec("drop:0") is None
    assert parse_fault_spec("drop:0,corrupt:0,stale:5") is None
    assert parse_fault_spec(FaultSpec()) is None  # all-zero spec object too
    spec = FaultSpec(drop=0.1)
    assert parse_fault_spec(spec) is spec


def test_parse_fault_spec_errors():
    with pytest.raises(ValueError, match="unknown fault-spec key"):
        parse_fault_spec("dorp:0.1")
    with pytest.raises(ValueError, match="key:value"):
        parse_fault_spec("drop=0.1")
    with pytest.raises(ValueError, match="in \\[0, 1\\]"):
        parse_fault_spec("drop:1.5")
    with pytest.raises(ValueError, match="sum"):
        parse_fault_spec("drop:0.6,corrupt:0.6")
    with pytest.raises(ValueError, match="stale"):
        FaultSpec(drop=0.1, stale=-1)


# ------------------------------------------------------------- digest/garble
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_digest_detects_every_garble(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 33)).astype(dtype)
    d = digest(x)
    assert d.shape == (4,) and d.dtype == jnp.int32
    # identical content -> identical digest, by construction
    assert (digest(jnp.array(np.asarray(x))) == d).all()
    # garble is bijective, never the identity, and always caught
    g = garble(x)
    assert not np.array_equal(np.asarray(g), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(garble(g)), np.asarray(x))
    assert (digest(g) != d).all()


def _digest_single_flip(seed: int, pos: int):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 17))
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    flipped = bits.at[pos % 2, pos % 17].set(bits[pos % 2, pos % 17] ^ 1)
    y = jax.lax.bitcast_convert_type(flipped, jnp.float32)
    assert int(digest(y)[pos % 2]) != int(digest(x)[pos % 2])


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(hst.integers(0, 2**20), hst.integers(0, 2**20))
    def test_digest_single_bit_flip(seed, pos):
        _digest_single_flip(seed, pos)

else:

    @pytest.mark.parametrize("seed,pos", [(s, p) for s in (0, 7, 123) for p in (0, 5, 33)])
    def test_digest_single_bit_flip(seed, pos):
        _digest_single_flip(seed, pos)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [2**12, 2**12 + 37])  # aligned + padded grids
def test_fused_encode_digest_parity(dtype, d):
    """The digest lane folded into the fused encode kernel equals the XLA
    ``digest(hat_new)`` — and turning it on changes no other output."""
    from repro.kernels.choco_fused import fused_round_leaf

    m = 4
    ks = jax.random.split(jax.random.PRNGKey(d), 3)
    leaf = jax.random.normal(ks[0], (m, d)).astype(dtype)
    hat = (jax.random.normal(ks[1], (m, d)) * 0.1).astype(dtype)
    s = jnp.zeros_like(leaf)
    shifts = ((1, 0.3), (3, 0.2))
    plain = fused_round_leaf(leaf, hat, s, ks[2], shifts, 0.5, 4, interpret=True)
    tn, hn, sn, dig = fused_round_leaf(
        leaf, hat, s, ks[2], shifts, 0.5, 4, interpret=True, with_digest=True
    )
    for a, b in zip(plain, (tn, hn, sn)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(dig), np.asarray(digest(hn)))
    # the lane detects a garbled hat like the XLA digest does
    assert (np.asarray(digest(garble(hn))) != np.asarray(dig)).all()


# ----------------------------------------------------------------- fixtures
def _theta(m, d, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "w": jax.random.normal(k1, (m, d)),
        "b": jax.random.normal(k2, (m,)),
    }


def _union_for(spec_or_topo, m, dropout=0.0, seed=1):
    if isinstance(spec_or_topo, str) and (":" in spec_or_topo or dropout):
        sched = topology.make_topology_schedule(
            spec_or_topo, m, dropout=dropout, seed=seed
        )
        return sched, compile_union_wire(compile_schedule_plans(sched))
    topo = make_topology(spec_or_topo, m)
    from repro.core.topology import compile_permute_plan

    return None, compile_union_wire((compile_permute_plan(topo),))


def _run_faulted_local(theta, rounds, spec, *, sched=None, topo=None,
                       union=None, comp=None, seed=0):
    comp = comp or RandomQuantization(bits=4)
    m = theta["w"].shape[0]
    state = gossip.choco_init(theta, cache_ops=union.n_ops, fault_ops=union.n_ops)

    @jax.jit
    def step(t, st, k, fk, s):
        return choco_round_cached_local(
            t, st, 0.3, comp, k, union=union, schedule=sched, topology=topo,
            step=s, faults=spec, fault_key=fk,
        )

    t = theta
    for i in range(rounds):
        t, state = step(
            t, state, jax.random.PRNGKey(100 + i),
            jax.random.fold_in(jax.random.PRNGKey(7 + seed), i), jnp.int32(i),
        )
    return t, state


# ------------------------------------------------- detection == ground truth
def test_divergence_detected_the_round_it_happens():
    """From an all-synced state, one faulted round's verdicts must equal an
    independent reconstruction from the same fault key: every live edge that
    drew drop/corrupt/delay diverges (dup and clean edges stay synced)."""
    m, d = 8, 40
    spec = FaultSpec(drop=0.25, corrupt=0.2, dup=0.1, delay=0.1, stale=2)
    theta = _theta(m, d)
    _, union = _union_for("ring", m)
    fkey = jax.random.PRNGKey(42)

    state = gossip.choco_init(theta, cache_ops=union.n_ops, fault_ops=union.n_ops)
    _, state = jax.jit(
        lambda t, st: choco_round_cached_local(
            t, st, 0.3, RandomQuantization(bits=4), jax.random.PRNGKey(0),
            union=union, step=jnp.int32(0), faults=spec, fault_key=fkey,
        )
    )(theta, state)

    ev = sample_events(spec, fkey, union.n_ops, m)
    exist = np.stack([np.asarray(s) >= 0 for s in union.senders])  # [n_ops, m]
    diverged = exist & np.asarray(ev.drop | ev.corrupt | ev.delay)
    assert diverged.any(), "draw produced no faults; pick a different key"

    fs = state.fault
    np.testing.assert_array_equal(
        np.asarray(fs.synced).T.astype(bool), exist & ~diverged | ~exist
    )
    np.testing.assert_array_equal(
        np.asarray(fs.detected), diverged.sum(0).astype(np.int32)
    )
    # no resync can have happened yet (stale bound not exceeded)
    assert int(np.asarray(fs.resyncs).sum()) == 0
    # delivered-bits meter: drops bill zero, dups twice, everything else once
    payload, dig, _ = __import__(
        "repro.core.exchange", fromlist=["_wire_msg_bits"]
    )._wire_msg_bits(RandomQuantization(bits=4), theta, gossip.BLOCK_SCAN_ELEMS)
    mult = np.where(np.asarray(ev.drop), 0.0, np.where(np.asarray(ev.dup), 2.0, 1.0))
    want_bits = np.zeros((m,))
    for k, snd in enumerate(union.senders):
        for i, j in enumerate(np.asarray(snd)):
            if j >= 0:
                want_bits[j] += mult[k, i] * (payload + dig)
    np.testing.assert_allclose(np.asarray(fs.bits), want_bits, rtol=1e-6)


# ------------------------------------- synced-mirror invariant + resync heal
def _assert_synced_mirrors_exact(state, union):
    """Every edge the state machine calls synced has a bit-identical mirror."""
    hats = jax.tree_util.tree_leaves(state.theta_hat)
    synced = np.asarray(state.fault.synced)  # [m, n_ops]
    checked = 0
    for k, snd in enumerate(union.senders):
        mirrors = jax.tree_util.tree_leaves(state.cache[k])
        for hat, mirror in zip(hats, mirrors):
            hat, mirror = np.asarray(hat), np.asarray(mirror)
            for i in range(hat.shape[0]):
                if snd[i] >= 0 and synced[i, k] > 0:
                    assert (mirror[i] == hat[snd[i]]).all(), (
                        f"op {k} node {i}: state machine claims synced but the "
                        f"mirror differs from sender {snd[i]}'s theta_hat"
                    )
                    checked += 1
    return checked


@pytest.mark.parametrize("spec_str,dropout", [
    ("ring", 0.0),
    ("matching:3", 0.25),
], ids=["static-ring", "matching-drop"])
def test_synced_mirror_invariant_and_resync(spec_str, dropout):
    """Across a faulted run the conditional mirror invariant holds every
    round, divergences accumulate, and resyncs fire and heal edges."""
    m, d, rounds = 8, 40, 10
    spec = FaultSpec(drop=0.3, corrupt=0.1, stale=1)
    theta = _theta(m, d)
    sched, union = _union_for(spec_str, m, dropout=dropout)
    topo = None if sched is not None else make_topology("ring", m)
    comp = RandomQuantization(bits=4)
    state = gossip.choco_init(theta, cache_ops=union.n_ops, fault_ops=union.n_ops)

    masked = dropout > 0

    @jax.jit
    def step(t, st, k, fk, s, mk=None):
        return choco_round_cached_local(
            t, st, 0.3, comp, k, union=union, schedule=sched, topology=topo,
            step=s, mask=mk, faults=spec, fault_key=fk,
        )

    t = theta
    checked = 0
    for i in range(rounds):
        kw = {}
        if masked:
            kw["mk"] = sched.mask_at(jax.random.PRNGKey(500 + i), i)
        t, state = step(
            t, state, jax.random.PRNGKey(100 + i),
            jax.random.fold_in(jax.random.PRNGKey(7), i), jnp.int32(i), **kw
        )
        checked += _assert_synced_mirrors_exact(state, union)

    assert checked > 0
    fs = state.fault
    assert int(np.asarray(fs.detected).sum()) > 0, "faults at 40% never diverged?"
    assert int(np.asarray(fs.resyncs).sum()) > 0, "no resync ever healed an edge"
    assert np.isfinite(np.asarray(jax.tree_util.tree_leaves(t)[0])).all()


def test_all_drop_wire_bills_zero_and_never_heals():
    """drop:1.0 — nothing is ever delivered: the meter stays at zero, no
    resync ever verifies, every live edge diverges immediately."""
    m, d = 6, 24
    spec = FaultSpec(drop=1.0, stale=1)
    theta = _theta(m, d)
    _, union = _union_for("ring", m)
    _, state = _run_faulted_local(theta, 5, spec, union=union)
    fs = state.fault
    assert float(np.asarray(fs.bits).sum()) == 0.0
    assert int(np.asarray(fs.resyncs).sum()) == 0
    assert not np.asarray(fs.synced).astype(bool).any()


# ------------------------------------------------- multi-lane fault isolation
def test_multilane_faulted_lane_isolation():
    """Per-lane fault machinery (ISSUE 8): each lane of a faulted multi-lane
    round draws its own fault events (fault_key folded per lane) and keeps
    its own recovery state — lane k of the 2-lane run is bit-identical to a
    single-lane faulted run keyed with lane_key, so a corrupted tracker-lane
    message can never stale (or heal) a model-lane mirror."""
    from repro.core.exchange import choco_round_cached_local_lanes

    m, d, rounds = 8, 40, 6
    spec = FaultSpec(drop=0.25, corrupt=0.15, stale=1)
    comp = RandomQuantization(bits=4)
    _, union = _union_for("ring", m)
    thetas0 = [_theta(m, d, seed=s) for s in (0, 1)]

    @jax.jit
    def step_lanes(ts, sts, k, fk, s):
        lanes = [gossip.LaneRound(t, st, 0.3, comp) for t, st in zip(ts, sts)]
        return choco_round_cached_local_lanes(
            lanes, k, union=union, step=s, faults=spec, fault_key=fk,
        )

    ts = list(thetas0)
    sts = [gossip.choco_init(t, cache_ops=union.n_ops, fault_ops=union.n_ops)
           for t in ts]
    for i in range(rounds):
        ts, sts = step_lanes(
            ts, sts, jax.random.PRNGKey(100 + i),
            jax.random.fold_in(jax.random.PRNGKey(7), i), jnp.int32(i),
        )
        ts, sts = list(ts), list(sts)
        # synced-mirror invariant holds per lane, every round
        for st in sts:
            _assert_synced_mirrors_exact(st, union)

    # per-lane reference: single-lane faulted runs with the folded keys
    for k in range(2):
        t = thetas0[k]
        state = gossip.choco_init(t, cache_ops=union.n_ops, fault_ops=union.n_ops)

        @jax.jit
        def step_one(t, st, key, fk, s):
            return choco_round_cached_local(
                t, st, 0.3, comp, key, union=union, step=s, faults=spec,
                fault_key=fk,
            )

        for i in range(rounds):
            t, state = step_one(
                t, state,
                gossip.lane_key(jax.random.PRNGKey(100 + i), k),
                gossip.lane_key(jax.random.fold_in(jax.random.PRNGKey(7), i), k),
                jnp.int32(i),
            )
        for a, b in zip(jax.tree_util.tree_leaves((ts[k], sts[k])),
                        jax.tree_util.tree_leaves((t, state))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the lanes really draw DIFFERENT events: identical inputs would make
    # identical fault state, and these started from different thetas but
    # share every key except the per-lane fold — their detected counters
    # must differ somewhere across a 6-round 40%-fault run
    assert not np.array_equal(np.asarray(sts[0].fault.detected),
                              np.asarray(sts[1].fault.detected)) or \
           not np.array_equal(np.asarray(sts[0].fault.synced),
                              np.asarray(sts[1].fault.synced)), (
        "both lanes drew identical fault events — fault_key not folded per lane"
    )
    # both lanes detect and heal independently under drop+corrupt churn
    for st in sts:
        assert int(np.asarray(st.fault.detected).sum()) > 0
        assert int(np.asarray(st.fault.resyncs).sum()) > 0


def test_gt_trainer_faulted_bits_meter():
    """Gradient-tracking under wire faults: the jitted realized-bits meter
    sums both lanes' delivered bits and matches bits_per_round(mode=
    'realized'); both lane fault machines accumulate independently."""
    from benchmarks.common import make_adgda
    from repro.data import rotated_minority_classification

    m = 6
    data = rotated_minority_classification(num_nodes=m, seed=0)
    trainer, init_fn, _ = make_adgda(
        "logistic", m, compressor="q4b", consensus="gt",
        fault_spec="drop:0.3,corrupt:0.1,stale:1",
    )
    state = trainer.init(init_fn(data.dim, data.num_classes), jax.random.PRNGKey(0))
    xb, yb = next(data.batches(20, seed=0))
    batch = (jnp.asarray(xb), jnp.asarray(yb))
    for _ in range(5):
        state, aux = trainer.step(state, batch)
        assert float(aux["bits_realized"]) == pytest.approx(
            trainer.bits_per_round(state, mode="realized")
        )
    cons = state.consensus
    det_m = int(np.asarray(cons.model.fault.detected).sum())
    det_t = int(np.asarray(cons.tracker.fault.detected).sum())
    assert det_m > 0 and det_t > 0, "both lanes should see faults at 40%"
    # independent per-lane draws: the two lanes' delivered-bits meters are
    # both live and (folded fault keys) not byte-for-byte the same stream
    bits_m = np.asarray(cons.model.fault.bits)
    bits_t = np.asarray(cons.tracker.fault.bits)
    assert bits_m.sum() > 0 and bits_t.sum() > 0
    assert not np.array_equal(bits_m, bits_t)


# --------------------------------------------------------- backend parity
def test_rolled_vs_ppermute_faulted_parity():
    """The rolled faulted round IS the ppermute body with one full-width
    shard — trajectories must be bit-identical, including the fault state."""
    m, d, rounds = 8, 40, 4
    spec = FaultSpec(drop=0.25, corrupt=0.1, stale=1)
    theta = _theta(m, d)
    sched = topology.make_topology_schedule("matching:3", m, dropout=0.0, seed=1)
    topo0 = sched.topology_at(0)
    union = compile_union_wire(compile_schedule_plans(sched))
    comp = RandomQuantization(bits=4)
    mesh = make_cpu_mesh(1, 1)

    def run(backend):
        state = gossip.choco_init(theta, cache_ops=union.n_ops, fault_ops=union.n_ops)
        kw = dict(backend=backend)
        if backend == "ppermute":
            kw["mesh"] = mesh

        @jax.jit
        def step(t, st, k, fk, s):
            return gossip.choco_round(
                t, st, topo0, 0.3, comp, k, packed=True, schedule=sched,
                step=s, union=union, faults=spec, fault_key=fk, **kw,
            )

        t = theta
        for i in range(rounds):
            t, state = step(
                t, state, jax.random.PRNGKey(100 + i),
                jax.random.fold_in(jax.random.PRNGKey(7), i), jnp.int32(i),
            )
        return t, state

    t_r, s_r = run("rolled")
    t_p, s_p = run("ppermute")
    for a, b in zip(jax.tree_util.tree_leaves((t_r, s_r)),
                    jax.tree_util.tree_leaves((t_p, s_p))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- determinism
def _determinism_case(seed: int, drop: float, corrupt: float):
    m, d = 6, 24
    spec = FaultSpec(drop=drop, corrupt=corrupt, stale=1)
    theta = _theta(m, d, seed=seed)
    _, union = _union_for("ring", m)
    t1, s1 = _run_faulted_local(theta, 3, spec, union=union, seed=seed)
    t2, s2 = _run_faulted_local(theta, 3, spec, union=union, seed=seed)
    for a, b in zip(jax.tree_util.tree_leaves((t1, s1)),
                    jax.tree_util.tree_leaves((t2, s2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        hst.integers(0, 1000),
        hst.floats(0.05, 0.45),
        hst.floats(0.0, 0.45),
    )
    def test_fault_determinism(seed, drop, corrupt):
        """Same seed + same spec -> bit-identical trajectories and fault
        state, for any rates."""
        _determinism_case(seed, drop, corrupt)

else:

    @pytest.mark.parametrize("seed,drop,corrupt", [
        (0, 0.3, 0.1), (1, 0.05, 0.45), (2, 0.45, 0.0),
    ])
    def test_fault_determinism(seed, drop, corrupt):
        """Same seed + same spec -> bit-identical trajectories and fault
        state (seeded sweep; hypothesis not in the container)."""
        _determinism_case(seed, drop, corrupt)


# --------------------------------------------------- memoryless faulted mix
def test_memoryless_all_drop_is_identity():
    """Exact/dual wire under drop:1.0: every edge leaves the mix, the
    surviving-subgraph rescale leaves each node with itself, zero bits."""
    m = 6
    topo = make_topology("ring", m)
    tree = {"lam": jax.random.normal(jax.random.PRNGKey(0), (m, m))}
    mixed, bits = mix_stacked_faulted_local(
        tree, topology=topo, faults=FaultSpec(drop=1.0),
        fault_key=jax.random.PRNGKey(3),
    )
    np.testing.assert_array_equal(np.asarray(mixed["lam"]), np.asarray(tree["lam"]))
    assert float(np.asarray(bits).sum()) == 0.0


def test_memoryless_faulted_mix_row_stochastic():
    """Under partial faults the faulted dense mix still averages with
    row-stochastic weights: mixing a constant tree returns it exactly."""
    m = 8
    topo = make_topology("ring", m)
    const = {"v": jnp.full((m, 3), 2.5)}
    mixed, bits = mix_stacked_faulted_local(
        const, topology=topo, faults=FaultSpec(drop=0.3, corrupt=0.2),
        fault_key=jax.random.PRNGKey(11),
    )
    np.testing.assert_allclose(np.asarray(mixed["v"]), 2.5, rtol=1e-6)
    assert float(np.asarray(bits).max()) > 0.0  # some deliveries billed


# ------------------------------------------------- satellite: realized bits
def test_trainer_bits_realized_under_heavy_drop():
    """Regression (billing bug): dropped deliveries are NOT billed — under
    50% drop the jitted aux meter equals bits_per_round(mode='realized'),
    both reading the exchange's delivered-bits meter, and sits well below
    the fault-free constant."""
    from benchmarks.common import make_adgda
    from repro.data import rotated_minority_classification

    from repro.core.exchange import _wire_msg_bits

    m = 6
    data = rotated_minority_classification(num_nodes=m, seed=0)
    # stale:9999 keeps resync traffic out of the picture, so the meter is
    # exactly (delivered hat-deltas) x (payload + digest lane)
    trainer, init_fn, _ = make_adgda(
        "logistic", m, compressor="q4b", fault_spec="drop:0.5,stale:9999"
    )
    state = trainer.init(init_fn(data.dim, data.num_classes), jax.random.PRNGKey(0))
    xb, yb = next(data.batches(20, seed=0))
    batch = (jnp.asarray(xb), jnp.asarray(yb))
    payload, dig, _ = _wire_msg_bits(
        trainer.compressor, state.theta, gossip.BLOCK_SCAN_ELEMS
    )
    full_all_nodes = float(trainer.consensus.union.out_degree.sum()) * (payload + dig)
    total = 0.0
    for _ in range(6):
        state, aux = trainer.step(state, batch)
        assert float(aux["bits_realized"]) == pytest.approx(
            trainer.bits_per_round(state, mode="realized")
        )
        total += float(np.asarray(state.consensus.fault.bits).sum())
    # half the deliveries dropped: summed over nodes, the measured traffic
    # must be strictly below billing every edge every round (deterministic,
    # seeded) — the old degree-formula billing would sit exactly at the bound
    assert 0.0 < total < 6 * full_all_nodes
