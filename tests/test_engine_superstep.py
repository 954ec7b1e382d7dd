"""Superstep execution engine: K>1 runs must be bit-for-bit identical to
K=1 (the per-tick gate makes fused ticks exact, not approximate), across
CC backends, the seed lanes, and the config sweep; and the per-seed
salt decorrelation of Study seed lanes must actually change RED marking."""

import jax
import numpy as np
import pytest

from repro.analysis import trace_guard
from repro.netsim import api, collectives, workloads
from repro.netsim.engine import SimConfig, build
from repro.netsim.scenarios import Scenario
from repro.netsim.units import FatTreeConfig, LinkConfig

TREE = FatTreeConfig(racks=2, nodes_per_rack=4, uplinks=2)
OVERSUB = FatTreeConfig(racks=2, nodes_per_rack=8, uplinks=2)   # 4:1
TREE3 = FatTreeConfig(racks=4, nodes_per_rack=4, uplinks=2,
                      pods=2, core_uplinks=1)                   # core 4:1
LINK = LinkConfig()


def _run(tree, wl, superstep, max_ticks=30000, **kw):
    sim = build(SimConfig(link=LINK, tree=tree, superstep=superstep, **kw), wl)
    st = sim.run(max_ticks=max_ticks)
    st.now.block_until_ready()
    return sim, st


def _assert_state_equal(st_a, st_b):
    """Full-pytree bitwise equality — stronger than the acceptance bar
    (fct/goodput/cwnd): every state leaf, metrics counters included."""
    la, lb = jax.tree.leaves(st_a), jax.tree.leaves(st_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_superstep_bit_for_bit_equals_k1(backend):
    wl = workloads.incast(TREE, degree=3, size_bytes=16 * 4096, seed=0)
    _, st1 = _run(TREE, wl, superstep=1, cc_backend=backend)
    for k in (0, 7):          # 0 = auto (one base RTT); 7 doesn't divide
        _, stk = _run(TREE, wl, superstep=k, cc_backend=backend)
        np.testing.assert_array_equal(np.asarray(st1.fct), np.asarray(stk.fct))
        np.testing.assert_array_equal(np.asarray(st1.goodput),
                                      np.asarray(stk.goodput))
        np.testing.assert_array_equal(np.asarray(st1.cc.cwnd),
                                      np.asarray(stk.cc.cwnd))
        assert int(st1.now) == int(stk.now)
        _assert_state_equal(st1, stk)


def test_superstep_bit_for_bit_pallas_fabric_transport():
    """Fused K>1 vs K=1 with the enqueue-rank/arbitration and ring-drain
    kernels on the pallas backend — the cond-gated superstep body must
    compose with the kernel call graph exactly as with the jnp refs."""
    wl = workloads.incast(TREE, degree=3, size_bytes=8 * 4096, seed=0)
    kw = dict(fabric_backend="pallas", transport_backend="pallas")
    _, st1 = _run(TREE, wl, superstep=1, **kw)
    _, stk = _run(TREE, wl, superstep=0, **kw)
    _assert_state_equal(st1, stk)


def test_superstep_exact_under_congestion_and_trimming():
    """An oversubscribed permutation exercises trims, retransmissions, and
    RED marking; the fused loop must still match K=1 exactly."""
    wl = workloads.permutation(OVERSUB, size_bytes=64 * 4096, seed=1)
    _, st1 = _run(OVERSUB, wl, superstep=1)
    _, stk = _run(OVERSUB, wl, superstep=0)
    assert int(st1.m.n_trim) > 0          # the scenario actually trims
    _assert_state_equal(st1, stk)


def test_superstep_exact_on_three_tier_core_congestion():
    """Cross-core permutation on an oversubscribed three-tier fabric:
    trims at the T1 uplinks, five-queue paths, longer rings — K>1 must
    still match K=1 over the full pytree."""
    wl = workloads.permutation(TREE3, size_bytes=48 * 4096, seed=6)
    _, st1 = _run(TREE3, wl, superstep=1)
    _, stk = _run(TREE3, wl, superstep=0)
    assert int(st1.m.n_trim) > 0          # the core actually congests
    _assert_state_equal(st1, stk)


def test_seed_lanes_match_k1_and_decorrelate_red():
    """Study seed lanes compose with supersteps, and the per-seed salts
    change RED marking outcomes (different mark draws -> different
    trajectories), while seed 0 reproduces the unbatched run exactly."""
    wl = workloads.permutation(OVERSUB, size_bytes=64 * 4096, seed=2)
    sim1 = build(SimConfig(link=LINK, tree=OVERSUB, superstep=1), wl)
    sck = Scenario(name=wl.name, wl=wl,
                   cfg=SimConfig(link=LINK, tree=OVERSUB, superstep=0))
    stb = api.study(sck, seeds=range(4)).run_states(max_ticks=30000)
    st1 = sim1.run(max_ticks=30000)

    # batch element 0 carries salt 0 == the unbatched run
    np.testing.assert_array_equal(np.asarray(st1.fct), np.asarray(stb.fct)[0])
    np.testing.assert_array_equal(np.asarray(st1.goodput),
                                  np.asarray(stb.goodput)[0])

    # decorrelation: the salt feeds the RED mark draw, so marking-driven
    # outcomes (ECN-driven cwnd trajectories -> fct) differ across seeds
    fcts = [tuple(np.asarray(stb.fct)[i]) for i in range(4)]
    assert len(set(fcts)) > 1
    hists = [tuple(np.asarray(stb.m.rtt_hist)[i]) for i in range(4)]
    assert len(set(hists)) > 1


def test_sweep_composes_with_supersteps():
    """The vmapped sweep under a superstep loop stays one-compile and
    matches the per-tick sweep point-for-point."""
    wl = workloads.incast(TREE, degree=4, size_bytes=32 * 4096, seed=1)
    points = [{"start_cwnd_mult": a} for a in (0.5, 1.0, 1.25)]
    cfg1 = SimConfig(link=LINK, tree=TREE, superstep=1)
    cfgk = SimConfig(link=LINK, tree=TREE, superstep=13)

    swk = api.study(Scenario(name=wl.name, cfg=cfgk, wl=wl), points=points)
    with trace_guard("engine.step", expect=1):
        states_k = swk.run_states(max_ticks=30000)
        states_k.now.block_until_ready()

    states_1 = api.study(Scenario(name=wl.name, cfg=cfg1, wl=wl),
                         points=points).run_states(max_ticks=30000)
    np.testing.assert_array_equal(np.asarray(states_1.fct),
                                  np.asarray(states_k.fct))
    np.testing.assert_array_equal(np.asarray(states_1.goodput),
                                  np.asarray(states_k.goodput))
    np.testing.assert_array_equal(np.asarray(states_1.cc.cwnd),
                                  np.asarray(states_k.cc.cwnd))
    assert int(states_1.now[0]) == int(states_k.now[0])


def test_donated_state_is_consumed():
    """The run loops donate their input state: callers must not reuse a
    SimState after passing it to a run loop (DESIGN.md Sec. 6 contract).
    Sim.run builds a fresh init() per call, so back-to-back runs agree."""
    wl = workloads.incast(TREE, degree=3, size_bytes=16 * 4096, seed=3)
    sim, st_a = _run(TREE, wl, superstep=0)
    st_b = sim.run(max_ticks=30000)
    np.testing.assert_array_equal(np.asarray(st_a.fct), np.asarray(st_b.fct))


def test_superstep_exact_dependency_gated_collectives():
    """K>1 vs K=1 under dependency gating (DESIGN.md Sec. 11): a flow
    released mid-superstep by a parent's chunk landing must activate on
    exactly the same tick inside the fused body."""
    wl = collectives.ring_allreduce(TREE, chunk_bytes=4 * 4096, nodes=8)
    _, st1 = _run(TREE, wl, superstep=1)
    assert bool(np.asarray(st1.done).all())
    for k in (0, 7):          # 0 = auto (one base RTT); 7 doesn't divide
        _, stk = _run(TREE, wl, superstep=k)
        _assert_state_equal(st1, stk)
