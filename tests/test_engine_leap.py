"""Event-horizon time leaping (DESIGN.md Sec. 6.3): leap-on trajectories
must be bit-for-bit identical to leap-off across the *full* state pytree
(`now`, metrics counters, RTT histograms included) — the leap skips only
ticks that are state no-ops, it never approximates.  Covered regimes:
dense incast/permutation/alltoall on both CC backends, credit-based
grants, timeout recovery without trimming, faulted links, the sparse
heavy-tailed scenario, and the Study lane loop (seed lanes and sweep
points) with its per-lane leap."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.netsim import api, collectives, workloads
from repro.netsim.engine import SimConfig, build
from repro.netsim.scenarios import Scenario
from repro.netsim.units import FatTreeConfig, LinkConfig

TREE = FatTreeConfig(racks=2, nodes_per_rack=4, uplinks=2)
OVERSUB = FatTreeConfig(racks=2, nodes_per_rack=8, uplinks=2)   # 4:1
TREE3 = FatTreeConfig(racks=4, nodes_per_rack=2, uplinks=2,
                      pods=2, core_uplinks=1)                   # core 2:1
LINK = LinkConfig()


def _run(tree, wl, leap, max_ticks=30000, **kw):
    sim = build(SimConfig(link=LINK, tree=tree, leap=leap, **kw), wl)
    st = sim.run(max_ticks=max_ticks)
    st.now.block_until_ready()
    return sim, st


def _assert_state_equal(st_a, st_b):
    la, lb = jax.tree.leaves(st_a), jax.tree.leaves(st_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_leap_equal(tree, wl, max_ticks=30000, **kw):
    _, st_off = _run(tree, wl, leap=False, max_ticks=max_ticks, **kw)
    _, st_on = _run(tree, wl, leap=True, max_ticks=max_ticks, **kw)
    _assert_state_equal(st_off, st_on)
    return st_on


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_leap_bit_for_bit_incast(backend):
    wl = workloads.incast(TREE, degree=3, size_bytes=16 * 4096, seed=0)
    _assert_leap_equal(TREE, wl, cc_backend=backend)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_leap_bit_for_bit_oversubscribed_permutation(backend):
    """Trims, retransmissions, RED marking — the congested regime where a
    wrong horizon would skip a deliverable event."""
    wl = workloads.permutation(OVERSUB, size_bytes=64 * 4096, seed=1)
    st = _assert_leap_equal(OVERSUB, wl, cc_backend=backend)
    assert int(st.m.n_trim) > 0


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_leap_bit_for_bit_windowed_alltoall(backend):
    wl = workloads.alltoall(TREE, size_bytes=8 * 4096, window=2, nodes=6)
    _assert_leap_equal(TREE, wl, max_ticks=60000, cc_backend=backend)


def test_leap_bit_for_bit_pallas_fabric_transport():
    """Leap parity with the fabric enqueue-rank/arbitration and transport
    ring-drain kernels on the pallas backend: the leap's no-op-tick
    contract has to hold through the kernels' padded tiles too (a padded
    lane that wrote anything would break bitwise equality here)."""
    wl = workloads.permutation(OVERSUB, size_bytes=32 * 4096, seed=1)
    st = _assert_leap_equal(OVERSUB, wl, fabric_backend="pallas",
                            transport_backend="pallas")
    assert int(st.m.n_trim) > 0


def test_leap_bit_for_bit_sparse_heavy_tailed():
    """The perf target: spread-out arrivals with heavy-tailed sizes keep
    the fabric quiescent most of the span — exactly where the leap engine
    must skip thousands of ticks and still land on every event."""
    wl = workloads.heavy_tailed(TREE, 10, size_base=2 * 4096,
                                size_cap=64 * 4096, gap_mean=1200.0, seed=2)
    st = _assert_leap_equal(TREE, wl, max_ticks=40000)
    assert int(st.now) > 5000          # the span really is sparse


def test_leap_bit_for_bit_three_tier_sparse():
    """Three-tier fabric: the longer (cross-core) wire/control rings and
    the extra routed tiers must leave the horizon reductions exact."""
    wl = workloads.heavy_tailed(TREE3, 10, size_base=2 * 4096,
                                size_cap=64 * 4096, gap_mean=1200.0, seed=11)
    st = _assert_leap_equal(TREE3, wl, max_ticks=40000)
    assert int(st.now) > 5000          # the span really is sparse


def test_leap_bit_for_bit_three_tier_core_fault():
    """A dead core uplink forces blackhole -> RTO cycles across the T2
    plane; the timeout horizon must land the leap on every expiry."""
    wl = workloads.permutation(TREE3, size_bytes=64 * 4096, seed=3)
    st = _assert_leap_equal(TREE3, wl, faults=(("t1_up", 0, 0, 0),),
                            fault_start=0, max_ticks=40000)
    assert int(st.m.n_black) > 0 and int(st.m.n_to) > 0


def test_leap_lands_on_timeouts():
    """Without trimming, recovery is timeout-driven: the leap must land
    exactly on each RTO expiry (first tick strictly beyond send + rto)."""
    wl = workloads.incast(OVERSUB, degree=6, size_bytes=32 * 4096, seed=3)
    st = _assert_leap_equal(OVERSUB, wl, trimming=False)
    assert int(st.m.n_to) > 0          # timeouts actually fired


def test_leap_with_dead_link_timeout_cycles():
    """A blackholed uplink forces RTO -> retransmit cycles with long
    quiescent waits in between — the timeout-dominated leap regime."""
    wl = workloads.permutation(OVERSUB, size_bytes=64 * 4096, seed=4)
    st = _assert_leap_equal(OVERSUB, wl, faults=((0, 1, 0),),
                            fault_start=100)
    assert int(st.m.n_black) > 0 and int(st.m.n_to) > 0


def test_leap_with_degraded_link_service_periods():
    """A half-rate link services its queue every other tick; the horizon
    treats any occupied port as eventful, so the leap must stay exact."""
    wl = workloads.permutation(OVERSUB, size_bytes=64 * 4096, seed=5)
    _assert_leap_equal(OVERSUB, wl, faults=((0, 1, 2),), fault_start=0)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_leap_bit_for_bit_fault_schedule_multi_transition(backend):
    """A FaultSchedule with four transitions (fail -> degrade -> repair,
    plus an independent late kill) under a nonzero fault_start: the
    fault-transition clamp in ``fabric.horizon`` must stop every leap at
    each state change, on both CC backends (ISSUE 8 acceptance: >= 3
    transitions, leap-on == leap-off bitwise)."""
    from repro.netsim.faults import FaultEvent, FaultSchedule
    sched = FaultSchedule(events=(
        FaultEvent(t=0, kind="t1_up", i=0, j=0, period=0),
        FaultEvent(t=400, kind="t1_up", i=0, j=0, period=3),
        FaultEvent(t=900, kind="t1_up", i=0, j=0, period=1),
        FaultEvent(t=1200, kind="t2_down", i=0, j=1, period=0)))
    wl = workloads.permutation(TREE3, size_bytes=64 * 4096, seed=3)
    st = _assert_leap_equal(TREE3, wl, faults=sched, fault_start=60,
                            max_ticks=40000, cc_backend=backend)
    assert int(st.m.n_black) > 0


def test_leap_bit_for_bit_flapping_uplink():
    """A flapping uplink alternates dead/healthy on a fixed cycle; the
    clamp must stop leaps at every phase boundary inside the window and
    ignore the flap entirely outside it."""
    from repro.netsim.faults import Flap, FaultSchedule
    sched = FaultSchedule(flaps=(
        Flap(kind="t0_up", i=0, j=1, up=40, cycle=90, t=50, t_end=1000),))
    wl = workloads.permutation(OVERSUB, size_bytes=64 * 4096, seed=4)
    st = _assert_leap_equal(OVERSUB, wl, faults=sched, fault_start=30)
    assert int(st.m.n_black) > 0


def test_leap_bit_for_bit_recovery_transport():
    """RTO backoff + REPS timeout eviction under a fail-then-repair
    schedule: the timeout horizon reads the *backed-off* per-flow RTO, so
    the leap must land exactly on every delayed retry."""
    from repro.netsim.faults import FaultEvent, FaultSchedule
    sched = FaultSchedule(events=(
        FaultEvent(t=100, kind="t0_up", i=0, j=0, period=0),
        FaultEvent(t=100, kind="t0_up", i=0, j=1, period=0),
        FaultEvent(t=2500, kind="t0_up", i=0, j=0, period=1),
        FaultEvent(t=2500, kind="t0_up", i=0, j=1, period=1)))
    wl = workloads.permutation(OVERSUB, size_bytes=64 * 4096, seed=6)
    st = _assert_leap_equal(OVERSUB, wl, faults=sched,
                            rto_backoff_max=3, evict_on_timeout=True)
    # backoff itself ends at 0 (the post-repair ACKs reset it); the
    # timeout count proves the delayed retries actually happened
    assert int(st.m.n_to) > 0


def test_leap_bit_for_bit_eqds_grants():
    """Credit-based algorithms add the grant-demand and credit-ring
    horizons; sparse starts make the receiver pacing the only clock."""
    wl = workloads.heavy_tailed(TREE, 8, size_base=4 * 4096,
                                size_cap=32 * 4096, gap_mean=800.0, seed=6)
    _assert_leap_equal(TREE, wl, algo="eqds", max_ticks=40000)
    _assert_leap_equal(TREE, wl, algo="eqds_smartt", max_ticks=40000)


@pytest.mark.parametrize("algo", ["swift", "mprdma", "ecn_only",
                                  "delay_only"])
def test_leap_bit_for_bit_baseline_algorithms(algo):
    """Dims.leap's contract — the CC choice mutates no state on event-free
    ticks — is per-algorithm: every non-paced baseline the figure suite
    runs leap-on must stay bitwise equal, so a future time-dependent term
    added to one of them fails here instead of silently skewing figures."""
    wl = workloads.heavy_tailed(TREE, 6, size_base=2 * 4096,
                                size_cap=32 * 4096, gap_mean=600.0, seed=8)
    _assert_leap_equal(TREE, wl, algo=algo, max_ticks=20000)


@pytest.mark.parametrize("lb", ["spray", "ecmp"])
def test_leap_bit_for_bit_other_load_balancers(lb):
    """Same contract for the LB hooks that keep leaping enabled (PLB is
    excluded statically; REPS is covered by every other test here)."""
    wl = workloads.heavy_tailed(TREE, 6, size_base=2 * 4096,
                                size_cap=32 * 4096, gap_mean=600.0, seed=9)
    _assert_leap_equal(TREE, wl, lb=lb, max_ticks=20000)


def test_leap_forced_off_for_paced_and_plb():
    """Rate pacing accrues budget every tick and PLB rolls its round clock
    on wall time — event-free ticks are not no-ops there, so the leap must
    be statically disabled no matter the knob."""
    wl = workloads.incast(TREE, degree=3, size_bytes=16 * 4096, seed=0)
    assert not build(SimConfig(link=LINK, tree=TREE, algo="bbr",
                               leap=True), wl).dims.leap
    assert not build(SimConfig(link=LINK, tree=TREE, lb="plb",
                               leap=True), wl).dims.leap
    assert build(SimConfig(link=LINK, tree=TREE, leap=True), wl).dims.leap


def _lanes(tree, wl, leap, max_ticks, **study_kw):
    """Final ``[B]`` states of a Study over ``wl`` (leap on or off)."""
    sc = Scenario(name=wl.name, wl=wl,
                  cfg=SimConfig(link=LINK, tree=tree, leap=leap))
    return api.study(sc, **study_kw).run_states(max_ticks=max_ticks)


def test_leap_seed_lanes_per_lane_horizons():
    """Batched lanes leap independently (each by its own horizon, frozen
    once done — shard.run_lanes); every lane must match its leap-off
    twin bit-for-bit."""
    wl = workloads.heavy_tailed(OVERSUB, 8, size_base=4 * 4096,
                                size_cap=64 * 4096, gap_mean=900.0, seed=7)
    st_on = _lanes(OVERSUB, wl, True, 40000, seeds=range(4))
    st_off = _lanes(OVERSUB, wl, False, 40000, seeds=range(4))
    _assert_state_equal(st_off, st_on)


def test_leap_sweep_per_point_horizons():
    """The sweep leap evaluates each grid point's horizon under its own
    swept Consts (different RTOs / start windows!) and each lane jumps by
    its own distance (shard.run_lanes)."""
    wl = workloads.incast(TREE, degree=4, size_bytes=32 * 4096, seed=1)
    points = [{"start_cwnd_mult": a, "rto_mult": r}
              for a, r in ((0.5, 3.0), (1.25, 5.0))]
    st_on = _lanes(TREE, wl, True, 30000, points=points)
    st_off = _lanes(TREE, wl, False, 30000, points=points)
    _assert_state_equal(st_off, st_on)


def test_leap_bit_for_bit_dependency_gated_ring_allreduce():
    """Dependency-gated activation (DESIGN.md Sec. 11): the horizon
    shares ``sender.activated`` with admission, and threshold crossings
    ride on deliveries the fabric horizon already bounds — so leap-on
    must stay bitwise equal through a full ring allreduce whose every
    flow past step 0 is released by a parent's chunk landing."""
    wl = collectives.ring_allreduce(TREE3, chunk_bytes=4 * 4096, nodes=8)
    st = _assert_leap_equal(TREE3, wl, max_ticks=40000)
    assert bool(np.asarray(st.done).all())


def test_leap_bit_for_bit_dependency_chain_sparse():
    """A staggered pipeline chain: activation alternates between
    start-clamped waits (t_start far beyond the dependency release) and
    dep-driven releases, with multi-thousand-tick quiescent stretches in
    between — the regime where an unclamped dependency term would let
    the leap overshoot a release tick."""
    pl = collectives.pipeline(TREE, stage_bytes=8 * 4096, stages=4,
                              microbatches=2)
    wl = dataclasses.replace(
        pl, t_start=(3000 * np.arange(pl.n_flows)).astype(np.int32))
    st = _assert_leap_equal(TREE, wl, max_ticks=40000)
    assert bool(np.asarray(st.done).all())
    assert int(st.now) > 5000          # the span really is sparse
