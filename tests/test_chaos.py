"""Chaos layer (ISSUE 8): randomized dynamic fault schedules driving the
engine's soundness invariants.

A seeded generator draws arbitrary-but-valid :class:`FaultSchedule`
timelines (fail / degrade / repair events on random ports and switches,
plus bounded flapping windows) that are guaranteed to end all-healthy.
Each drawn schedule must uphold:

* **conservation** — the packet ledger (sent == delivered + trimmed +
  dropped + blackholed + queued + on-wire) closes at every tick boundary;
* **leap parity** — leap-on and leap-off trajectories are bit-for-bit
  identical across the full state pytree (the fault-transition clamp in
  ``fabric.horizon`` is what makes this hold);
* **no permanent stall** — once the last repair lands, every flow
  completes within a generous budget (with and without the recovery
  knobs: a healthy fabric plus armed retransmission timers must always
  drain).

The seeded numpy draws always run; hypothesis (a declared test
dependency — CI installs ``.[test]`` and pins ``derandomize=True``)
additionally drives the same properties through minimized search where
available, matching the ``tests/test_topology.py`` idiom.
"""

import jax
import numpy as np
import pytest

from repro.netsim import state, workloads
from repro.netsim.engine import SimConfig, build
from repro.netsim.faults import FaultEvent, FaultSchedule, Flap
from repro.netsim.metrics import conservation_ledger
from repro.netsim.units import FatTreeConfig, LinkConfig

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:              # local envs without the test extra
    HAVE_HYPOTHESIS = False

LINK = LinkConfig()
TREE3 = FatTreeConfig(racks=4, nodes_per_rack=2, uplinks=2,
                      pods=2, core_uplinks=1)                      # core 2:1

# every (kind, i, j) coordinate valid on TREE3, switch kills included
_TARGETS = (
    [("t0_up", i, j) for i in range(4) for j in range(2)]
    + [("t1_up", i, 0) for i in range(4)]
    + [("t2_down", i, j) for i in range(2) for j in range(2)]
    + [("t1_down", i, j) for i in range(4) for j in range(2)]
    + [("switch", i, 0) for i in range(4, 10)]    # T1 + core switches
)

# all real faults end by here; every touched target is repaired at T_HEAL
T_HEAL = 300


def chaos_schedule(seed: int) -> FaultSchedule:
    """A random valid schedule over TREE3 that ends all-healthy: up to 5
    fail/degrade/repair events and up to one flap window, all strictly
    inside [0, T_HEAL), plus a closing repair for every touched target."""
    rng = np.random.default_rng(seed)
    touched, events = set(), []
    for _ in range(int(rng.integers(1, 6))):
        kind, i, j = _TARGETS[int(rng.integers(len(_TARGETS)))]
        t = int(rng.integers(0, 250))
        period = int(rng.choice([0, 0, 1, 2, 3]))   # lean toward dead
        events.append(FaultEvent(t=t, kind=kind, i=i, j=j, period=period))
        touched.add((kind, i, j))
    flaps = ()
    if rng.integers(2):
        kind, i, j = _TARGETS[int(rng.integers(len(_TARGETS)))]
        cycle = int(rng.integers(8, 40))
        up = int(rng.integers(1, cycle))
        t0 = int(rng.integers(0, 120))
        flaps = (Flap(kind=kind, i=i, j=j, up=up, cycle=cycle,
                      t=t0, t_end=int(rng.integers(t0 + 1, T_HEAL))),)
    events += [FaultEvent(t=T_HEAL, kind=k, i=i, j=j, period=1)
               for (k, i, j) in sorted(touched)]
    return FaultSchedule(events=tuple(events), flaps=flaps)


def _recovery_knobs(seed: int) -> dict:
    """Half the draws run with the recovery transport on."""
    if seed % 2:
        return dict(rto_backoff_max=2, evict_on_timeout=True)
    return {}


def check_conservation(seed: int, ticks: int = 400) -> None:
    wl = workloads.permutation(TREE3, size_bytes=24 * 4096, seed=seed)
    sched = chaos_schedule(seed)
    sim = build(SimConfig(link=LINK, tree=TREE3, faults=sched,
                          **_recovery_knobs(seed)), wl)
    step = jax.jit(sim.step)
    s = state.ring_loop_form(sim.init())
    for t in range(ticks):
        s = step(s)
        sent, accounted = conservation_ledger(sim.dims, s)
        assert sent == accounted, (
            f"seed {seed} tick {t + 1}: {sent} sent, {accounted} accounted"
            f"\nschedule: {sched}")


def check_leap_parity(seed: int, max_ticks: int = 6000) -> None:
    wl = workloads.permutation(TREE3, size_bytes=24 * 4096, seed=seed)
    sched = chaos_schedule(seed)
    kw = dict(faults=sched, fault_start=int(seed % 3) * 17,
              **_recovery_knobs(seed))
    states = {}
    for leap in (False, True):
        sim = build(SimConfig(link=LINK, tree=TREE3, leap=leap, **kw), wl)
        states[leap] = sim.run(max_ticks=max_ticks)
        states[leap].now.block_until_ready()
    la, lb = jax.tree.leaves(states[False]), jax.tree.leaves(states[True])
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"seed {seed}\n{sched}")


def check_no_permanent_stall(seed: int, budget: int = 30000) -> None:
    wl = workloads.permutation(TREE3, size_bytes=24 * 4096, seed=seed)
    sched = chaos_schedule(seed)
    sim = build(SimConfig(link=LINK, tree=TREE3, faults=sched,
                          **_recovery_knobs(seed)), wl)
    s = sim.run(max_ticks=budget)
    done = np.asarray(s.done)
    assert done.all(), (
        f"seed {seed}: {int(done.sum())}/{done.size} flows done after "
        f"{budget} ticks on an all-healthy-after-{T_HEAL} fabric"
        f"\nschedule: {sched}")


# ---- seeded draws (always run) -------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_chaos_conservation(seed):
    check_conservation(seed)


@pytest.mark.parametrize("seed", range(8))
def test_chaos_leap_parity(seed):
    check_leap_parity(seed)


@pytest.mark.parametrize("seed", range(4))
def test_chaos_no_permanent_stall(seed):
    check_no_permanent_stall(seed)


def test_chaos_schedule_generator_is_valid_and_heals():
    """Generator sanity: every draw compiles against the topology and is
    all-healthy at and after T_HEAL."""
    from repro.netsim import faults as fm
    from repro.netsim.state import derive
    wl = workloads.permutation(TREE3, size_bytes=4096, seed=0)
    topo, _, _, _ = derive(SimConfig(link=LINK, tree=TREE3), wl)
    for seed in range(40):
        cf = fm.compile_tables(chaos_schedule(seed), topo, 0)
        for t in (T_HEAL, T_HEAL + 1, T_HEAL + 1000):
            assert (fm.np_port_period(cf, 0, t) == 1).all(), seed


# ---- hypothesis search (when available; CI pins the seed) ----------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=12, deadline=None, derandomize=True)
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    def test_chaos_leap_parity_hypothesis(seed):
        check_leap_parity(seed, max_ticks=4000)

    @settings(max_examples=8, deadline=None, derandomize=True)
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    def test_chaos_conservation_hypothesis(seed):
        check_conservation(seed, ticks=250)


# ---- dependency-gated collectives under faults (DESIGN.md Sec. 11) -------

def check_collective_no_stall(seed: int, budget: int = 30000) -> None:
    """A mid-collective fault must never deadlock activation: once the
    schedule heals (all-healthy after T_HEAL by construction), stalled
    parents finish via timeout recovery and every dependent flow is
    eventually released — the DAG drains."""
    from repro.netsim import collectives
    wl = collectives.ring_allreduce(TREE3, chunk_bytes=4 * 4096, nodes=8)
    sched = chaos_schedule(seed)
    sim = build(SimConfig(link=LINK, tree=TREE3, faults=sched,
                          **_recovery_knobs(seed)), wl)
    s = sim.run(max_ticks=budget)
    done = np.asarray(s.done)
    assert done.all(), (
        f"seed {seed}: {int(done.sum())}/{done.size} collective flows done "
        f"after {budget} ticks on an all-healthy-after-{T_HEAL} fabric"
        f"\nschedule: {sched}")


@pytest.mark.parametrize("seed", range(4))
def test_chaos_collective_no_permanent_stall(seed):
    check_collective_no_stall(seed)


def test_mid_collective_uplink_kill_does_not_deadlock():
    """The ISSUE's pointed case: kill both uplinks of the rack hosting a
    ring participant mid-collective, heal later; the dependency chain
    threads through the dead rack, so a wrong activation predicate (or a
    lost release) would stall the whole ring forever."""
    from repro.netsim import collectives
    wl = collectives.ring_allreduce(TREE3, chunk_bytes=4 * 4096, nodes=8)
    sched = FaultSchedule(events=(
        FaultEvent(t=40, kind="t0_up", i=0, j=0, period=0),
        FaultEvent(t=40, kind="t0_up", i=0, j=1, period=0),
        FaultEvent(t=400, kind="t0_up", i=0, j=0, period=1),
        FaultEvent(t=400, kind="t0_up", i=0, j=1, period=1)))
    sim = build(SimConfig(link=LINK, tree=TREE3, faults=sched), wl)
    s = sim.run(max_ticks=30000)
    assert int(s.m.n_black) > 0, "the kill never bit"
    assert bool(np.asarray(s.done).all()), "collective stalled permanently"
