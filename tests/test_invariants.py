"""Engine-wide packet-conservation invariant.

Every data packet the NICs ever emitted is, at every tick boundary, in
exactly one place: delivered at a receiver, trimmed/dropped at a full
queue, blackholed on a dead link, parked in a port queue, or in flight on
the wire ring.  Emissions are counted from transport state (``next_seq``
counts first sends, ``n_retx`` counts retransmissions), so the ledger

    sum(next_seq) + n_retx ==
        delivered + trimmed + dropped + blackholed + queued + on_wire

closes with no slack term — the soundness contract the delay-ring design
(zero-on-read; valid entry <=> live event) and therefore the event-horizon
leap machinery rest on (DESIGN.md Sec. 6.3).  Checked tick by tick, for
trimming on and off, on two- and three-tier fabrics including faulted
links.
"""

import jax
import numpy as np
import pytest

from repro.netsim import state, workloads
from repro.netsim.engine import SimConfig, build
from repro.netsim.metrics import conservation_ledger
from repro.netsim.units import FatTreeConfig, LinkConfig

LINK = LinkConfig()
TREE2 = FatTreeConfig(racks=2, nodes_per_rack=4, uplinks=2)        # 4:1
TREE3 = FatTreeConfig(racks=4, nodes_per_rack=2, uplinks=2,
                      pods=2, core_uplinks=1)                      # core 2:1


def _check_conservation(tree, wl, ticks, **cfg_kw):
    sim = build(SimConfig(link=LINK, tree=tree, **cfg_kw), wl)
    step = jax.jit(sim.step)
    st = state.ring_loop_form(sim.init())
    for t in range(ticks):
        st = step(st)
        sent, accounted = conservation_ledger(sim.dims, st)
        assert sent == accounted, (
            f"tick {t + 1}: {sent} packets sent but {accounted} accounted "
            f"(delivered+trimmed+dropped+blackholed+queued+on-wire)")
    return state.ring_public_form(st, sim.dims.NQ + 1, sim.dims.CAP)


@pytest.mark.parametrize("trimming", [True, False],
                         ids=["trim", "drop"])
def test_conservation_two_tier_oversubscribed(trimming):
    """A 4:1 incast overflows queues: the trim (or drop) path must account
    for every rejected packet, every tick."""
    wl = workloads.incast(TREE2, degree=6, size_bytes=24 * 4096, seed=0)
    st = _check_conservation(TREE2, wl, 500, trimming=trimming)
    lost = int(st.m.n_trim) if trimming else int(st.m.n_drop)
    assert lost > 0, "scenario was meant to overflow queues"


@pytest.mark.parametrize("trimming", [True, False],
                         ids=["trim", "drop"])
def test_conservation_three_tier_core(trimming):
    """Cross-core permutation on an oversubscribed three-tier fabric."""
    wl = workloads.permutation(TREE3, size_bytes=24 * 4096, seed=2)
    st = _check_conservation(TREE3, wl, 500, trimming=trimming)
    assert int(st.m.delivered_pkts) > 0


def test_conservation_with_dead_and_degraded_core_links():
    """Blackholed packets leave the fabric through the n_black counter;
    a half-rate core link only delays, never loses."""
    wl = workloads.permutation(TREE3, size_bytes=64 * 4096, seed=3)
    st = _check_conservation(
        TREE3, wl, 600,
        faults=(("t1_up", 0, 0, 0), ("t2_down", 0, 1, 2)), fault_start=0)
    assert int(st.m.n_black) > 0, "dead core uplink never blackholed"


def test_conservation_under_dynamic_fault_schedule():
    """The ledger must close tick by tick through fail -> degrade ->
    repair transitions and a whole-switch kill (every port the switch
    owns blackholes at once, then all come back) — ISSUE 8 soundness."""
    from repro.netsim.faults import FaultEvent, FaultSchedule
    wl = workloads.permutation(TREE3, size_bytes=64 * 4096, seed=3)
    sched = FaultSchedule(events=(
        FaultEvent(t=50, kind="t1_up", i=0, j=0, period=0),
        FaultEvent(t=200, kind="t1_up", i=0, j=0, period=2),
        FaultEvent(t=350, kind="t1_up", i=0, j=0, period=1),
        FaultEvent(t=120, kind="switch", i=5, period=0),       # a T1 switch
        FaultEvent(t=420, kind="switch", i=5, period=1)))
    st = _check_conservation(TREE3, wl, 600, faults=sched)
    assert int(st.m.n_black) > 0, "schedule never blackholed a packet"


def test_conservation_with_recovery_transport():
    """RTO backoff + REPS eviction change *when* retransmissions happen,
    never how many packets exist — the ledger must stay exact."""
    from repro.netsim.faults import FaultEvent, FaultSchedule
    wl = workloads.permutation(TREE3, size_bytes=64 * 4096, seed=4)
    sched = FaultSchedule(events=(
        FaultEvent(t=30, kind="t1_up", i=1, j=0, period=0),
        FaultEvent(t=450, kind="t1_up", i=1, j=0, period=1)))
    st = _check_conservation(TREE3, wl, 600, faults=sched,
                             rto_backoff_max=3, evict_on_timeout=True)
    assert int(st.m.n_to) > 0, "recovery path never exercised"


@pytest.mark.parametrize("trimming", [True, False],
                         ids=["trim", "drop"])
def test_conservation_pallas_fabric_transport(trimming):
    """The ledger must close identically when the enqueue-rank/arbitration
    and ring-drain kernels run on the pallas backend (interpret mode on
    CPU) — the kernels sit exactly on the enqueue/trim and ACK-drain
    edges the ledger counts."""
    wl = workloads.incast(TREE2, degree=6, size_bytes=16 * 4096, seed=0)
    st = _check_conservation(TREE2, wl, 300, trimming=trimming,
                             fabric_backend="pallas",
                             transport_backend="pallas")
    lost = int(st.m.n_trim) if trimming else int(st.m.n_drop)
    assert lost > 0, "scenario was meant to overflow queues"


def test_conservation_eqds_credit_path():
    """Credit-based EQDS adds grant/credit rings; data-packet conservation
    must be untouched by the control plane."""
    wl = workloads.incast(TREE2, degree=5, size_bytes=16 * 4096, seed=4)
    _check_conservation(TREE2, wl, 400, algo="eqds")


def test_paper_scale_three_tier_bit_parity():
    """The acceptance case at paper scale: on the 512-node three-tier
    permutation, the production engine (superstep auto + leap) and a Study
    lane are both bit-for-bit equal to the plain K=1 leap-off run over the
    full final state pytree."""
    from repro.netsim import api
    from repro.netsim.scenarios import scenario

    sc = scenario("perm_512n_3t")
    base = sc.with_(superstep=1, leap=False).build()
    assert base.dims.tiers == 3 and base.dims.N == 512
    st_ref = base.run(max_ticks=sc.max_ticks)
    st_prod = sc.build().run(max_ticks=sc.max_ticks)  # production defaults
    lane = api.study(sc).run_states()     # 1-point x 1-seed lane batch
    for a, b in zip(jax.tree.leaves(st_ref), jax.tree.leaves(st_prod)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(st_ref), jax.tree.leaves(lane)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[0])


def test_conservation_dependency_gated_collective():
    """Dependency gating (DESIGN.md Sec. 11) only delays emissions — it
    must never invent or lose a packet: the ledger closes tick by tick
    through a ring allreduce whose every post-step-0 flow waits on a
    parent chunk, including across the trim-recovery path of the
    oversubscribed core."""
    from repro.netsim import collectives
    wl = collectives.ring_allreduce(TREE3, chunk_bytes=6 * 4096, nodes=8)
    st = _check_conservation(TREE3, wl, 500)
    assert int(st.m.delivered_pkts) > 0
