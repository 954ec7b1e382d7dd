"""The port-queue ring's two forms: the public ``q_fields [NQ+1, CAP, 5]``
and the run loops' flat, field-major form (``state.ring_loop_form``).

The conversion round-trips every element; the two phases that touch the
ring read and write on the loop form exactly what the public form's
``[q, pos]`` indexing reads and writes; and a lane batch, whose loop form
carries the lanes behind the fields, equals its standalone runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.enqueue_arb import ops as enqueue_arb_ops
from repro.netsim import api, state, workloads
from repro.netsim.engine import SimConfig, build
from repro.netsim.scenarios import Scenario
from repro.netsim.units import FatTreeConfig, LinkConfig

I32 = jnp.int32
TREE16 = FatTreeConfig(racks=2, nodes_per_rack=8, uplinks=2)     # 16 hosts
LINK = LinkConfig()


def _ring_only(q_fields):
    return state.SimState(*[None] * len(state.SimState._fields))._replace(
        q_fields=q_fields)


@pytest.mark.parametrize("lead,rows,cap", [((), 7, 13), ((3,), 7, 13),
                                           ((), 4, 32), ((2, 2), 5, 40)],
                         ids=["padded", "lanes", "aligned", "lanes2d"])
def test_loop_form_round_trips_every_element(lead, rows, cap):
    pub = np.arange(np.prod(lead + (rows, cap, 5)), dtype=np.int32
                    ).reshape(lead + (rows, cap, 5)) * 7 + 3
    loop = np.asarray(state.ring_loop_form(_ring_only(jnp.asarray(pub)))
                      .q_fields)
    cols = loop.shape[-1]
    assert loop.shape == (5,) + lead + (cols,)
    assert cols % state.RING_COLS_ALIGN == 0 and cols >= rows * cap
    want = np.zeros_like(loop)
    for q in range(rows):
        for pos in range(cap):
            want[..., q * cap + pos] = np.moveaxis(pub[..., q, pos, :], -1, 0)
    np.testing.assert_array_equal(loop, want)      # pad columns stay zero
    back = state.ring_public_form(_ring_only(jnp.asarray(loop)), rows, cap)
    np.testing.assert_array_equal(np.asarray(back.q_fields), pub)


def _busy_state(sim, ticks):
    """A public mid-run state whose ring holds a distinct odd value in
    every field of every slot (odd, so the departures' ECN mark, an OR
    of 1, leaves the value as read)."""
    st = sim.run(max_ticks=ticks)
    n = (sim.dims.NQ + 1) * sim.dims.CAP * 5
    ring = (jnp.arange(n, dtype=I32) * 2 + 1).reshape(st.q_fields.shape)
    return st._replace(q_fields=ring)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_phases_on_loop_form_match_public_indexing(backend):
    wl = workloads.incast(TREE16, degree=12, size_bytes=64 * 4096, seed=0)
    sim = build(SimConfig(link=LINK, tree=TREE16, fabric_backend=backend), wl)
    dims, consts = sim.dims, sim.consts
    rows, cap, NQ, L = dims.NQ + 1, dims.CAP, dims.NQ, dims.L
    phases = dict(sim.phases)
    pub = _busy_state(sim, 20)
    t = int(pub.now)

    # departures: the head read, against the public form's [qidx, head]
    dep = phases["departures"](consts, state.ring_loop_form(pub))
    dep_pub = state.ring_public_form(dep, rows, cap)
    np.testing.assert_array_equal(np.asarray(dep_pub.q_fields),
                                  np.asarray(pub.q_fields))
    head = np.asarray(pub.q_fields)[np.arange(NQ), np.asarray(pub.q_head)[:NQ]]
    infl = np.asarray(dep.infl)
    qe = dims.QE
    wire = np.concatenate(
        [infl[(t + int(consts.lat_core)) % L, :qe],
         infl[(t + int(consts.lat_edge)) % L, qe:NQ]])
    emit = wire[:, 0] == 1
    assert emit.any(), "no port departed: the state is not busy"
    np.testing.assert_array_equal(wire[:, 2:7],
                                  np.where(emit[:, None], head, 0))

    # arrivals: the enqueue write, against the public form's [row, pos]
    # scatter (ranked by the jnp reference whatever the backend)
    arr = phases["arrivals"](consts, dep)
    earr = dep_pub.infl[t % L][consts.enq_ids]
    edst = jnp.where((earr[:, 0] == 1) & (earr[:, 1] >= 0), earr[:, 1], NQ)
    enqueue, _ = enqueue_arb_ops.get("jnp")
    acc, pos, _ = enqueue(consts.in_tbl, consts.in_pos, consts.sw_of_q, edst,
                          dep_pub.q_head, dep_pub.q_size, cap, NQ)
    assert bool(acc.any()), "nothing enqueued: the state is not busy"
    want = dep_pub.q_fields.at[jnp.where(acc, edst, NQ),
                               jnp.where(acc, pos, 0)].set(
        jnp.where(acc[:, None], earr[:, 2:7], 0))
    np.testing.assert_array_equal(
        np.asarray(state.ring_public_form(arr, rows, cap).q_fields),
        np.asarray(want))


@pytest.mark.parametrize("max_ticks", [60, 30000], ids=["mid_run", "done"])
def test_seed_lanes_ring_equals_standalone_runs(max_ticks):
    wl = workloads.incast(TREE16, degree=12, size_bytes=32 * 4096, seed=1)
    sim = build(SimConfig(link=LINK, tree=TREE16), wl)
    seeds = (3, 5, 7, 11)
    batch = api.study(Scenario(name=wl.name, cfg=sim.cfg, wl=wl),
                      seeds=seeds).run_states(max_ticks=max_ticks)
    assert batch.q_fields.shape == (4, sim.dims.NQ + 1, sim.dims.CAP, 5)
    for i, s in enumerate(seeds):
        one = sim.run(max_ticks=max_ticks, seed=s)
        np.testing.assert_array_equal(np.asarray(batch.q_fields[i]),
                                      np.asarray(one.q_fields))
        for a, b in zip(jax.tree.leaves(batch), jax.tree.leaves(one)):
            np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b))
    assert np.asarray(batch.q_fields).any()
