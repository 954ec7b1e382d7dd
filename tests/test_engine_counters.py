"""Run-loop instrumentation: the loop counters change nothing in the final
state and add up to ``now``; the live-flow count equals a recount on the
host, tick by tick; the lowered loop names the tick's phases and the
loop's own control; with counters off the loop carries the state alone
and never counts live flows; the program's host spans land in a profiler
trace."""

import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.netsim import api, engine, scenarios, sender, shard, state, \
    workloads
from repro.netsim.engine import SimConfig, build
from repro.netsim.units import FatTreeConfig, LinkConfig

TREE = FatTreeConfig(racks=2, nodes_per_rack=4, uplinks=2)
LINK = LinkConfig()
MAX_TICKS = 40000
SCOPES = ("departures", "arrivals", "control", "grants", "sends", "metrics",
          "leap", "loop_ctl")
# spread-out arrivals: the fabric is quiescent most of the span, so the
# leap has stretches to skip
SPARSE = workloads.heavy_tailed(TREE, 6, size_base=2 * 4096,
                                size_cap=16 * 4096, gap_mean=1200.0, seed=2)


def _sim(leap, superstep, **kw):
    return build(SimConfig(link=LINK, tree=TREE, leap=leap,
                           superstep=superstep, **kw), SPARSE)


def _assert_state_equal(st_a, st_b):
    la, lb = jax.tree.leaves(st_a), jax.tree.leaves(st_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _check_counts(c, now, leap, superstep):
    executed, leapt = np.asarray(c.ticks_executed), np.asarray(c.ticks_leapt)
    np.testing.assert_array_equal(executed + leapt, now)
    if leap:
        assert np.all(leapt > 0) and np.all(np.asarray(c.leaps) > 0)
    else:
        np.testing.assert_array_equal(executed, now)
        assert not np.any(leapt) and not np.any(np.asarray(c.leaps))
    assert np.all(np.asarray(c.supersteps) * superstep >= executed)
    live = np.asarray(c.flow_ticks_live)
    assert np.all(live > 0) and np.all(live <= executed * SPARSE.n_flows)


@pytest.mark.parametrize("leap", [False, True], ids=["leap_off", "leap_on"])
@pytest.mark.parametrize("superstep", [1, 0], ids=["k1", "k_auto"])
def test_single_run_counters_leave_state_bit_identical(leap, superstep):
    sim = _sim(leap, superstep)
    st = sim.run(MAX_TICKS, seed=3)
    st_c, c = sim.run(MAX_TICKS, seed=3, counters=True)
    _assert_state_equal(st, st_c)
    assert int(st_c.now) > 5000
    _check_counts(c, int(st_c.now), leap, sim.dims.superstep)


@pytest.mark.parametrize("path", ["vmap", "shard_map"])
@pytest.mark.parametrize("leap", [False, True], ids=["leap_off", "leap_on"])
@pytest.mark.parametrize("superstep", [1, 0], ids=["k1", "k_auto"])
def test_lane_loop_counters_leave_state_bit_identical(path, leap, superstep):
    sim = _sim(leap, superstep)
    horizon = sim.horizon_fn if sim.dims.leap else None
    axes = api.no_axes(sim.consts)
    K = sim.dims.superstep

    def lanes(counters):
        base = sim.init()
        states = jax.tree.map(
            lambda x: jax.numpy.broadcast_to(x[None], (3,) + x.shape), base)
        states = states._replace(salt=jax.numpy.arange(3, dtype=np.int32))
        live_flows = sim.live_flows if counters else None
        if path == "vmap":
            return shard.run_lanes(sim.step_fn, horizon, axes, MAX_TICKS, K,
                                   sim.consts, states, counters=counters,
                                   live_flows=live_flows)
        mesh = shard.lane_mesh(jax.devices()[:1])
        return shard._run_lanes_sharded(sim.step_fn, horizon, axes,
                                        MAX_TICKS, K, mesh, sim.consts,
                                        states, counters, live_flows)

    st = lanes(False)
    st_c, c = lanes(True)
    _assert_state_equal(st, st_c)
    assert np.asarray(c.ticks_executed).shape == (3,)
    _check_counts(c, np.asarray(st_c.now), leap, K)


def _recount(sim, max_ticks, salt):
    """``flow_ticks_live`` recounted on the host: the run loop's leaps,
    supersteps and per-tick gate stepped by hand, one jitted tick at a
    time, summing ``sender.activated`` over the ticks that step; returns
    the sum and the final state."""
    dims = sim.dims

    def cond(st):
        return bool(st.now < max_ticks) and not bool(np.all(st.done))

    live = jax.jit(lambda st: sender.activated(dims, sim.consts, st))
    step = jax.jit(sim.step)
    leap = jax.jit(engine._leap(sim.horizon, max_ticks))
    st = state.ring_loop_form(sim.init()._replace(
        salt=jax.numpy.asarray(salt, np.int32)))
    total = 0
    while cond(st):
        if dims.leap:
            st, _ = leap(st)
        for _ in range(max(dims.superstep, 1)):
            if cond(st):
                total += int(np.sum(np.asarray(live(st))))
                st = step(st)
    rows, cap = sim.init().q_fields.shape[:2]
    return total, state.ring_public_form(st, rows, cap)


RING = scenarios.scenario("tiny_allreduce_ring")


@pytest.mark.parametrize("leap", [False, True], ids=["leap_off", "leap_on"])
@pytest.mark.parametrize("superstep", [1, 0], ids=["k1", "k_auto"])
def test_flow_ticks_live_equals_a_host_recount(leap, superstep):
    sim = RING.with_(leap=leap, superstep=superstep).build()
    assert sim.dims.D == 1
    want, st_host = _recount(sim, RING.max_ticks, 5)
    st, c = sim.run(RING.max_ticks, seed=5, counters=True)
    _assert_state_equal(st_host, st)
    assert int(c.flow_ticks_live) == want
    # the ring's 8 ranks send one chunk at a time each, of 112 flows
    assert 0 < want <= 8 * int(c.ticks_executed)


def test_flow_ticks_live_equals_a_host_recount_per_study_lane():
    study = api.study(RING, seeds=(0, 1))
    sim = study.sim
    horizon = sim.horizon_fn if sim.dims.leap else None
    st, c = shard.run_lanes(sim.step_fn, horizon, study.axes,
                            RING.max_ticks, sim.dims.superstep,
                            study.consts_b, study.init(), counters=True,
                            live_flows=sim.live_flows)
    assert np.asarray(c.flow_ticks_live).shape == (2,)
    for lane, salt in enumerate(study.salts):
        want, st_host = _recount(sim, RING.max_ticks, salt)
        assert int(c.flow_ticks_live[lane]) == want
        _assert_state_equal(st_host, jax.tree.map(lambda x: x[lane], st))


def test_counting_needs_the_live_flow_count():
    sim = _sim(True, 0)
    with pytest.raises(ValueError, match="live_flows"):
        engine._run_until_done(sim.step_fn, sim.horizon_fn, sim.consts,
                               sim.init(), MAX_TICKS, sim.dims.superstep,
                               True)


def _lower(sim, counters=False, live_flows=None):
    return engine._run_until_done.lower(
        sim.step_fn, sim.horizon_fn if sim.dims.leap else None, sim.consts,
        sim.init(), MAX_TICKS, sim.dims.superstep, counters, live_flows)


def _never(consts, st):
    raise AssertionError("live flows counted with counters off")


@pytest.mark.parametrize("scenario", ["sparse", "tiny_allreduce_ring"])
def test_loop_without_counters_never_counts_live_flows(scenario):
    sim = _sim(True, 0) if scenario == "sparse" else RING.build()
    plain = _lower(sim).as_text()
    assert _lower(sim, False, _never).as_text() == plain
    # Sim.run passes its count only to a counting loop
    never = dataclasses.replace(sim, live_flows=_never)
    want = sim.run(MAX_TICKS, seed=2)
    _assert_state_equal(never.run(MAX_TICKS, seed=2), want)


def test_lowered_loop_names_every_scope():
    # EQDS, so that the grants phase does work
    text = _lower(_sim(True, 0, algo="eqds")).as_text(debug_info=True)
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope


def _while_carry(sim, counters):
    """The run loop's while carry as (state leaves it takes as passed,
    other values); the leaves the loop never changes travel as constants,
    and the port-queue ring travels in its loop form."""
    st = sim.init()
    closed = jax.make_jaxpr(
        lambda c, s: engine._run_until_done(
            sim.step_fn, sim.horizon_fn, c, s, MAX_TICKS,
            sim.dims.superstep, counters,
            sim.live_flows if counters else None))(sim.consts, st)
    (jit,) = closed.eqns
    inner = jit.params["jaxpr"].jaxpr
    (loop,) = [e for e in inner.eqns if e.primitive.name == "while"]
    n_consts = loop.params["cond_nconsts"] + loop.params["body_nconsts"]
    state_vars = inner.invars[-len(jax.tree.leaves(st)):]
    carry = loop.invars[n_consts:]
    taken = [v for v in carry if v in state_vars]
    return taken, [v.aval for v in carry if v not in state_vars]


def test_loop_carry_holds_the_state_alone_without_counters():
    sim = _sim(True, 0)
    ring = jax.eval_shape(state.ring_loop_form,
                          jax.eval_shape(sim.init)).q_fields
    ring = [(ring.shape, ring.dtype)]
    taken, other = _while_carry(sim, False)
    assert taken and [(a.shape, a.dtype) for a in other] == ring
    taken_c, other_c = _while_carry(sim, True)
    assert len(taken_c) == len(taken)
    assert [(a.shape, a.dtype) for a in other_c] == ring + [((), np.int32)] * 5


def test_api_run_reports_counters():
    res = api.run("tiny_3t", counters=True)
    c = res.counters
    assert isinstance(c.ticks_executed, int)
    assert c.ticks_executed + c.ticks_leapt == res.ticks
    assert res.summary()["loop"] == c._asdict()
    plain = api.run("tiny_3t")
    assert plain.counters is None and "loop" not in plain.summary()
    np.testing.assert_array_equal(plain.fct, res.fct)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    names = set()
    for path in glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    names.update(e.name for e in line.events
                                 if e.name.startswith("netsim."))
    return names


def test_host_spans_land_in_the_profiler_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        api.run("tiny_3t")
        api.study("tiny_3t", seeds=(0, 1)).run()
        api.study("tiny_3t", seeds=(0, 1)).run(chunk_lanes=1)
    assert _host_spans(tmp_path) == {
        "netsim.build", "netsim.init_state", "netsim.run_loop",
        "netsim.study.init", "netsim.study.lanes", "netsim.study.gather"}
