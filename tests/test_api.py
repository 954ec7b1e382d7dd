"""Experiment API (DESIGN.md Sec. 7): the declarative Scenario/Study
entry point must lower a {point x seed} grid onto ONE compiled step while
keeping every lane bit-for-bit equal to its standalone execution, in
``points`` order; only numeric keys are sweepable (``apply_point``)."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.analysis import trace_guard
from repro.netsim import api, engine, scenarios, workloads
from repro.netsim.api import apply_point
from repro.netsim.scenarios import Scenario, scenario
from repro.netsim.state import SimConfig
from repro.netsim.units import FatTreeConfig, LinkConfig

TREE = FatTreeConfig(racks=2, nodes_per_rack=4, uplinks=2)
LINK = LinkConfig()
CFG = SimConfig(link=LINK, tree=TREE, algo="smartt", lb="reps")

POINTS = ({}, {"start_cwnd_mult": 0.5}, {"rto_mult": 5.0},
          {"start_cwnd_mult": 0.75, "react_every": 4})
SEEDS = (0, 1, 2, 3)
MAX_TICKS = 30_000

# a 9-point tuning grid: initial window x reaction granularity, plus a CC
# gain and RED thresholds swept together
GRID9 = (
    [{"start_cwnd_mult": a, "react_every": r}
     for a in (0.5, 0.75, 1.0, 1.25) for r in (1, 4)]
    + [{"fd": 0.4, "kmin_frac": 0.1, "kmax_frac": 0.5}]
)
# points out of sorted order: lane i must still be points[i]
SHUFFLED = [{"start_cwnd_mult": a} for a in (1.25, 0.5, 1.0)]


def _scenario(leap=True, **cfg_kw) -> Scenario:
    wl = workloads.incast(TREE, degree=4, size_bytes=32 * 4096, seed=1)
    return Scenario(name="t_incast4",
                    cfg=SimConfig(link=LINK, tree=TREE, leap=leap, **cfg_kw),
                    wl=wl, max_ticks=MAX_TICKS)


def _assert_state_equal(st_a, st_b):
    la, lb = jax.tree.leaves(st_a), jax.tree.leaves(st_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _lane(states, i):
    return jax.tree.map(lambda x: x[i], states)


# --------------------------------------------------------------------------
# acceptance: one compile, per-lane bitwise equivalence (leap on and off)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("leap,points,seeds", [
    (True, POINTS, SEEDS),
    (False, POINTS, SEEDS),
    (True, GRID9, (0,)),
    (False, GRID9, (0,)),
    (True, SHUFFLED, (0,)),
], ids=["True", "False", "grid9", "grid9-noleap", "shuffled"])
def test_study_one_compile_and_lanes_match_standalone(leap, points, seeds):
    """A whole {point x seed} Study compiles exactly one step, every lane
    finishes, and every lane's final state equals the standalone
    ``Sim.run`` of that (point, seed) across the FULL SimState pytree —
    ``now``, metrics counters, and RTT histograms included — in
    ``points`` order.  The swept knobs actually change the outcome."""
    sc = _scenario(leap=leap)
    st_obj = api.study(sc, points=points, seeds=seeds)
    assert st_obj.n_lanes == len(points) * len(seeds)

    with trace_guard("engine.step", expect=1):
        res = st_obj.run()
    assert all(r.all_done for r in res)
    assert len({r.completion for r in res}) > 1

    for pi, pt in enumerate(points):
        cfg_i = apply_point(sc.cfg, pt)
        sim_i = engine.build(cfg_i, sc.wl)
        assert sim_i.dims.leap == leap
        for si, seed in enumerate(seeds):
            st_i = sim_i.run(max_ticks=MAX_TICKS, seed=seed)
            _assert_state_equal(st_i,
                                _lane(res.states, pi * len(seeds) + si))
            # the typed lane result reflects the same run
            r = res.lane(pi, si)
            assert r.seed == seed and dict(r.point) == pt
            assert r.ticks == int(st_i.now)
            np.testing.assert_array_equal(r.fct, np.asarray(st_i.fct))


def test_study_lanes_match_standalone_three_tier():
    """Same per-lane bitwise contract on a three-tier scenario: the lane
    loop's per-lane horizons/exits must stay exact with core-path routing
    and the longer cross-core rings."""
    sc = scenario("tiny_3t")
    points = ({}, {"start_cwnd_mult": 0.5})
    seeds = (0, 3)
    res = api.study(sc, points=points, seeds=seeds).run()
    for pi, pt in enumerate(points):
        sim_i = engine.build(apply_point(sc.cfg, pt), sc.wl)
        assert sim_i.dims.tiers == 3
        for si, seed in enumerate(seeds):
            st_i = sim_i.run(max_ticks=sc.max_ticks, seed=seed)
            _assert_state_equal(st_i,
                                _lane(res.states, pi * len(seeds) + si))


def test_sim_run_passes_consts_as_arguments(monkeypatch):
    """``Sim.run`` hands ``Consts`` to its compiled loop as arguments, as
    the lane loop does.  Closed over, the CC parameters become literals
    that XLA folds into the f32 window arithmetic (``cwnd / bdp * fd``
    into one multiply), and on a TPU the standalone run then rounds
    ``cc.cwnd`` differently from the same run as a Study lane."""
    sim = scenario("tiny_3t").build()
    lowered = []
    real = engine._run_until_done

    def spy(*args):
        lowered.append(real.lower(*args).as_text())
        return real(*args)

    monkeypatch.setattr(engine, "_run_until_done", spy)
    sim.run(max_ticks=50)
    bdp = f"dense<{float(sim.consts.cc.bdp):.6e}>"
    assert len(lowered) == 1 and bdp not in lowered[0]


def test_study_single_init_trace():
    """The [P*S] lane batch comes from ONE vmapped init_state trace."""
    st_obj = api.study(_scenario(), points=POINTS, seeds=SEEDS)
    with trace_guard("state.init", expect=1):
        states = st_obj.init()
    np.testing.assert_array_equal(
        np.asarray(states.salt), np.tile(SEEDS, len(POINTS)))


# --------------------------------------------------------------------------
# planner validation
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kw,exc,match", [
    (dict(points=[{"superstep": 4}]), KeyError, "changes Dims"),
    (dict(points=[{"trimming": 0.0}]), KeyError, "changes Dims"),
    (dict(points=[{"algo": 1.0}]), KeyError, "changes Dims"),
    (dict(points=[{"quantum_entanglement": 1.0}]), KeyError, "unsweepable"),
    (dict(points=[]), ValueError, "empty sweep"),
    (dict(seeds=[]), ValueError, "empty seeds"),
], ids=["superstep", "trimming", "algo", "unknown", "no_points", "no_seeds"])
def test_study_rejects_dims_changing_and_unknown_keys(kw, exc, match):
    with pytest.raises(exc, match=match):
        api.study(_scenario(), **kw)


def test_apply_point_routes_cc_keys_into_overrides():
    cfg = apply_point(CFG, {"fd": 0.5, "start_cwnd_mult": 0.7})
    assert ("fd", 0.5) in cfg.cc_overrides
    assert cfg.start_cwnd_mult == 0.7


def test_apply_point_unknown_key_names_the_valid_ones():
    with pytest.raises(KeyError, match="unsweepable key 'bogus'") as ei:
        apply_point(CFG, {"bogus": 1.0})
    assert "start_cwnd_mult" in str(ei.value)      # actionable: lists keys


@pytest.mark.parametrize("key", ["superstep", "leap", "trimming",
                                 "cc_backend", "lb", "tree"])
def test_apply_point_dims_changing_key_raises(key):
    """Keys that change Dims (shapes/branch selectors) cannot ride one
    compiled step; the error says to build one Scenario per value."""
    with pytest.raises(KeyError, match="changes Dims"):
        apply_point(CFG, {key: 1})


def test_study_validates_workload_up_front():
    """A bad flow table fails at plan time with an actionable message,
    not deep inside tracing."""
    bad = workloads.Workload(
        name="bad", src=np.array([0, 1], np.int32),
        dst=np.array([0, 2], np.int32),          # flow 0: src == dst
        size=np.array([4096, 4096], np.int32),
        t_start=np.zeros(2, np.int32), order=np.zeros(2, np.int32))
    sc = dataclasses.replace(_scenario(), wl=bad)
    with pytest.raises(ValueError, match="src == dst"):
        api.study(sc)
    with pytest.raises(ValueError, match="src == dst"):
        api.run(sc)


# --------------------------------------------------------------------------
# scenario registry
# --------------------------------------------------------------------------


def test_scenario_registry_resolves_and_overrides():
    names = scenarios.names()
    assert {"incast8_32n", "perm64", "sparse_heavy_32n",
            "tiny_incast3"} <= set(names)
    sc = scenario("tiny_incast3", algo="swift", max_ticks=12_345)
    assert sc.cfg.algo == "swift" and sc.max_ticks == 12_345
    assert sc.name == "tiny_incast3"
    # aliases resolve to the same catalogue entry
    assert scenario("perm_64n").name == "perm64"
    with pytest.raises(KeyError, match="tiny_incast3"):
        scenario("no_such_scenario")


def test_api_accepts_scenario_names():
    r = api.run("tiny_incast3")
    assert r.scenario == "tiny_incast3" and r.all_done
    res = api.study("tiny_incast3",
                    points=[{"start_cwnd_mult": a} for a in (0.5, 1.0)],
                    seeds=(0, 1)).run()
    assert len(res) == 4 and all(rr.all_done for rr in res)


# --------------------------------------------------------------------------
# typed results
# --------------------------------------------------------------------------


def test_run_result_derived_fields():
    r = api.run("tiny_incast3")
    assert r.all_done and r.n_done == r.n_flows
    assert r.completion == int(r.fct_done.max())
    assert 0.0 < r.jain <= 1.0
    assert r.fct_min <= r.fct_mean <= r.fct_p99 <= r.completion
    # slowdown vs the uncongested ideal: >= ~1 for every finished flow
    assert np.nanmin(r.slowdown) > 0.9
    assert r.slowdown_p99 >= r.slowdown_mean > 0
    s = r.summary()
    assert s["fct_max"] == r.completion and s["trims"] == r.trims


def test_study_result_rows_are_point_major_and_tidy():
    points = [{"start_cwnd_mult": a} for a in (0.5, 1.0, 1.25)]
    seeds = (0, 7)
    res = api.study("tiny_incast3", points=points, seeds=seeds).run()
    rows = res.rows()
    assert len(rows) == len(points) * len(seeds)
    for pi, pt in enumerate(points):
        for si, seed in enumerate(seeds):
            row = rows[pi * len(seeds) + si]
            assert row["point"] == pt and row["seed"] == seed
            assert row["scenario"] == "tiny_incast3"
            assert {"name", "completion", "jain", "slowdown_p99",
                    "trims", "ticks"} <= set(row)
    # lane() indexes the same grid
    assert res.lane(2, 1).seed == 7
    assert dict(res.lane(2, 1).point) == points[2]
    best = res.best("completion")
    assert best.completion == min(r.completion for r in res)


# --------------------------------------------------------------------------
# best() tie-handling (regression: unfinished lanes must rank strictly
# last, whatever their partial metric looks like)
# --------------------------------------------------------------------------


def _synthetic_result(fct, done, seed):
    """A hand-built RunResult with exactly the finished-flow structure
    the test wants (the derived metrics — completion, slowdown — follow
    from fct/done)."""
    nf = len(fct)
    z = np.zeros(nf, np.int32)
    return api.RunResult(
        scenario="syn", algo="smartt", lb="reps", point=(), seed=seed,
        max_ticks=100, ticks=100, mtu=4096, brtt=10,
        fct=np.asarray(fct, np.int32), goodput=z,
        done=np.asarray(done, bool),
        size=np.full(nf, 4096, np.int32), t_start=z,
        flow_brtt=np.full(nf, 10.0, np.float32),
        trims=0, drops=0, blackholed=0, timeouts=0, retx=0, acks=0,
        spurious_retx=0, delivered_pkts=0, delivered_bytes=0.0,
        rtt_hist=np.zeros(8, np.int32), q_mean=0.0, q_max=0)


def _synthetic_study(results):
    return api.StudyResult(scenario="syn", points=((),) * len(results),
                           seeds=(0,), results=tuple(results),
                           states=None, wall_s=0.0)


def test_best_unfinished_lanes_rank_strictly_last():
    """An unfinished lane whose partial metric looks perfect — e.g. one
    early flow finished at tick 0, so ``completion == 0`` — must never
    beat a finished lane, for any metric; sentinel values (-1, NaN) rank
    last within each group; exact ties resolve to the lowest lane."""
    unfinished_looks_great = _synthetic_result([0, -1], [True, False],
                                               seed=0)
    assert not unfinished_looks_great.all_done
    assert unfinished_looks_great.completion == 0     # the trap value
    finished_slow = _synthetic_result([50, 70], [True, True], seed=1)
    res = _synthetic_study([unfinished_looks_great, finished_slow])
    assert res.best("completion") is finished_slow
    assert res.best("fct_mean") is finished_slow
    # slowdown of the unfinished lane is a -1 sentinel -> ranks last even
    # against a large finished value
    assert res.best("slowdown_p99") is finished_slow

    # nothing finished at all: fall back to the metric among unfinished
    # lanes (the -1 sentinel maps to inf, so real progress wins)
    part = _synthetic_result([5, -1], [True, False], seed=0)
    none_ = _synthetic_result([-1, -1], [False, False], seed=1)
    assert _synthetic_study([none_, part]).best("completion") is part

    # exact tie between finished lanes: stable, lowest lane index
    twin_a = _synthetic_result([9, 9], [True, True], seed=0)
    twin_b = _synthetic_result([9, 9], [True, True], seed=1)
    assert _synthetic_study([twin_a, twin_b]).best("completion") is twin_a
