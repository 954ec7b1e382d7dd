"""Dependency-driven collectives (DESIGN.md Sec. 11): the oracle-backed
test layer.

Three independent lines of evidence pin the activation predicate
(``sender.activated``) to the workload's chunk DAG:

* a ~50-line host-side numpy reference (``oracle_rounds``) computes the
  dependency-release partial order of a random DAG with Kahn peeling;
  the engine's observed activation ticks must be a linearization of it,
  and the engine must never *emit* a flow before its release tick
  (checked on >= 20 seeded random DAGs, plus a hypothesis sweep when the
  test extra is installed);
* ring allreduce on an ideal uncongested fabric completes in exactly the
  analytic ``2(N-1) * (chunk_pkts - 1 + fwd) + ret`` ticks — the
  closed-form step count of the bucket algorithm;
* dep-free workloads are bit-for-bit unchanged: an explicit empty
  dependency table traces to the same graph as no table at all, and
  every pre-existing registered scenario reproduces the final-state
  digest recorded in ``tests/data/scenario_digests.json`` before the
  dependency machinery existed.

Validation error paths (cycles, range, thresholds) and the CCT metric
plumbing (``api.RunResult.cct`` -> ledger row) are covered here too.
"""

import dataclasses
import functools
import json
import pathlib
import platform
import sys

import jax
import numpy as np
import pytest

from repro.netsim import api, cache, collectives, scenarios, state, workloads
from repro.netsim.engine import SimConfig, build
from repro.netsim.units import FatTreeConfig, LinkConfig
from repro.netsim.workloads import Workload

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st_h
    HAVE_HYPOTHESIS = True
except ImportError:              # local envs without the test extra
    HAVE_HYPOTHESIS = False

LINK = LinkConfig()
TREE4 = FatTreeConfig(racks=2, nodes_per_rack=2, uplinks=2)       # 4 nodes
MTU = LINK.mtu_bytes

# --------------------------------------------------------------------------
# random DAG workloads with frozen Dims (one compile for the whole sweep)
# --------------------------------------------------------------------------

_F, _D = 12, 2     # flows / max parents per flow — fixed so Dims are fixed


def _dag_wl(seed: int) -> Workload:
    """A random dependency DAG over a *fixed* traffic pattern.

    src/dst follow a balanced deterministic schedule (3 flows per sender,
    3 per receiver on the 4-node tree) so ``Dims`` — FMAX, FRMAX, W —
    are identical across seeds and all DAGs share one compiled step;
    only sizes, start ticks, and the dependency table randomize.
    Parents always have smaller flow ids, so the table is a DAG by
    construction."""
    rng = np.random.default_rng(seed)
    n = TREE4.n_nodes
    f_ids = np.arange(_F)
    src = (f_ids % n).astype(np.int32)
    dst = ((src + 1 + (f_ids // n) % (n - 1)) % n).astype(np.int32)
    size = (rng.integers(1, 4, _F) * MTU).astype(np.int32)
    t_start = rng.integers(0, 40, _F).astype(np.int32)
    dep_par = np.full((_F, _D), -1, np.int32)
    dep_thr = np.zeros((_F, _D), np.int32)
    for f in range(1, _F):
        for j in range(rng.integers(0, _D + 1)):
            p = int(rng.integers(0, f))
            if p in dep_par[f]:
                continue
            dep_par[f, j] = p
            dep_thr[f, j] = int(rng.integers(1, size[p] + 1))
    order = np.zeros(_F, np.int32)
    cnt: dict[int, int] = {}
    for f in range(_F):
        s = int(src[f])
        order[f] = cnt.get(s, 0)
        cnt[s] = order[f] + 1
    return Workload(name=f"dag{seed}", src=src, dst=dst, size=size,
                    t_start=t_start, order=order,
                    dep_par=dep_par, dep_thr=dep_thr)


@functools.lru_cache(maxsize=1)
def _dag_rig():
    """One compiled (step, trace) shared by every random-DAG case."""
    cfg = SimConfig(link=LINK, tree=TREE4)
    sim = build(cfg, _dag_wl(0))

    @functools.partial(jax.jit, static_argnums=2)
    def trace(consts, st0, ticks):
        def body(st, _):
            st2 = sim.step_fn(consts, st)
            return st2, (st2.goodput, st2.next_seq)
        return jax.lax.scan(body, st0, None, length=ticks)

    return cfg, sim, trace


def _run_dag(wl: Workload, ticks: int = 400):
    """(goodput[ticks, F], next_seq[ticks, F], final state) for one DAG,
    through the shared compiled step.  Index k = state after tick k."""
    cfg, sim, trace = _dag_rig()
    _, _, dims, consts = state.derive(cfg, wl)
    assert dims == sim.dims, "fixed traffic pattern must freeze Dims"
    fin, (gp, nseq) = trace(
        consts, state.ring_loop_form(state.init_state(dims, consts)), ticks)
    return np.asarray(gp), np.asarray(nseq), jax.device_get(fin)


def oracle_rounds(dep_par: np.ndarray) -> np.ndarray:
    """Host-side numpy reference for the dependency-release partial
    order: round[f] = Kahn peel depth — 0 for dep-free flows, else
    1 + max over parents.  -1 marks flows stuck on (or behind) a cycle.
    The engine must activate flows in an order consistent with this:
    a flow's activation tick strictly after every parent's."""
    F, _ = dep_par.shape
    used = dep_par >= 0
    indeg = used.sum(axis=1)
    children = [[] for _ in range(F)]
    for f, j in zip(*np.nonzero(used)):
        children[int(dep_par[f, j])].append(int(f))
    rounds = np.where(indeg == 0, 0, -1)
    frontier = list(np.flatnonzero(indeg == 0))
    while frontier:
        p = frontier.pop()
        for c in children[p]:
            indeg[c] -= 1
            if indeg[c] == 0:
                rounds[c] = 1 + max(rounds[q] for q in dep_par[c] if q >= 0)
                frontier.append(c)
    return rounds


def _check_dag_property(seed: int):
    """The oracle property for one random DAG.

    * engine activation ticks (first tick every parent's goodput crossed
      its threshold, floored at t_start) are a linearization of the
      oracle partial order: strictly increasing along every edge;
    * the engine never emits a packet of a flow before that tick
      (``next_seq`` is independent evidence — it only moves in phase 5
      when ``sender.activated`` admitted the flow);
    * every flow still finishes (dependency gating never deadlocks a
      valid DAG)."""
    wl = _dag_wl(seed)
    wl.validate(n_nodes=TREE4.n_nodes)
    gp, nseq, fin = _run_dag(wl)
    assert bool(fin.done.all()), f"seed {seed}: DAG did not drain"

    ticks = gp.shape[0]
    rounds = oracle_rounds(wl.dep_par)
    assert (rounds >= 0).all()

    # activation tick: gp[k] is goodput after tick k; arrivals (phase 2)
    # precede sends (phase 5), so a threshold crossed during tick k
    # releases the child within tick k
    act = np.asarray(wl.t_start, np.int64).copy()
    for f in range(_F):
        for j in range(_D):
            p, thr = int(wl.dep_par[f, j]), int(wl.dep_thr[f, j])
            if p < 0:
                continue
            crossed = np.flatnonzero(gp[:, p] >= thr)
            assert crossed.size, f"seed {seed}: parent {p} never delivered"
            act[f] = max(act[f], int(crossed[0]))

    for f in range(_F):
        for p in wl.dep_par[f]:
            if p >= 0:
                assert act[f] > act[p], (
                    f"seed {seed}: flow {f} activated at {act[f]}, not "
                    f"after its parent {p} at {act[p]} — violates the "
                    f"oracle partial order (rounds {rounds[f]} > {rounds[p]})")

    # emission evidence: first next_seq movement is at or after activation
    first_emit = np.where((nseq >= 1).any(axis=0),
                          (nseq >= 1).argmax(axis=0), ticks)
    assert (first_emit < ticks).all(), f"seed {seed}: flow never emitted"
    early = first_emit < act
    assert not early.any(), (
        f"seed {seed}: flows {np.flatnonzero(early).tolist()} emitted "
        f"before their dependency release ticks")
    # dep-free flows start the moment the clock allows
    roots = (np.asarray(wl.dep_par) < 0).all(axis=1)
    assert (first_emit[roots] >= wl.t_start[roots]).all()


def test_oracle_partial_order_random_dags():
    """>= 20 seeded random DAGs against the numpy oracle (one compile)."""
    for seed in range(20):
        _check_dag_property(seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None, derandomize=True)
    @given(seed=st_h.integers(min_value=0, max_value=10_000))
    def test_oracle_partial_order_hypothesis(seed):
        _check_dag_property(seed)


# --------------------------------------------------------------------------
# analytic ring-allreduce CCT on an ideal fabric
# --------------------------------------------------------------------------


def test_ring_allreduce_cct_analytic():
    """On an uncongested 1:1 fabric with every ring edge in the same
    latency class (participants strided one per rack), the bucket
    algorithm's dependency chain serializes perfectly: each of the
    2(N-1) steps takes exactly (chunk_pkts - 1) serialization ticks plus
    the one-way delivery latency, and the recorded CCT lands the ACK
    return on top — no congestion term, no slack."""
    tree = scenarios.TREE_FLAT                       # 4 racks, 1:1
    n, chunk_pkts = 4, 3
    wl = collectives.ring_allreduce(tree, chunk_bytes=chunk_pkts * MTU,
                                    nodes=n, spread=True)
    sim = build(SimConfig(link=LINK, tree=tree), wl)
    st = jax.device_get(sim.run(max_ticks=8000, seed=0))
    assert bool(st.done.all())

    brtt = np.unique(np.asarray(sim.consts.cc.brtt))
    assert brtt.size == 1, "all ring edges must share one latency class"
    ret = int(np.asarray(sim.consts.ret))
    fwd = float(brtt[0]) - ret                       # one-way send->deliver
    steps = 2 * (n - 1)
    analytic = steps * (chunk_pkts - 1 + fwd) + ret

    finish = np.asarray(st.fct, np.int64) + np.asarray(sim.consts.t_start)
    cct = int(finish.max() - np.asarray(sim.consts.t_start).min())
    assert cct == analytic


# --------------------------------------------------------------------------
# generators: structure + registered scenarios
# --------------------------------------------------------------------------


def test_generator_structures():
    n = TREE4.n_nodes
    ring = collectives.ring_allreduce(TREE4, chunk_bytes=MTU, nodes=n)
    assert ring.n_flows == 2 * (n - 1) * n and ring.n_deps == 1
    ag = collectives.all_gather(TREE4, chunk_bytes=MTU, nodes=n)
    assert ag.n_flows == (n - 1) * n
    tr = collectives.tree_allreduce(TREE4, msg_bytes=MTU, nodes=n)
    assert tr.n_flows == 2 * (n - 1)
    pl = collectives.pipeline(TREE4, stage_bytes=MTU, stages=3,
                              microbatches=5)
    assert pl.n_flows == 2 * 5 and pl.n_deps == 1
    for wl in (ring, ag, tr, pl):
        wl.validate(n_nodes=n)                       # DAG checks pass
        assert wl.coll_id is not None and (wl.coll_id == 0).all()
    # strided participants stay inside the fabric and unique
    big = scenarios.TREE_128_3T
    spread = collectives.all_gather(big, chunk_bytes=MTU, nodes=64,
                                    spread=True)
    nodes = np.unique(np.concatenate([spread.src, spread.dst]))
    assert nodes.size == 64 and nodes.max() < big.n_nodes
    with pytest.raises(ValueError, match="2 <= nodes"):
        collectives.ring_allreduce(TREE4, chunk_bytes=MTU, nodes=1)
    with pytest.raises(ValueError, match="stages >= 2"):
        collectives.pipeline(TREE4, stage_bytes=MTU, stages=1,
                             microbatches=1)


def test_registered_collective_scenarios_build():
    """Every registered collective scenario derives (validate + shape
    math) without building the full step."""
    for name in ("tiny_allreduce_ring", "tiny_allgather", "tiny_pipeline",
                 "allreduce_ring_128n_3t", "allreduce_tree_128n_3t",
                 "allgather_64n_3t", "pipeline_32n"):
        sc = scenarios.scenario(name)
        _, _, dims, consts = state.derive(sc.cfg, sc.wl)
        assert dims.D >= 1
        assert consts.dep_par.shape == (dims.NF, dims.D)
        # lowering: -1 slots became the NF sentinel with threshold 0
        free = np.asarray(sc.wl.dep_par) < 0
        assert (np.asarray(consts.dep_par)[free] == dims.NF).all()
        assert (np.asarray(consts.dep_thr)[free] == 0).all()


# --------------------------------------------------------------------------
# CCT metric plumbing
# --------------------------------------------------------------------------


def test_cct_metric_and_row():
    r = api.run("tiny_allgather")
    assert r.all_done
    fin = r.fct.astype(np.int64) + r.t_start
    assert r.cct_by_coll == {0: int(fin.max() - r.t_start.min())}
    assert r.cct == r.cct_by_coll[0] > 0
    row = r.row()
    assert row["cct"] == r.cct and row["n_collectives"] == 1
    # unfinished collective reports the -1 sentinel, never a partial time
    r_cut = api.run("tiny_allgather", max_ticks=3)
    assert not r_cut.all_done and r_cut.cct == -1
    assert r_cut.row()["cct"] == -1
    # flow-list workloads keep their rows key-identical to before
    r_plain = api.run("tiny_perm4")
    assert r_plain.coll_id is None
    assert r_plain.cct == -1 and r_plain.cct_by_coll == {}
    assert "cct" not in r_plain.row()


# --------------------------------------------------------------------------
# dep-free bit-parity: empty table == no table, and the pre-PR digests
# --------------------------------------------------------------------------


def _state_digest(st) -> str:
    return cache.state_digest(jax.device_get(st))


def test_empty_dep_table_bitwise_identical():
    """An explicit [F, 0] dependency table lowers to D == 0 — the traced
    graph, and therefore the whole trajectory, is bitwise the legacy
    t_start-only one."""
    base = workloads.permutation(TREE4, size_bytes=8 * MTU, seed=1)
    withtab = dataclasses.replace(
        base, dep_par=np.zeros((base.n_flows, 0), np.int32),
        dep_thr=np.zeros((base.n_flows, 0), np.int32))
    cfg = SimConfig(link=LINK, tree=TREE4)
    digs = []
    for wl in (base, withtab):
        sim = build(cfg, wl)
        assert sim.dims.D == 0
        digs.append(_state_digest(sim.run(max_ticks=3000, seed=0)))
    assert digs[0] == digs[1]


_FIXTURE = pathlib.Path(__file__).parent / "data" / "scenario_digests.json"


@pytest.mark.slow
def test_dep_free_scenarios_digest_parity():
    """Every scenario registered before the dependency machinery existed
    reproduces the final-state digest captured on pre-PR main (same
    budgets, seed 0).  Guards the D == 0 path end to end: any bit the
    new admission predicate, Consts layout, or horizon changed for a
    dep-free workload shows up here.  Digests are platform/jax-version
    pinned; on other environments the fixture is skipped (the structural
    ``test_empty_dep_table_bitwise_identical`` still runs)."""
    doc = json.loads(_FIXTURE.read_text())
    env = f"{sys.platform}-{platform.machine()}"
    if doc["env"]["jax"] != jax.__version__ or \
            doc["env"]["platform"] != env:
        pytest.skip(f"digest fixture recorded on jax "
                    f"{doc['env']['jax']}/{doc['env']['platform']}, "
                    f"running {jax.__version__}/{env}")
    mismatches = []
    for name, want in sorted(doc["digests"].items()):
        sc = scenarios.scenario(name)
        assert sc.wl.n_deps == 0, f"{name} predates the dep table"
        sim = sc.build()
        got = _state_digest(sim.run(max_ticks=doc["budgets"][name],
                                    seed=doc["seed"]))
        if got != want:
            mismatches.append(name)
    assert not mismatches, (
        f"dep-free scenarios drifted from pre-dependency main: "
        f"{mismatches}")


# --------------------------------------------------------------------------
# validation error paths
# --------------------------------------------------------------------------


def _wl(dep_par=None, dep_thr=None, coll_id=None, **over):
    base = dict(
        name="t", src=np.array([0, 1, 2], np.int32),
        dst=np.array([1, 2, 0], np.int32),
        size=np.full(3, 4 * MTU, np.int32),
        t_start=np.zeros(3, np.int32), order=np.zeros(3, np.int32),
        dep_par=dep_par, dep_thr=dep_thr, coll_id=coll_id)
    base.update(over)
    return Workload(**base)


def _deps(*rows):
    par = np.array([[p for p, _ in r] for r in rows], np.int32)
    thr = np.array([[t for _, t in r] for r in rows], np.int32)
    return dict(dep_par=par, dep_thr=thr)


def test_validate_dep_partner_missing():
    with pytest.raises(ValueError, match="given together"):
        _wl(dep_par=np.zeros((3, 1), np.int32)).validate(n_nodes=4)


def test_validate_dep_shape_mismatch():
    with pytest.raises(ValueError, match="aligned"):
        _wl(dep_par=np.full((3, 2), -1, np.int32),
            dep_thr=np.zeros((3, 1), np.int32)).validate(n_nodes=4)
    with pytest.raises(ValueError, match="aligned"):
        _wl(dep_par=np.full((2, 1), -1, np.int32),
            dep_thr=np.zeros((2, 1), np.int32)).validate(n_nodes=4)


def test_validate_dep_parent_out_of_range():
    with pytest.raises(ValueError, match=r"flows \[1\].*outside \[0, 3\)"):
        _wl(**_deps([(-1, 0)], [(3, 1)], [(-1, 0)])).validate(n_nodes=4)


def test_validate_dep_self_dependency():
    with pytest.raises(ValueError, match=r"flows \[2\] depend on themselves"):
        _wl(**_deps([(-1, 0)], [(-1, 0)], [(2, 1)])).validate(n_nodes=4)


def test_validate_dep_threshold_bounds():
    # above the parent's size
    with pytest.raises(ValueError, match=r"\[1, parent size\]"):
        _wl(**_deps([(-1, 0)], [(0, 5 * MTU)], [(-1, 0)])).validate(n_nodes=4)
    # zero threshold on a real slot
    with pytest.raises(ValueError, match=r"\[1, parent size\]"):
        _wl(**_deps([(-1, 0)], [(0, 0)], [(-1, 0)])).validate(n_nodes=4)


def test_validate_dep_cycle():
    with pytest.raises(ValueError, match="dependency cycle"):
        _wl(**_deps([(2, 1)], [(0, 1)], [(1, 1)])).validate(n_nodes=4)
    # a 2-cycle hanging off a valid root names the stuck flows
    with pytest.raises(ValueError, match=r"flows \[1, 2\]"):
        _wl(**_deps([(-1, 0)], [(2, 1)], [(1, 1)])).validate(n_nodes=4)


def test_validate_coll_id():
    with pytest.raises(ValueError, match="coll_id must be 1-D"):
        _wl(coll_id=np.zeros((3, 1), np.int32)).validate(n_nodes=4)
    with pytest.raises(ValueError, match="coll_id < -1"):
        _wl(coll_id=np.array([0, -2, 0], np.int32)).validate(n_nodes=4)


def test_valid_dep_table_passes():
    wl = _wl(**_deps([(-1, 0)], [(0, MTU)], [(1, 4 * MTU)]),
             coll_id=np.array([0, 0, -1], np.int32))
    assert wl.validate(n_nodes=4) is wl
    assert wl.n_deps == 1
