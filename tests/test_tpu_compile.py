"""The tick's Pallas kernels compile for a TPU v5e at paper-scale widths,
and the compiled run loops move no whole port-queue ring per tick.

Interpret-mode parity tests cannot see what Mosaic, the TPU kernel
compiler, refuses (unaligned slices, lane reshapes, selects between
boolean vectors, integer argmin, ...).  These tests compile each kernel
with ``interpret=False`` for a described ``v5e:2x2`` topology, no chip
attached, at the widths of ``perm_1024n_3t`` (and of ``alltoall_3t`` for
the round-robin pick, which only runs with several flows per sender), and
check that the program holds the compiled kernel.

The run loops (``engine._run_until_done`` at the benchmark cell's widths,
``shard._run_lanes`` with 8 lanes) are compiled whole the same way, and
their while bodies are searched for a relayout of the ring ``q_fields``,
and, at the widths of a 64-rank ring all-reduce on 1024 hosts, for a
sender arbitration table with a row for every host.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cc_update import kernel as cc_kernel
from repro.kernels.cc_update import ref as cc_ref
from repro.kernels.enqueue_arb import kernel as arb_kernel
from repro.kernels.ring_drain import kernel as drain_kernel
from repro.netsim import api, collectives, engine, scenarios, shard, state
from repro.netsim.units import LinkConfig

I32, F32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def dims():
    """Static widths of the two scenarios the kernels are compiled at,
    plus the switch fan-in table's shape ``[switches, max fan-in]``."""
    def of(name):
        sc = scenarios.scenario(name)
        _, _, d, consts = state.derive(sc.cfg, sc.wl)
        return d, tuple(consts.in_tbl.shape)
    (perm, groups), (alltoall, _) = of("perm_1024n_3t"), of("alltoall_3t")
    return dict(perm=perm, alltoall=alltoall, groups=groups)


@pytest.fixture(scope="module")
def programs(one_chip, dims):
    """(kernel callable with its statics bound, argument shapes) per
    kernel."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    d, a = dims["perm"], dims["alltoall"]
    nf, w, ww, maxw = d.NF, d.W, d.WW, d.MAXW
    groups = dims["groups"]
    flows = lambda dt, names: tuple(arg((nf,), dt) for _ in names)
    return {
        "cc_update": (
            lambda *xs: cc_kernel.cc_update(*xs, interpret=False),
            (arg((len(cc_ref.PARAM_FIELDS),), F32), arg((), I32),
             arg((nf,), F32), arg((nf,), F32), arg((nf,), F32),
             flows(F32, cc_ref.STATE_F32), flows(I32, cc_ref.STATE_I32),
             flows(F32, cc_ref.EVENT_F32), flows(I32, cc_ref.EVENT_I32))),
        "enqueue_rank": (
            lambda *xs: arb_kernel.enqueue_rank(
                *xs, cap=d.CAP, nq=d.NQ, interpret=False),
            (arg(groups, I32),) * 3),
        "rr_pick": (
            lambda e, r: arb_kernel.rr_pick(e, r, kmax=a.FMAX,
                                            interpret=False),
            (arg((a.N, a.FMAX), jnp.bool_), arg((a.N,), I32))),
        "ring_drain": (
            lambda *xs: drain_kernel.ring_drain(
                *xs, w=w, ww=ww, maxw=maxw, interpret=False),
            (arg((), I32), arg((nf,), F32), arg((nf,), jnp.bool_),
             arg((nf,), jnp.bool_), arg((nf,), I32), arg((nf, ww), I32),
             arg((nf, maxw), I32), arg((nf, w), I32), arg((nf, w), I32),
             arg((nf, w), I32))),
    }


def test_paper_scale_widths(dims):
    d, a = dims["perm"], dims["alltoall"]
    assert (d.NF, d.W, d.WW, d.MAXW) == (1024, 64, 2, 2)
    assert dims["groups"] == (176, 20)
    assert a.FMAX == 31 and a.N == 512


@pytest.mark.parametrize("name", ["cc_update", "enqueue_rank", "rr_pick",
                                  "ring_drain"])
def test_kernel_compiles_for_v5e(name, programs, no_persistent_cache):
    fn, args = programs[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


# --------------------------------------------------------------------------
# the run loops: no whole-ring relayout inside the while body
# --------------------------------------------------------------------------

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])"
                    r"(\{[^}]*\})? ([\w\-]+)\(%?([\w.\-]*)")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|branch_computations|"
                     r"true_computation|false_computation)="
                     r"(\{[^}]*\}|%?[\w.\-]+)")


def _computations(hlo: str) -> dict:
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _in_while_bodies(comps: dict) -> list:
    """Every instruction line of every computation a while body reaches
    (nested loops, conds, fusions)."""
    todo = [c for lines in comps.values() for line in lines
            for c in re.findall(r"body=%?([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            for ref in _CALLED.findall(line):
                todo.extend(r for r in re.split(r"[{},%\s]+", ref) if r)
    return [line for c in seen for line in comps[c]]


def _elems(shape: str) -> int:
    n = 1
    for d in re.findall(r"\d+", shape.split("[", 1)[1]):
        n *= int(d)
    return n


def ring_relayouts(hlo: str, elems: set) -> list:
    """Names of the instructions in the while bodies of compiled ``hlo``
    that move a whole ring (an array of one of ``elems`` elements) to
    another layout: a ``reshape`` or ``transpose`` (XLA makes the free ones
    bitcasts), or a ``copy`` whose layout differs from its operand's (the
    memory space, ``S(n)``, aside)."""
    comps = _computations(hlo)
    layout = {}
    for lines in comps.values():
        for line in lines:
            m = _INSTR.match(line)
            if m:
                layout[m.group(1)] = (m.group(2), re.sub(r"S\(\d+\)", "",
                                                         m.group(3) or ""))
    out = []
    for line in _in_while_bodies(comps):
        m = _INSTR.match(line)
        if not m or _elems(m.group(2)) not in elems:
            continue
        name, op, operand = m.group(1), m.group(4), m.group(5)
        if op in ("reshape", "transpose") or (
                op == "copy" and layout.get(operand) != layout[name]):
            out.append(name)
    return out


def _structs(tree, sharding, lead=()):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        lead + tuple(x.shape), x.dtype, sharding=sharding), tree)


def _ring_elems(sim, lanes=1):
    """Element counts of the ring in both its forms (public and loop)."""
    public = jax.eval_shape(sim.init)
    loop = jax.eval_shape(state.ring_loop_form, public)
    return {lanes * math.prod(public.q_fields.shape),
            lanes * math.prod(loop.q_fields.shape)}


@pytest.fixture(scope="module")
def cell_sim():
    """The benchmark cell's widths: ``perm_1024n_3t`` on its 800 Gb/s,
    4 KiB links (the catalogue's 100 Gb/s gives CAP 40)."""
    sc = scenarios.scenario("perm_1024n_3t")
    return engine.build(dataclasses.replace(
        sc.cfg, link=LinkConfig(rate_gbps=800.0, mtu_bytes=4096)), sc.wl)


def test_cell_widths(cell_sim):
    assert (cell_sim.dims.NQ, cell_sim.dims.CAP) == (2304, 286)


def test_run_loop_moves_no_whole_ring_per_tick(cell_sim, one_chip,
                                               no_persistent_cache):
    sim, d = cell_sim, cell_sim.dims
    hlo = engine._run_until_done.lower(
        sim.step_fn, sim.horizon_fn if d.leap else None,
        _structs(sim.consts, one_chip),
        _structs(jax.eval_shape(sim.init), one_chip), 60_000, d.superstep,
        False).compile().as_text()
    assert ring_relayouts(hlo, _ring_elems(sim)) == []


def test_lane_loop_moves_no_whole_ring_per_tick(one_chip,
                                                no_persistent_cache):
    lanes = 8
    sim = scenarios.scenario("perm_512n_3t").build()
    d = sim.dims
    hlo = shard._run_lanes.lower(
        sim.step_fn, sim.horizon_fn if d.leap else None,
        api.no_axes(sim.consts), 60_000, d.superstep,
        _structs(sim.consts, one_chip),
        _structs(jax.eval_shape(sim.init), one_chip, (lanes,))
    ).compile().as_text()
    assert ring_relayouts(hlo, _ring_elems(sim, lanes)) == []


# --------------------------------------------------------------------------
# the run loop: sender arbitration over the hosts that own flows alone
# --------------------------------------------------------------------------


def gathers(hlo: str) -> dict:
    """The gathers in the while bodies of compiled ``hlo``: name ->
    number of elements it yields."""
    out = {}
    for line in _in_while_bodies(_computations(hlo)):
        m = _INSTR.match(line)
        if m and m.group(4) == "gather":
            out[m.group(1)] = _elems(m.group(2))
    return out


def test_ring_run_loop_gathers_no_table_over_every_host(one_chip,
                                                        no_persistent_cache):
    """``ring64.run``'s widths: 64 ranks on 1024 hosts, 126 flows each.
    Only the 64 rows of the hosts that send are arbitrated, so no gather
    builds the ``[N, FMAX]`` table of 1024 x 126 flags."""
    sim = engine.build(
        state.SimConfig(link=LinkConfig(rate_gbps=800.0, mtu_bytes=4096),
                        tree=scenarios.TREE_1024_3T),
        collectives.ring_allreduce(scenarios.TREE_1024_3T, 409600, nodes=64,
                                   spread=True))
    d = sim.dims
    assert (d.N, d.NF, d.FMAX, d.NS) == (1024, 8064, 126, 64)
    hlo = engine._run_until_done.lower(
        sim.step_fn, sim.horizon_fn if d.leap else None,
        _structs(sim.consts, one_chip),
        _structs(jax.eval_shape(sim.init), one_chip), 80_000, d.superstep,
        False).compile().as_text()
    found = gathers(hlo)
    assert found, "no gather found in the while bodies"
    assert [g for g, n in found.items() if n == d.N * d.FMAX] == []
