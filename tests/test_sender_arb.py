"""The senders' round-robin arbitration over the rows of the hosts that
own flows (``sender.arbitrate``) against the same pick over every host's
row of the full ``[N, FMAX]`` table (``ref.rr_pick_ref``), on flow tables
of every shape ``state.derive`` tells apart; and, through ``engine.build``,
a ring with hosts that send nothing against ``bench/reference.py``."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.enqueue_arb import ops as arb_ops
from repro.kernels.enqueue_arb.ref import rr_pick_ref
from repro.netsim import collectives, scenarios, sender, state
from repro.netsim.workloads import Workload

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
import compare  # noqa: E402
import harness  # noqa: E402
from reference import Reference  # noqa: E402

I32 = jnp.int32
N16 = scenarios.TREE_16                       # 16 hosts
SMALL = dict(racks=4, nodes_per_rack=4, uplinks=1, pods=2, core_uplinks=1)


def _workload(src, order, n):
    src = np.asarray(src, np.int32)
    f = len(src)
    return Workload(name="arb", src=src, dst=(src + 1) % n,
                    size=np.full(f, 8192, np.int32),
                    t_start=np.zeros(f, np.int32),
                    order=np.asarray(order, np.int32))


def _ragged(rng):
    """Uneven counts, some hosts with none, flow ids in no block order."""
    counts = rng.integers(0, 6, N16.n_nodes)
    counts[[2, 9]] = 0
    src = rng.permutation(np.repeat(np.arange(N16.n_nodes), counts))
    return N16, _workload(src, rng.permutation(len(src)), N16.n_nodes)


def _idle_hosts(rng):
    """Every other host sends three flows; the rest send nothing."""
    src = rng.permutation(np.repeat(np.arange(0, N16.n_nodes, 2), 3))
    return N16, _workload(src, np.zeros(len(src)), N16.n_nodes)


def _one_sender(rng):
    return N16, _workload(np.full(7, 5), rng.permutation(7), N16.n_nodes)


def _every_host(rng):
    counts = rng.integers(1, 5, N16.n_nodes)
    src = rng.permutation(np.repeat(np.arange(N16.n_nodes), counts))
    return N16, _workload(src, np.zeros(len(src)), N16.n_nodes)


def _sender_major(rng):
    """Hosts 1, 4, 7, ... send four flows each, ids in sender order: a
    dense block, but sender-major, so the gather map serves it."""
    src = np.repeat(np.arange(1, N16.n_nodes, 3), 4)
    return N16, _workload(src, np.tile(np.arange(4), len(src) // 4),
                          N16.n_nodes)


def _step_major(rng):
    """``ring64.run``'s layout: flow ``s * ranks + k`` is rank ``k``'s send
    of step ``s``; 64 ranks on 128 hosts."""
    tree = scenarios.TREE_128_3T
    return tree, collectives.ring_allreduce(tree, 8192, nodes=64, spread=True)


TABLES = {"ragged": (_ragged, "gather"), "idle_hosts": (_idle_hosts, "gather"),
          "one_sender": (_one_sender, "gather"),
          "every_host": (_every_host, "gather"),
          "sender_major": (_sender_major, "gather"),
          "step_major": (_step_major, "dense")}


def _full_table_pick(consts, elig, rr, fmax, nf):
    """The pick over every host's row of ``flows_of``."""
    flows_of = consts.flows_of
    n = flows_of.shape[0]
    has, sel = rr_pick_ref(jnp.pad(elig, (0, 1))[flows_of], rr, kmax=fmax)
    sflow = jnp.where(has, flows_of[jnp.arange(n), sel], nf)
    rr = jnp.where(has, (sel + 1) % fmax, rr)
    return has, sel, sflow, rr, sflow[consts.src] == jnp.arange(nf)


@pytest.mark.parametrize("backend", arb_ops.BACKENDS)
@pytest.mark.parametrize("table", TABLES)
def test_arbitration_equals_the_full_table(table, backend):
    rng = np.random.default_rng(sorted(TABLES).index(table))
    make, amap = TABLES[table]
    tree, wl = make(rng)
    _, _, d, c = state.derive(state.SimConfig(tree=tree), wl)
    senders = np.unique(wl.src)
    assert d.FMAX > 1 and d.arb_map == amap and d.NS == len(senders)
    np.testing.assert_array_equal(np.asarray(c.senders), senders)
    np.testing.assert_array_equal(np.asarray(c.flows_of_c),
                                  np.asarray(c.flows_of)[senders])
    _, arb = arb_ops.get(backend)
    for trial in range(4):
        # sparse to dense flags, so some senders have nothing eligible
        elig = jnp.asarray(rng.random(d.NF) < (0.1, 0.3, 0.7, 1.0)[trial])
        rr = jnp.asarray(rng.integers(0, d.FMAX, d.N), I32)
        got = sender.arbitrate(d, c, elig, rr, arb)
        want = _full_table_pick(c, elig, rr, d.FMAX, d.NF)
        for name, g, w in zip(("has", "sel", "sflow", "rr", "emit"), got,
                              want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=f"{name}, trial {trial}")


def _small_ring(ranks):
    """``ring64.run``'s configuration, 800 Gb/s links included, on a
    16-host tree: ``ranks`` spread ranks, 16 KiB chunks."""
    cfg = harness.load_cell("ring64.run").config
    cfg["tree"] = dict(SMALL)
    cfg["flows"] = dict(cfg["flows"], ranks=ranks,
                        message_bytes=ranks * 16384)
    cfg["max_ticks"] = 20000
    return cfg, harness.flow_table(cfg, 11)


def _small_ragged():
    """The same fabric, ten flows of uneven sizes from four hosts, in no
    block order: the gather map."""
    cfg, _ = _small_ring(4)
    src = np.array([3, 3, 3, 8, 12, 12, 8, 3, 6, 12], np.int32)
    flows = dict(src=src, dst=(src * 5 + 1) % 16,
                 size=np.arange(1, 11, dtype=np.int32) * 6000,
                 t_start=np.zeros(10, np.int32),
                 order=np.array([2, 0, 1, 0, 1, 0, 1, 3, 0, 2], np.int32))
    return cfg, flows


@pytest.mark.parametrize("case,salt,ns,amap,fill", [
    ("ring8", 5, 8, "dense", 1.0),
    ("ring4", 2**31 - 9, 4, "dense", 1.0),
    ("ragged", 17, 4, "gather", 10 / 16)])
def test_program_equals_the_reference_with_idle_hosts(case, salt, ns, amap,
                                                      fill):
    cfg, flows = {"ring8": lambda: _small_ring(8),
                  "ring4": lambda: _small_ring(4),
                  "ragged": _small_ragged}[case]()
    sim = harness.scenario(cfg, flows, case).build()
    assert (sim.dims.N, sim.dims.NS) == (16, ns)
    assert (sim.dims.arb_map, sim.arb_fill) == (amap, fill)
    got = compare.flatten(jax.device_get(sim.run(cfg["max_ticks"],
                                                 seed=salt)))
    assert bool(got["done"].all())
    ref = Reference(cfg["tree"], cfg["link"], flows, cfg["smartt"],
                    cfg["params"], cfg["max_ticks"])
    want = jax.device_get(ref.jit_run()(ref.consts(dict(cfg["params"])),
                                        salt))
    assert compare.differing(got, want) == {}


def test_one_flow_per_sender_bypasses_arbitration():
    sim = scenarios.scenario("perm_16n").build()
    assert (sim.dims.arb_map, sim.arb_fill) == ("identity", 1.0)
