"""The benchmark's own flow generators give the tables of the program's
registered generators at the registered parameters."""

import numpy as np
import pytest

from repro.netsim import collectives, scenarios, workloads

import harness


def _tree(t):
    return dict(racks=t.racks, nodes_per_rack=t.nodes_per_rack,
                uplinks=t.uplinks, pods=t.pods, core_uplinks=t.core_uplinks)


def _same(got, wl):
    for k in ("src", "dst", "size", "t_start", "order"):
        np.testing.assert_array_equal(got[k], getattr(wl, k), err_msg=k)
    if wl.dep_par is None:
        assert "dep_par" not in got
    else:
        np.testing.assert_array_equal(got["dep_par"], wl.dep_par)
        np.testing.assert_array_equal(got["dep_thr"], wl.dep_thr)


@pytest.mark.parametrize("tree", [scenarios.TREE_1024_3T,
                                  scenarios.TREE_128_3T,
                                  scenarios.TREE_3T_TINY])
@pytest.mark.parametrize("seed", [7, 0, 12345])
def test_permutation(tree, seed):
    config = {"tree": _tree(tree),
              "flows": {"generator": "permutation", "size_bytes": 262144}}
    _same(harness.flow_table(config, seed),
          workloads.permutation(tree, size_bytes=262144, seed=seed))


@pytest.mark.parametrize("tree,chunk", [(scenarios.TREE_128_3T, 32768),
                                        (scenarios.TREE_3T_TINY, 8192)])
def test_ring_allreduce(tree, chunk):
    config = {"tree": _tree(tree),
              "flows": {"generator": "ring_allreduce", "chunk_bytes": chunk}}
    _same(harness.flow_table(config, 3),
          collectives.ring_allreduce(tree, chunk_bytes=chunk))


def test_registered_scenarios_use_these_parameters():
    perm = scenarios.scenario("perm_1024n_3t")
    assert perm.cfg.tree == scenarios.TREE_1024_3T
    assert int(perm.wl.size[0]) == 262144
    ring = scenarios.scenario("allreduce_ring_128n_3t")
    assert ring.cfg.tree == scenarios.TREE_128_3T
    assert ring.wl.n_flows == 32512 and int(ring.wl.size[0]) == 32768
