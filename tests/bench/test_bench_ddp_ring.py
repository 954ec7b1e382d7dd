"""The data-parallel ring all-reduce deployment (``fattree1024_ddp_ring64``,
cell ``ring64.run``): its generator gives the program's own spread ring
all-reduce, the program equals the reference on its flows at 800 Gb/s, its
file keeps the layout ``bench/README.md`` gives and the permutation's
fabric, and the live-flow share reads the run loop's counters."""

import json
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.netsim import collectives, scenarios

import compare
import harness
from test_bench_flows import _same, _tree
from test_bench_reference import SMALL, program_state, reference_state

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
FABRIC = ("tree", "link", "algo", "lb", "trimming", "smartt", "params")


def ring_config() -> dict:
    return harness.load_cell("ring64.run").config


def test_cell_flow_table_is_the_programs_spread_ring():
    cfg = ring_config()
    tree = scenarios.TREE_1024_3T
    assert cfg["tree"] == _tree(tree)
    flows = harness.flow_table(cfg, 2**31 + 7)
    _same(flows, collectives.ring_allreduce(tree, 409600, nodes=64,
                                            spread=True))
    # NF 8064 flows, one dependency each, 126 flows per sending and per
    # receiving rank, on the first host of every other rack
    assert len(flows["src"]) == 2 * 63 * 64 == 8064
    assert flows["dep_par"].shape == (8064, 1)
    assert np.bincount(flows["src"]).max() == 126
    assert np.bincount(flows["dst"]).max() == 126
    np.testing.assert_array_equal(np.unique(flows["src"]),
                                  np.arange(64) * 16)


@pytest.mark.parametrize("tree,ranks,message", [
    (scenarios.TREE_128_3T, 16, 16 * 32768),
    (scenarios.TREE_3T_TINY, 4, 4 * 8192 + 3)])
def test_ddp_ring(tree, ranks, message):
    config = {"tree": _tree(tree),
              "flows": {"generator": "ddp_ring", "ranks": ranks,
                        "message_bytes": message}}
    _same(harness.flow_table(config, 3),
          collectives.ring_allreduce(tree, message // ranks, nodes=ranks,
                                     spread=True))


def small_ring_config() -> dict:
    """``ring64.run``'s configuration, 800 Gb/s links included, on a
    16-host tree with 8 spread ranks and 16 KiB chunks."""
    cfg = ring_config()
    cfg["tree"] = dict(SMALL)
    cfg["flows"] = dict(cfg["flows"], ranks=8, message_bytes=8 * 16384)
    cfg["max_ticks"] = 20000
    return cfg


@pytest.mark.parametrize("salt", [3, 2**31 - 5])
def test_reference_equals_program_on_ddp_ring(salt):
    cfg = small_ring_config()
    assert cfg["link"]["rate_gbps"] == 800.0
    flows = harness.flow_table(cfg, 11)
    got = program_state(cfg, flows, salt)
    assert bool(got["done"].all())
    assert compare.differing(got, reference_state(cfg, flows, salt)) == {}


def _readme_config_keys() -> set:
    text = (ROOT / "bench" / "README.md").read_text()
    row = next(line for line in text.splitlines()
               if line.startswith("| `bench/configs/<config>.json`"))
    holds = row.split("|")[2]
    return set(re.findall(r"`([a-z_]+)`", holds))


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_config_file_follows_the_readme_layout(name):
    entry = next(c for c in SPEC["configs"] if c["name"] == name)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert set(cfg) == _readme_config_keys() | {"name", "source",
                                                "deployment"}
    assert cfg["source"] == entry["source"]
    assert cfg["precision"] == "float32"
    # every cut of the source is explained in assumed, by its key
    said = {a.split(":")[0] for a in cfg["assumed"]}
    assert set(cfg["reduced"]) <= said


def test_ring_shares_the_permutations_fabric():
    ring = ring_config()
    perm = harness.load_cell("perm1024.run").config
    for key in FABRIC:
        assert ring[key] == perm[key], key
    assert ring["flows"] == {"generator": "ddp_ring", "ranks": 64,
                             "message_bytes": 26214400}
    assert ring["max_ticks"] == 80000
    assert ring["reduced"] == ["tree", "smartt", "params", "ranks"]


def _read(rec):
    return harness._load_module("metrics", "live_flow_share").read(rec)


def _small_cell():
    cell = harness.load_cell("ring64.run")
    cell.config = small_ring_config()
    return cell


def _record(cell, iterations):
    return harness.Record(cell=cell, setup_s=1.0, window_s=1.0,
                          iterations=iterations, spans=[],
                          trace={"busy_s": 0.5, "window_s": 1.0},
                          trace_iterations=iterations)


def _read_in_a_run(rec, seed):
    """The reader as ``harness.run_cell`` calls it: from a frame that
    holds the run's ``rec`` and ``seed``."""
    return _read(rec)


def test_live_flow_share_reader():
    rec = _record(_small_cell(), [dict(ticks=[100], done=[True])])
    # the program's slots for 8 ranks: 2 * 7 * 8 = 112 flows
    rec.scopes, rec.counters = None, {"ticks_executed": 50,
                                      "flow_ticks_live": 280, "now": 100}
    assert _read_in_a_run(rec, 4) == pytest.approx(100 * 280 / (50 * 112))
    # silent: a program whose loop does not count live flows, no counted
    # run, no run to take the seed from
    for counters in ({"ticks_executed": 50, "now": 100}, None,
                     {"ticks_executed": 0, "flow_ticks_live": 0, "now": 0}):
        rec.counters = counters
        assert _read_in_a_run(rec, 4) is None
    rec.counters = {"ticks_executed": 50, "flow_ticks_live": 280, "now": 100}
    assert _read(rec) is None


def test_live_flow_share_on_a_small_ring():
    """On the CPU: the scoped measurement's counted run of the slice's
    first salt gives the share, which stays under the ranks' share of the
    flows."""
    cell = _small_cell()
    seed = 2**33 + 9
    mix = harness.RunsMix(cell, seed, harness.Spans(), jax.devices()[:1])
    mix.iteration(keep=False)
    window = [mix.iteration(keep=False)]
    sliced = [mix.iteration(keep=False) for _ in range(2)]
    rec = _record(cell, window)
    rec.trace_iterations = sliced
    share = _read_in_a_run(rec, seed)
    c = rec.counters
    assert c["now"] == sliced[0]["ticks"][0]
    assert share == pytest.approx(
        100.0 * c["flow_ticks_live"] / (c["ticks_executed"] * 112))
    assert 0 < share <= 100.0 * 8 / 112
