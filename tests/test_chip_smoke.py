"""The chip smoke script's phases on tiny fabrics, on the CPU.

``chip_smoke.py`` runs the paper-scale scenarios on a TPU; here each phase
runs on a tiny scenario with the Pallas kernels in interpret mode, so its
checks and control flow are exercised without a chip.  ``main`` itself must
refuse a machine without a TPU.
"""

import importlib.util
import json
from pathlib import Path

import jax
import pytest

from repro.netsim import scenarios, workloads

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _alltoall_tiny():
    """Eight nodes, seven flows each: FMAX > 1, so the round-robin pick
    kernel runs."""
    tree = scenarios.TREE_3T_TINY
    return scenarios.scenario("tiny_3t").with_(
        name="tiny_3t_alltoall",
        wl=workloads.alltoall(tree, size_bytes=8 * scenarios.KiB, window=4))


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and out.strip() == ""


def test_phase_a_runs_to_completion(smoke, capsys):
    sim, st = smoke.phase_a(scenarios.scenario("tiny_3t"))
    assert bool(st.done.all())
    sent, accounted = smoke.conservation_ledger(sim.dims, st)
    assert sent == accounted > 0
    assert "8/8 flows done" in capsys.readouterr().out


def test_phase_a_fails_when_the_budget_runs_out(smoke):
    with pytest.raises(RuntimeError, match="flows done"):
        smoke.phase_a(scenarios.scenario("tiny_3t", max_ticks=20))


@pytest.mark.parametrize("make", [lambda: scenarios.scenario("tiny_incast3"),
                                  _alltoall_tiny],
                         ids=["incast", "alltoall"])
def test_phase_b_kernels_match_jnp(smoke, make):
    """In interpret mode every kernel, cc_update included, is bit-identical
    to its jnp reference, and no compiled kernel is in the tick."""
    sc = make()
    assert sc.name != "tiny_3t_alltoall" or sc.build().dims.FMAX > 1
    b = smoke.phase_b(sc)
    assert b == dict(kernel_calls=0, cc_diff=[])


def test_phase_c_lane_zero_equals_standalone(smoke):
    digest = smoke.phase_c(scenarios.scenario("tiny_incast3"), (0, 1, 2))
    assert len(digest) == 64


def test_cpu_digest_is_reported_not_checked(smoke, capsys):
    sim = scenarios.scenario("tiny_3t").build()
    same = smoke.cpu_digest(sim, "tiny_3t")
    assert isinstance(same, bool)
    doc = json.loads(smoke.DIGESTS.read_text())
    assert f"at {doc['budgets']['tiny_3t']} ticks" in capsys.readouterr().out


def test_phase_sharded_on_the_visible_devices(smoke):
    devices = jax.devices()
    d = smoke.phase_sharded(scenarios.scenario("tiny_3t"), (0, 1, 2, 3),
                            devices[:min(len(devices), 4)])
    assert len(d) == 64


def test_differing_leaves_names_the_leaf(smoke):
    st = scenarios.scenario("tiny_3t").build().init()
    other = st._replace(now=st.now + 1)
    assert smoke.differing_leaves(st, st) == []
    assert smoke.differing_leaves(st, other) == [".now"]
