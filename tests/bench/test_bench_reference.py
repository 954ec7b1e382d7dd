"""The plain reference against the program, and its control.

At small sizes on the CPU the program's final state equals the
reference's in every element, for permutation and ring traffic and for
several salts; the reference computed in bfloat16 (the control: the
precision below the float32 the configurations state) does not."""

import jax
import jax.numpy as jnp
import pytest

import compare
import harness
from reference import Reference

SMALL = dict(racks=4, nodes_per_rack=4, uplinks=1, pods=2, core_uplinks=1)


def small_config(generator: str) -> dict:
    """``perm1024.run``'s configuration on a 16-host tree, with its
    permutation or with the ring all-reduce's dependency-gated flows."""
    cfg = harness.load_cell("perm1024.run").config
    cfg["tree"] = dict(SMALL)
    cfg["max_ticks"] = 20000
    if generator == "permutation":
        cfg["flows"]["size_bytes"] = 64 * 1024
    else:
        cfg["flows"] = {"generator": generator, "chunk_bytes": 16 * 1024}
    return cfg


def program_state(cfg, flows, salt, **point):
    sc = harness.scenario(cfg, flows, "small")
    if point:
        sc = sc.with_(**point)
    return compare.flatten(jax.device_get(
        sc.build().run(max_ticks=cfg["max_ticks"], seed=salt)))


def reference_state(cfg, flows, salt, F=jnp.float32, **point):
    ref = Reference(cfg["tree"], cfg["link"], flows, cfg["smartt"],
                    cfg["params"], cfg["max_ticks"], F=F)
    c = ref.consts(dict(cfg["params"], **point))
    return jax.device_get(ref.jit_run()(c, salt))


@pytest.mark.parametrize("generator,salt", [
    ("permutation", 1), ("permutation", 2**31 - 2),
    ("ring_allreduce", 9)])
def test_reference_equals_program(generator, salt):
    cfg = small_config(generator)
    flows = harness.flow_table(cfg, 3)
    got = program_state(cfg, flows, salt)
    assert bool(got["done"].all())
    assert compare.differing(got, reference_state(cfg, flows, salt)) == {}


def test_reference_follows_the_sweep_point():
    cfg = small_config("permutation")
    flows = harness.flow_table(cfg, 5)
    got = program_state(cfg, flows, 4, start_cwnd_mult=0.5)
    assert compare.differing(
        got, reference_state(cfg, flows, 4, start_cwnd_mult=0.5)) == {}
    assert compare.differing(got, reference_state(cfg, flows, 4)) != {}


def test_control_in_bfloat16_is_not_correct():
    cfg = small_config("permutation")
    flows = harness.flow_table(cfg, 3)
    got = program_state(cfg, flows, 1)
    diff = compare.differing(got, reference_state(cfg, flows, 1,
                                                  F=jnp.bfloat16))
    assert diff.get("cc.cwnd", 0) > 0, diff


def test_differing_counts_missing_and_reshaped_leaves():
    a = {"x": [1, 2, 3], "y": [0.5]}
    assert compare.differing(a, {"x": [1, 2, 4], "y": [0.5]}) == {"x": 1}
    assert compare.differing(a, {"x": [1, 2, 3]}) == {"y": 1}
    assert compare.differing(a, {"x": [1, 2], "y": [0.5]}) == {"x": 3}
