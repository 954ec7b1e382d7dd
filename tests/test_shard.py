"""Device-sharded lane execution (netsim/shard.py, DESIGN.md Sec. 7):
the shard_map path must be bit-for-bit identical to the single-device
vmap path — full final-state pytree, every lane — and lane padding must
be inert ballast.

Single-device runs exercise the shard_map machinery on a 1-device mesh
(same partition specs, same loop body); the true multi-device parity
tests run wherever >= 2 host devices are forced
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — CI's
multidevice job) and skip elsewhere.
"""

import jax
import numpy as np
import pytest

from repro.netsim import api, shard

MULTI = jax.device_count() >= 2

POINTS = ({}, {"start_cwnd_mult": 0.5})
SEEDS = (0, 1, 2)


def _study():
    return api.study("tiny_3t", points=POINTS, seeds=SEEDS)


def _assert_state_equal(st_a, st_b):
    la, lb = jax.tree.leaves(st_a), jax.tree.leaves(st_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _sharded(st, mesh, max_ticks=None):
    """Run a Study's lane batch through the shard_map path explicitly
    (``run_lanes`` would short-circuit a 1-device mesh to vmap)."""
    mt = st._max_ticks(max_ticks)
    horizon_fn = st.sim.horizon_fn if st.sim.dims.leap else None
    states, consts_p, n_pad = shard.pad_lanes(st.init(), st.consts_b,
                                              st.axes, mesh.size)
    out = shard._run_lanes_sharded(st.sim.step_fn, horizon_fn, st.axes, mt,
                                   st.sim.dims.superstep, mesh, consts_p,
                                   states)
    if n_pad:
        out = jax.tree.map(lambda x: x[:st.n_lanes], out)
    return out


# --------------------------------------------------------------------------
# single-device (runs everywhere)
# --------------------------------------------------------------------------


def test_shard_map_on_one_device_matches_vmap():
    """shard_map with a 1-device mesh is the same program as the vmap
    path — bit-identical full final states."""
    st = _study()
    ref = st.run_states()
    out = _sharded(st, shard.lane_mesh(jax.devices()[:1]))
    _assert_state_equal(ref, out)


def test_run_lanes_short_circuits_small_mesh():
    """``run_lanes(mesh=1-device)`` must take the plain vmap path and
    stay bit-identical to ``mesh=None``."""
    st = _study()
    ref = st.run_states()
    out = st.run_states(mesh=shard.lane_mesh(jax.devices()[:1]))
    _assert_state_equal(ref, out)


def test_pad_lanes_shapes_and_inertness():
    """Padding to a non-dividing multiple appends copies of the last lane
    with every flow done; the gated loop then freezes them bitwise (a pad
    lane's final state == its initial state) while real lanes are
    untouched."""
    st = _study()
    B = st.n_lanes
    padded, consts_p, n_pad = shard.pad_lanes(st.init(), st.consts_b,
                                              st.axes, 4)
    assert n_pad == (-B) % 4 and n_pad > 0
    assert padded.now.shape[0] == B + n_pad
    assert bool(np.all(np.asarray(padded.done)[B:]))
    # swept consts leaves padded alongside, deduped leaves untouched
    for leaf, ax in zip(jax.tree.leaves(consts_p),
                        shard.axes_leaves(st.axes)):
        if ax == 0:
            assert np.asarray(leaf).shape[0] == B + n_pad
    # run the padded batch; real lanes match the unpadded run, pad lanes
    # froze at their (done-marked) init
    mesh = shard.lane_mesh(jax.devices()[:1])
    horizon_fn = st.sim.horizon_fn if st.sim.dims.leap else None
    mt = st._max_ticks(None)
    init_pad = jax.device_get(jax.tree.map(lambda x: x[B:], padded))
    out = shard._run_lanes_sharded(st.sim.step_fn, horizon_fn, st.axes, mt,
                                   st.sim.dims.superstep, mesh, consts_p,
                                   padded)
    ref = st.run_states()
    _assert_state_equal(ref, jax.tree.map(lambda x: x[:B], out))
    _assert_state_equal(init_pad, jax.tree.map(lambda x: x[B:], out))


def test_pad_lanes_noop_when_divisible():
    st = _study()
    states0 = st.init()
    padded, consts_p, n_pad = shard.pad_lanes(states0, st.consts_b,
                                              st.axes, st.n_lanes)
    assert n_pad == 0
    assert padded is states0 and consts_p is st.consts_b


# --------------------------------------------------------------------------
# multi-device (CI multidevice job; skips on a single-device host)
# --------------------------------------------------------------------------


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=N)")
def test_multi_device_study_bit_identical_to_vmap():
    """THE acceptance property: a Study sharded over every forced host
    device produces lane states bit-identical to the single-device vmap
    path — full final-state pytree, including ``now`` and metrics.  Lane
    count (6) does not divide the device count, so the pad path is
    exercised too."""
    st = _study()
    ref = st.run_states()
    out = st.run_states(mesh=shard.lane_mesh())
    _assert_state_equal(ref, out)


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_multi_device_study_run_results_match():
    """The typed results of a sharded ``Study.run`` are row-for-row equal
    to the plain run."""
    st = _study()
    ref = st.run()
    out = st.run(mesh=shard.lane_mesh())
    assert [r.row() for r in ref.results] == [r.row() for r in out.results]
    _assert_state_equal(ref.states, out.states)


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_multi_device_seed_lanes_match():
    """Seed-only ``Study.run_states(mesh=...)`` parity — and transitively
    parity with every standalone ``run(seed=s)`` (test_api covers that
    leg)."""
    st = api.study("tiny_3t", seeds=range(5))
    ref = st.run_states()
    out = st.run_states(mesh=shard.lane_mesh())
    _assert_state_equal(ref, out)
