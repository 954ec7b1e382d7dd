"""Device-sharded lane execution (DESIGN.md Sec. 7).

The experiment API lowers a ``Scenario x points x seeds`` grid onto one
``[B = P*S]`` lane batch (``netsim/api.py``).  This module is the
executor under it: the *lane loop* — the per-lane gated, per-lane
leaping superstep loop — plus the machinery that partitions a lane
batch across every host/accelerator device through ``jax.shard_map``:

* ``lane_loop``        the vmapped loop as a pure ``(consts_b, states)
                       -> states`` function (shared verbatim by the
                       single-device jit and every shard body, so the
                       two paths cannot drift);
* ``lane_mesh``        a 1-D ``Mesh`` over the available devices
                       (``jax.sharding.Mesh``, axis ``"lanes"`` — the
                       same mesh idiom as ``src/repro/sharding.py``,
                       reduced to the one axis lane batches need);
* ``pad_lanes``        pads a batch to a device-count multiple with
                       *frozen* lanes (copies of the last lane with
                       every flow marked done — the lane gate makes a
                       finished lane a bitwise no-op, so padding never
                       perturbs real lanes and costs no loop
                       iterations on its shard);
* ``run_lanes``        the one entry point: vmap on a single device,
                       ``shard_map`` otherwise.

Sharding semantics: each device owns a contiguous ``B/D`` block of
lanes (the batch is point-major, so seed replicas of one point land
together) and runs its *own* while loop over them — the exit reduction
and the superstep cadence are per shard, so a shard whose lanes all
finish (or leap far) stops early instead of idling through the gated
ticks of a congested lane on another device.  Per-lane trajectories
are independent by construction (the gate and the leap are per lane —
DESIGN.md Sec. 7), so the sharded result is **bit-for-bit identical**
to the single-device vmap path, which is itself bit-identical to the
standalone run of every (point, seed) (tests/test_shard.py asserts
both, over the full final-state pytree).

Swept ``Consts`` leaves (vmap axis 0) shard with the lanes; deduped
leaves (axis ``None``) replicate.  The incoming state batch is donated
(Sec. 6.1 contract); the batched consts are not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.netsim import engine, metrics, state

I32 = jnp.int32

LANE_AXIS = "lanes"


# --------------------------------------------------------------------------
# the lane loop (shared by the vmap path and every shard body)
# --------------------------------------------------------------------------


def lane_loop(step_fn, horizon_fn, axes, max_ticks: int, superstep: int,
              counters: bool = False, live_flows=None):
    """The ``[B]`` lane batch run loop as a pure function
    ``(consts_b, states) -> states`` (not jitted — the callers wrap it).

    Each lane is gated on its *own* exit predicate — the same scalar
    ``(now < max_ticks) & ~all(done)`` the standalone loop uses — so a
    finished lane freezes (its gated tick is the identity, bitwise)
    while the rest keep stepping, and every lane's final state equals
    its standalone ``Sim.run`` bit-for-bit, ``now`` included.  With
    ``horizon_fn`` the loop leaps **per lane**: each lane jumps by its
    own next-event distance under its own swept ``Consts`` (clamped to
    its remaining budget, zero once the lane is done), so sparse lanes
    skip their quiescent stretches without waiting on busy lanes
    (DESIGN.md Sec. 6.3).  The superstep structure (leap once, then K
    gated ticks per while iteration) matches ``engine._superstep_loop``
    exactly.  With ``counters`` the function returns
    ``(states, LoopCounters)``, one count per lane; ``live_flows`` (the
    ``Sim``'s, ``(Consts, SimState) -> i32``) counts each lane's live
    flows."""

    def lane_live(st):
        return (st.now < max_ticks) & ~jnp.all(st.done)

    def lane_tick(c, st):
        return jax.lax.cond(lane_live(st), lambda s: step_fn(c, s),
                            lambda s: s, st)

    st_axes = state.LANE_AXES_LOOP_FORM      # the carry is in the loop form
    vtick = jax.vmap(lane_tick, in_axes=(axes, st_axes), out_axes=st_axes)
    vlive = jax.vmap(lane_live, in_axes=(st_axes,))

    def cond(st):
        return jnp.any((st.now < max_ticks) & ~jnp.all(st.done, axis=-1))

    def run(consts_b, states: state.SimState):
        count = None
        if live_flows is not None:
            vcount = jax.vmap(live_flows, in_axes=(axes, st_axes))

            def count(st):
                return vcount(consts_b, st)

        leap = None
        if horizon_fn is not None:
            vhorizon = jax.vmap(horizon_fn, in_axes=(axes, st_axes))

            def leap(st):
                d = jnp.minimum(vhorizon(consts_b, st), max_ticks - st.now)
                d = jnp.where(vlive(st), d, 0)
                occ = jnp.sum(st.q_size[:, :-1], axis=1)
                return st._replace(now=st.now + d,
                                   m=metrics.leap_account(st.m, d, occ)), d

        return engine._superstep_loop(lambda st: vtick(consts_b, st), cond,
                                      superstep, leap, counters,
                                      live=vlive, live_flows=count)(states)

    return run


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 7, 8),
                   donate_argnums=(6,))
def _run_lanes(step_fn, horizon_fn, axes, max_ticks: int, superstep: int,
               consts_b, states: state.SimState, counters: bool = False,
               live_flows=None):
    """Single-device vmap execution of :func:`lane_loop` (the historical
    ``api._run_lanes``).  ``states`` is donated; ``consts_b`` is not
    (reused across calls)."""
    return lane_loop(step_fn, horizon_fn, axes, max_ticks,
                     superstep, counters, live_flows)(consts_b, states)


# --------------------------------------------------------------------------
# mesh + padding
# --------------------------------------------------------------------------


def lane_mesh(devices=None) -> Mesh:
    """A 1-D device mesh over ``devices`` (default: every visible
    device) with the single axis ``"lanes"``.  On CPU, multiple host
    devices come from ``XLA_FLAGS=--xla_force_host_platform_device_count=D``
    (set before jax initializes — CI's multi-device job does)."""
    devs = list(devices) if devices is not None else list(jax.devices())
    return Mesh(np.asarray(devs), (LANE_AXIS,))


def axes_leaves(axes) -> list:
    """Flatten a vmap in_axes tree (0 / None leaves) to a per-leaf
    list aligned with ``jax.tree_util.tree_flatten`` of the matching
    pytree (``None`` is a leaf here, not an empty subtree)."""
    return jax.tree_util.tree_flatten(
        axes, is_leaf=lambda x: x is None)[0]


def pad_lanes(states: state.SimState, consts_b, axes, mult: int):
    """Pad a ``[B]`` lane batch (and the swept consts leaves) to the
    next multiple of ``mult``.

    Pad lanes are copies of the last real lane with every flow marked
    ``done`` — the lane gate (`lane_loop`) then freezes them from tick
    zero, so they are pure ballast: bit-inert, loop-iteration-free on
    their shard, and sliced off by the caller after the run.  Returns
    ``(states, consts_b, n_pad)``."""
    B = int(states.now.shape[0])
    n_pad = (-B) % max(int(mult), 1)
    if n_pad == 0:
        return states, consts_b, 0

    def pad_state(x):
        tail = jnp.broadcast_to(x[-1:], (n_pad,) + x.shape[1:])
        return jnp.concatenate([x, tail], axis=0)

    states = jax.tree.map(pad_state, states)
    states = states._replace(done=states.done.at[B:].set(True))
    leaves, treedef = jax.tree_util.tree_flatten(consts_b)
    padded = [pad_state(x) if a == 0 else x
              for x, a in zip(leaves, axes_leaves(axes))]
    return (states, jax.tree_util.tree_unflatten(treedef, padded), n_pad)


def _specs(states, axes, treedef):
    """(state_specs, consts_specs) partition-spec trees: every state
    leaf shards on the lane axis; consts leaves shard iff swept
    (vmap axis 0), else replicate."""
    lane = P(LANE_AXIS)
    state_specs = jax.tree.map(lambda _: lane, states)
    consts_specs = jax.tree_util.tree_unflatten(
        treedef, [lane if a == 0 else P() for a in axes_leaves(axes)])
    return state_specs, consts_specs


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 8, 9),
                   donate_argnums=(7,))
def _run_lanes_sharded(step_fn, horizon_fn, axes, max_ticks: int,
                       superstep: int, mesh: Mesh, consts_b,
                       states: state.SimState, counters: bool = False,
                       live_flows=None):
    """shard_map execution: each device runs :func:`lane_loop` over its
    own contiguous lane block under its own while loop.  Lane count
    must be a multiple of ``mesh.size`` (see :func:`pad_lanes`)."""
    loop = lane_loop(step_fn, horizon_fn, axes, max_ticks, superstep,
                     counters, live_flows)
    _, treedef = jax.tree_util.tree_flatten(consts_b)
    state_specs, consts_specs = _specs(states, axes, treedef)
    out_specs = state_specs
    if counters:
        out_specs = (state_specs, engine.LoopCounters(
            *[P(LANE_AXIS)] * len(engine.LoopCounters._fields)))
    sharded = jax.shard_map(loop, mesh=mesh,
                            in_specs=(consts_specs, state_specs),
                            out_specs=out_specs, check_vma=False)
    return sharded(consts_b, states)


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------


def run_lanes(step_fn, horizon_fn, axes, max_ticks: int, superstep: int,
              consts_b, states: state.SimState, mesh: Mesh | None = None,
              counters: bool = False, live_flows=None):
    """Run a ``[B]`` lane batch to completion — THE batched run loop
    behind ``Study``.

    ``mesh=None`` (or a 1-device mesh) is the single-device vmap path,
    unchanged from PR 4.  A larger mesh pads the batch to a
    device-count multiple, shards lanes (and swept consts) across the
    mesh via ``shard_map``, runs one independent loop per device, and
    gathers + slices the result back to ``[B]`` — bit-identical to the
    vmap path, lane for lane.  ``states`` is donated either way.  With
    ``counters`` it returns ``(states, LoopCounters)``, one count per
    lane, and needs the ``Sim``'s ``live_flows``."""
    if mesh is None or mesh.size <= 1:
        return _run_lanes(step_fn, horizon_fn, axes, max_ticks, superstep,
                          consts_b, states, counters, live_flows)
    B = int(states.now.shape[0])
    states, consts_p, n_pad = pad_lanes(states, consts_b, axes, mesh.size)
    out = _run_lanes_sharded(step_fn, horizon_fn, axes, max_ticks,
                             superstep, mesh, consts_p, states, counters,
                             live_flows)
    if n_pad:
        out = jax.tree.map(lambda x: x[:B], out)
    return out
