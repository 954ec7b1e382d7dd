"""Vectorized, time-stepped packet-level simulator — composition layer.

Execution model (DESIGN.md Sec. 6): one tick = one MTU serialization time;
every output port forwards at most one data packet per tick.  All state is
struct-of-arrays with static shapes; one tick is a pure function
``step: SimState -> SimState`` executed in superstep-fused run loops
(aggregate runs, early exit) or under ``lax.scan`` (trace runs, per-tick
outputs).

The aggregate run loops execute in *supersteps* (DESIGN.md Sec. 6): a
``lax.fori_loop`` fuses ``Dims.superstep`` ticks per ``while_loop``
iteration, amortizing the while-loop round-trip (cond dispatch + carry
handling) over K ticks; each fused tick is individually gated on the same
exit condition (``lax.cond``), keeping every trajectory bit-for-bit
identical to the K=1 loop.  When ``Dims.leap`` holds, each superstep first
applies an *event-horizon time leap* (DESIGN.md Sec. 6.3): a cheap
reduction over the delay rings, armed timers, and admission predicates
yields the distance to the next eventful tick, and ``now`` advances by it
in O(1) — event-free ticks are state no-ops by construction, so the
leap-on trajectory stays bit-for-bit equal to leap-off.  All run-loop
entry points donate the incoming ``SimState`` buffers to XLA (callers
must treat a state passed to a run loop as consumed).

The six sub-steps of a tick live in dedicated phase modules, each a pure
function ``(Dims, Consts, SimState) -> SimState``:

  1. departures : ``fabric.departures``  (dequeue, RED mark, route, wire)
  2. arrivals   : ``fabric.arrivals``    (enqueue/trim/drop or deliver/ACK)
  3. control    : ``transport.control``  (ACK/trim/timeout -> CC + LB)
  4. grants     : ``sender.grants``      (EQDS pull credits)
  5. sends      : ``sender.sends``       (arbitration, admission, emission)
  6. metrics    : ``metrics.account``    (occupancy/rate accounting)

``build`` resolves the CC algorithm to a backend-qualified update function
(``cc_backend="jnp"`` pure jnp, or ``"pallas"`` for the ``kernels/
cc_update`` kernel) — and, the same way, the fabric's fused
enqueue-rank/arbitration pair (``fabric_backend`` ->
``kernels/enqueue_arb``) and the transport's packed sent-ring drain
(``transport_backend`` -> ``kernels/ring_drain``); every backend pair is
bit-for-bit interchangeable (DESIGN.md Sec. 6.4).  The phases compose
over a ``Consts`` bundle of traced numerics — so retuning any parameter,
or sweeping a whole grid of them, reuses one compiled step.  Batched
execution (seed batches, sweep grids, full seed x point studies) lives in
the experiment API (``netsim/api.py``, DESIGN.md Sec. 7): its lane loop
(``shard.run_lanes``) vmaps ``step_fn`` over ``[P*S]`` lanes with per-lane
exit gating and leap horizons.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis import counter as _trace_counter
from repro.core import registry, reps
from repro.core.types import CCParams
from repro.kernels.enqueue_arb import ops as enqueue_arb_ops
from repro.kernels.ring_drain import ops as ring_drain_ops
from repro.netsim import fabric, metrics, sender, transport
from repro.netsim.metrics import HIST_BINS, jain_fairness, summarize  # noqa: F401 (re-export)
from repro.netsim.state import (Consts, Dims, SimConfig, SimState,  # noqa: F401
                                derive, init_state, ring_loop_form,
                                ring_public_form)
from repro.netsim.topology import Topology
from repro.netsim.units import Timing
from repro.netsim.workloads import Workload

I32 = jnp.int32
F32 = jnp.float32

# Incremented each time a composed step function is *traced* (not executed).
# ``tests/test_api.py`` asserts a whole parameter grid costs exactly one:
# ``with trace_guard("engine.step", expect=1): ...`` (repro.analysis).
_STEP_TRACES = _trace_counter("engine.step")


class LoopCounters(NamedTuple):
    """What a run loop did, counted on the device beside the state (never
    a ``SimState`` leaf): ``ticks_executed`` gated ticks that stepped,
    ``supersteps`` while iterations, ``leaps`` leaps with a distance > 0,
    ``ticks_leapt`` the sum of those distances, ``flow_ticks_live`` the
    sum over the ticks that stepped of the flows ``sender.activated``
    holds live at the tick's start (``Sim.live_flows``).  Scalars for a
    single run; in the lane loop one entry per lane (``supersteps``
    counts the iterations of the loop that ran the lane)."""

    ticks_executed: jax.Array
    supersteps: jax.Array
    leaps: jax.Array
    ticks_leapt: jax.Array
    flow_ticks_live: jax.Array


@dataclasses.dataclass(frozen=True)
class Sim:
    """Compiled simulator bundle."""

    cfg: SimConfig
    topo: Topology
    timing: Timing
    wl: Workload
    cc_params: CCParams
    lb_params: reps.LBParams
    dims: Dims
    consts: Consts
    phases: tuple           # ordered ((name, (Consts, SimState) -> SimState),
                            #   ...) — the six tick sub-steps step_fn composes,
                            # each under ``jax.named_scope(name)``; the jaxpr
                            # auditor (repro.analysis.audit) walks these so its
                            # phase split can never drift from the real tick
    step_fn: callable       # (Consts, SimState) -> SimState — sweepable form;
                            #   phases and step take the state with its
                            #   ring in the loop form (ring_loop_form)
    step: callable          # SimState -> SimState (consts bound)
    horizon_fn: callable    # (Consts, SimState) -> i32 next-event distance
    horizon: callable       # SimState -> i32 (consts bound)
    init: callable          # () -> SimState
    live_flows: callable    # (Consts, SimState) -> i32 flows that
                            #   sender.activated holds live (counters)

    @property
    def arb_fill(self) -> float:
        """Share of ``sender.sends``' round-robin table, ``[NS, FMAX]``,
        whose cells hold a flow: ``NF / (NS * FMAX)``.  ``dims.arb_map``
        says how the flags fill it."""
        d = self.dims
        return d.NF / (d.NS * d.FMAX)

    def run(self, max_ticks: int, seed: int = 0, counters: bool = False):
        """Run to completion.  ``seed`` sets the per-run hash salt
        (RED/ECMP decorrelation) — seed 0 is the historical default.
        With ``counters`` the loop also counts what it did and the call
        returns ``(state, LoopCounters)``; the state is bit-identical."""
        with jax.profiler.TraceAnnotation("netsim.init_state"):
            st0 = self.init()
            if seed:
                st0 = st0._replace(salt=jnp.asarray(seed, I32))
        with jax.profiler.TraceAnnotation("netsim.run_loop"):
            return _run_until_done(self.step_fn,
                                   self.horizon_fn if self.dims.leap else None,
                                   self.consts, st0, max_ticks,
                                   self.dims.superstep, counters,
                                   self.live_flows if counters else None)

    def run_trace(self, ticks: int, trace_flows: int = 8):
        return _run_trace(self.step, self.init(), ticks, trace_flows)


# --------------------------------------------------------------------------
# build
# --------------------------------------------------------------------------


def build(cfg: SimConfig, wl: Workload) -> Sim:
    with jax.profiler.TraceAnnotation("netsim.build"):
        return _build(cfg, wl)


def _build(cfg: SimConfig, wl: Workload) -> Sim:
    topo, tm, dims, consts = derive(cfg, wl)
    cc_update = registry.get(cfg.algo, cfg.cc_backend)
    # fabric/transport hot-loop backends, resolved once like cc_update:
    # enqueue-rank + round-robin arbitration (kernels/enqueue_arb) and the
    # packed sent-ring drain (kernels/ring_drain) — "jnp" is the reference
    # vector program, "pallas" the bit-identical blocked kernel
    enqueue, arb = enqueue_arb_ops.get(cfg.fabric_backend)
    drain = ring_drain_ops.get(cfg.transport_backend)

    phases = (
        ("departures", lambda c, st: fabric.departures(dims, c, st)),
        ("arrivals", lambda c, st: fabric.arrivals(dims, c, st,
                                                   enqueue=enqueue)),
        ("control", lambda c, st: transport.control(dims, c, cc_update, st,
                                                    drain=drain)),
        ("grants", lambda c, st: sender.grants(dims, c, st, arb=arb)),
        ("sends", lambda c, st: sender.sends(dims, c, st, arb=arb)),
        ("metrics", lambda c, st: metrics.account(dims, c, st)),
    )

    def step_fn(consts: Consts, st: SimState) -> SimState:
        _STEP_TRACES.hit()
        for name, phase in phases:
            with jax.named_scope(name):
                st = phase(consts, st)
        return st._replace(now=st.now + 1)

    def step(st: SimState) -> SimState:
        return step_fn(consts, st)

    def horizon_fn(consts: Consts, st: SimState):
        """Distance (ticks) to the next eventful tick — min over the
        per-phase next-event reductions (DESIGN.md Sec. 6.3)."""
        h = fabric.horizon(dims, consts, st)
        h = jnp.minimum(h, transport.horizon(dims, consts, st))
        return jnp.minimum(h, sender.horizon(dims, consts, st))

    def horizon(st: SimState):
        return horizon_fn(consts, st)

    def init() -> SimState:
        return init_state(dims, consts)

    def live_flows(consts: Consts, st: SimState):
        return jnp.sum(sender.activated(dims, consts, st).astype(I32))

    return Sim(cfg=cfg, topo=topo, timing=tm, wl=wl, cc_params=consts.cc,
               lb_params=consts.lb, dims=dims, consts=consts, phases=phases,
               step_fn=step_fn, step=step, horizon_fn=horizon_fn,
               horizon=horizon, init=init, live_flows=live_flows)


# --------------------------------------------------------------------------
# run loops (superstep execution; donated state buffers)
# --------------------------------------------------------------------------
#
# The outer while loop advances one *superstep* (K fused ticks) per
# iteration, amortizing the loop round-trip over K ticks.  Each fused tick
# is gated on the *same* exit predicate via ``lax.cond`` (so the cheap
# reduction still runs per tick, but as part of the fused body) — the
# predicate is scalar (reduced over flows; the api lane loop additionally
# gates each lane on its own predicate) so the cond stays a real branch,
# and once the run
# finishes or hits max_ticks the remaining ticks of the superstep are
# identity — which makes every K > 1 trajectory bit-for-bit identical to
# K = 1, including ``now`` and all metrics counters (asserted in
# tests/test_engine_superstep.py).
#
# ``donate_argnums`` hands the incoming state's buffers to XLA for in-place
# reuse as the loop carry.  Contract: a ``SimState`` passed to a run loop
# is consumed — callers must not read it afterwards (all entry points here
# build a fresh ``init()`` per call).


def _superstep_loop(step, cond, K, leap=None, counters=False, live=None,
                    live_flows=None):
    """while(cond) { leap?; K x (cond ? step : id) } — cond reduced once
    per K.

    Every K (including 1) uses the same gated fori-in-while structure, so
    the tick graph is embedded — and therefore lowered by XLA — identically
    for every superstep size; only the trip count changes.  (Embedding the
    K=1 tick bare in the while body changes XLA's fusion/FMA-contraction
    decisions and perturbs f32 CC arithmetic by an ULP, which would break
    the bit-for-bit equivalence contract across K.)

    ``leap``, when given, runs once per superstep before the fused ticks:
    ``st -> (st, d)`` advances ``now`` by ``d`` to the next event horizon
    in O(1) (DESIGN.md Sec. 6.3).  The leap lands *at or before* the next
    eventful tick and the leap distance is clamped to the remaining tick
    budget, so the gated ticks that follow execute exactly the eventful
    ticks (plus event-free ticks, which are state no-ops) of the leap-free
    trajectory.

    The leap runs under ``jax.named_scope("leap")``, the exit predicate
    and the per-tick gate under ``"loop_ctl"`` (the step names its own
    phases).  With ``counters`` the loop carries a ``LoopCounters`` beside
    the state and returns ``(state, counters)``; ``live`` (default
    ``cond``) says which ticks stepped and ``live_flows`` (``st -> i32``,
    required with ``counters``) how many flows are live at a tick's start
    — both per lane in the lane loop.  With ``counters`` off the carry is
    the state alone and ``live_flows`` is never traced.

    ``run`` takes and returns the public state; the loop carries the
    port-queue ring in its loop form (``state.ring_loop_form``), converted
    once on entry and once on exit, which the step expects."""
    live = cond if live is None else live
    if counters and live_flows is None:
        raise ValueError("a counting loop needs live_flows")

    def gate(st):
        with jax.named_scope("loop_ctl"):
            return cond(st)

    def tick(_, carry):
        st, c = carry
        if c is not None:
            with jax.named_scope("loop_ctl"):
                stepped = live(st)
                c = c._replace(
                    ticks_executed=c.ticks_executed + stepped.astype(I32),
                    flow_ticks_live=c.flow_ticks_live
                    + jnp.where(stepped, live_flows(st), 0))
        return jax.lax.cond(gate(st), step, lambda s: s, st), c

    def body(carry):
        st, c = carry
        if leap is not None:
            with jax.named_scope("leap"):
                st, d = leap(st)
            if c is not None:
                c = c._replace(leaps=c.leaps + (d > 0).astype(I32),
                               ticks_leapt=c.ticks_leapt + d)
        if c is not None:
            c = c._replace(supersteps=c.supersteps + 1)
        return jax.lax.fori_loop(0, max(K, 1), tick, (st, c))

    def run(st):
        rows, cap = st.q_fields.shape[-3:-1]
        st = ring_loop_form(st)
        c = None
        if counters:
            zero = jnp.zeros(jax.eval_shape(live, st).shape, I32)
            c = LoopCounters(*[zero] * len(LoopCounters._fields))
        st, c = jax.lax.while_loop(lambda carry: gate(carry[0]), body,
                                   (st, c))
        st = ring_public_form(st, rows, cap)
        return (st, c) if counters else st

    return run


def _leap(horizon, max_ticks):
    """Single-run time leap: jump ``now`` to the next event horizon and
    apply the closed-form Δ-tick accounting (``metrics.leap_account``);
    returns ``(state, d)``, ``d`` the distance leapt.

    Today's leap predicate only jumps with every queue empty, so the
    occupancy integral provably contributes 0.0 — the general Δ * Σq form
    is kept so a relaxed predicate (e.g. leaping a degraded link's idle
    service periods with packets parked) inherits correct accounting."""
    def leap(st):
        d = jnp.minimum(horizon(st), max_ticks - st.now)
        occ = jnp.sum(st.q_size[:-1])
        return st._replace(now=st.now + d,
                           m=metrics.leap_account(st.m, d, occ)), d
    return leap


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5, 6, 7),
                   donate_argnums=(3,))
def _run_until_done(step_fn, horizon_fn, consts: Consts, state0: SimState,
                    max_ticks: int, superstep: int, counters: bool = False,
                    live_flows=None):
    # ``consts`` is an argument, as in the lane loop, and not closed over:
    # closed-over scalars become literals that XLA folds into the f32
    # arithmetic (``cwnd / bdp * fd`` -> ``cwnd * c``), which rounds
    # differently from the lane loop on the TPU
    def cond(st):
        return (st.now < max_ticks) & ~jnp.all(st.done)

    step = functools.partial(step_fn, consts)
    leap = (_leap(functools.partial(horizon_fn, consts), max_ticks)
            if horizon_fn is not None else None)
    count = (functools.partial(live_flows, consts)
             if live_flows is not None else None)
    return _superstep_loop(step, cond, superstep, leap, counters,
                           live_flows=count)(state0)


@functools.partial(jax.jit, static_argnums=(0, 2, 3), donate_argnums=(1,))
def _run_trace(step, state0: SimState, ticks: int, trace_flows: int):
    tf = trace_flows

    def body(st, _):
        st2 = step(st)
        nq = st2.q_size.shape[0] - 1
        ys = dict(
            cwnd=st2.cc.cwnd[:tf],
            q_mean=jnp.mean(st2.q_size[:nq].astype(F32)),
            q_max=jnp.max(st2.q_size[:nq]),
            delivered=st2.m.delivered_bytes,
            goodput=st2.goodput[:tf],
            done=jnp.sum(st2.done.astype(I32)),
        )
        return st2, ys

    rows, cap = state0.q_fields.shape[-3:-1]
    st, ys = jax.lax.scan(body, ring_loop_form(state0), None, length=ticks)
    return ring_public_form(st, rows, cap), ys
