"""Backend dispatch for the enqueue-rank + arbitration kernel.

``get(backend)`` resolves ``SimConfig.fabric_backend`` to a pair of
phase-facing callables (the engine passes them into ``fabric.arrivals``
and ``sender.grants``/``sends``):

  ``enqueue(in_tbl, in_pos, sw_of_q, edst, q_head, q_size, cap, nq)
      -> (acc, pos, q_counts)``
      Same-destination enqueue acceptance + ring position per
      enqueue-capable emitter (the compact [EQ] axis — see
      ``topology.build_topology``), plus the per-queue accepted count.
      The switch-group gather/scatter (``in_tbl``/``in_pos``) and the
      ``sw_of_q`` group-reduce stay out here in jnp — only the
      O(DMAX^2) compare+reduce core differs per backend.  ``q_counts``
      replaces a ``segment_sum`` scatter: every writer into queue q sits
      in the fan-in group of q's owning switch, so a [NQ, DMAX]
      compare+mask reduce over ``gdst[sw_of_q]`` counts acceptances
      densely.

  ``arb(elig, rr, kmax) -> (has, sel)``
      Per-row round-robin argmin (see ``ref.rr_pick_ref``).

Both backends are bit-for-bit interchangeable (asserted engine-deep in
tests/test_engine_pallas.py); ``pallas`` compiles through Mosaic on a
TPU and runs in interpret mode elsewhere (``kernels.interpret_mode``).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.enqueue_arb import kernel as K
from repro.kernels.enqueue_arb import ref as R

I32 = jnp.int32

BACKENDS = ("jnp", "pallas")


def enqueue_rank(in_tbl, in_pos, sw_of_q, edst, q_head, q_size, cap: int,
                 nq: int, *, backend: str, interpret: bool):
    """Acceptance + queue position for every emitter's enqueue attempt.

    ``edst`` is i32 [EQ] over the compact enqueue-capable emitters
    (sentinel ``nq`` = no enqueue this tick); ``q_head``/``q_size`` are
    the [NQ+1] queue rings.  Returns ``(acc, pos, q_counts)``
    ([EQ] bool / [EQ] i32 / [NQ] i32), bit-identical to the historical
    global [NE, NE] compare+reduce + segment_sum for every emitter with
    ``edst < nq``.
    """
    gdst = jnp.concatenate([edst, jnp.full((1,), nq, I32)])[in_tbl]
    ghead = q_head[gdst]
    gsize = q_size[gdst]
    if backend == "pallas":
        _, acc_g, pos = K.enqueue_rank(gdst, ghead, gsize, cap=cap, nq=nq,
                                       interpret=interpret)
    else:
        _, acc_g, pos = R.enqueue_rank_ref(gdst, ghead, gsize, cap=cap,
                                           nq=nq)
    # accepted count per queue, scatter-free: all of queue q's writers
    # live in the fan-in group of its owning switch, so a [NQ, DMAX]
    # compare+mask over that group's gathered destinations counts them
    qsel = gdst[sw_of_q] == jnp.arange(nq, dtype=I32)[:, None]
    q_counts = jnp.sum(jnp.where(qsel & acc_g[sw_of_q], 1, 0),
                       axis=1).astype(I32)
    # in_pos is each compact emitter's flat slot in the group tables
    return acc_g.reshape(-1)[in_pos], pos.reshape(-1)[in_pos], q_counts


def rr_pick(elig, rr, kmax: int, *, backend: str, interpret: bool):
    """Round-robin argmin per row — see ``ref.rr_pick_ref``."""
    if backend == "pallas":
        return K.rr_pick(elig, rr, kmax=kmax, interpret=interpret)
    return R.rr_pick_ref(elig, rr, kmax=kmax)


def get(backend: str):
    """Resolve a fabric backend name to ``(enqueue, arb)`` callables."""
    if backend not in BACKENDS:
        raise KeyError(
            f"unknown fabric backend {backend!r}; have {BACKENDS}")
    interpret = interpret_mode()
    return (functools.partial(enqueue_rank, backend=backend,
                              interpret=interpret),
            functools.partial(rr_pick, backend=backend,
                              interpret=interpret))
