"""Data-parallel gradient all-reduce: one bucket of ``message_bytes``
reduced by the ring algorithm (Patarasuk & Yuan, JPDC 2009; NCCL's ring)
over ``ranks`` participants spread evenly across the fabric, rank ``k`` on
host ``k * (hosts // ranks)``.  The bucket is cut into one chunk per rank,
``message_bytes // ranks`` bytes: ``2 (ranks - 1)`` steps, in each of
which every rank sends one chunk to its successor.  From the second step
on, rank ``k``'s chunk waits until the whole chunk of the step before has
arrived from its predecessor ``k - 1`` (one dependency per flow).  Flow
``s * ranks + k`` is rank ``k``'s send of step ``s``; each rank's flows are
ordered by step.  The seed plays no part."""

from __future__ import annotations

import numpy as np


def generate(tree: dict, params: dict, seed: int) -> dict:
    del seed
    hosts = tree["racks"] * tree["nodes_per_rack"]
    n = params["ranks"]
    if not 2 <= n <= hosts:
        raise ValueError(f"ddp_ring wants 2 <= ranks <= {hosts}, got {n}")
    host_of = np.arange(n) * (hosts // n)
    chunk = params["message_bytes"] // n
    steps = 2 * (n - 1)
    step = np.repeat(np.arange(steps), n)
    rank = np.tile(np.arange(n), steps)
    f = len(rank)
    dep_par = np.where(step > 0, (step - 1) * n + (rank - 1) % n, -1)
    return dict(src=host_of[rank].astype(np.int32),
                dst=host_of[(rank + 1) % n].astype(np.int32),
                size=np.full(f, chunk, np.int32),
                t_start=np.zeros(f, np.int32),
                order=step.astype(np.int32),
                dep_par=dep_par.astype(np.int32)[:, None],
                dep_thr=np.where(step > 0, chunk, 0).astype(np.int32)[:, None])
