"""Bucket ring all-reduce (Patarasuk & Yuan, JPDC 2009; the NCCL ring)
over every host in rank order: ``2 (N - 1)`` steps, in each of which
every host sends one ``chunk_bytes`` chunk to its successor.  From the
second step on, host ``i``'s chunk waits until the chunk of the step
before has fully arrived from its predecessor ``i - 1`` (one dependency
per flow).  Flow ``s * N + i`` is host ``i``'s send of step ``s``; each
host's flows are ordered by step.  The seed plays no part."""

from __future__ import annotations

import numpy as np


def generate(tree: dict, params: dict, seed: int) -> dict:
    del seed
    n = tree["racks"] * tree["nodes_per_rack"]
    steps = 2 * (n - 1)
    step = np.repeat(np.arange(steps), n)
    host = np.tile(np.arange(n), steps)
    f = len(host)
    chunk = params["chunk_bytes"]
    dep_par = np.where(step > 0, (step - 1) * n + (host - 1) % n, -1)
    return dict(src=host.astype(np.int32),
                dst=((host + 1) % n).astype(np.int32),
                size=np.full(f, chunk, np.int32),
                t_start=np.zeros(f, np.int32),
                order=step.astype(np.int32),
                dep_par=dep_par.astype(np.int32)[:, None],
                dep_thr=np.where(step > 0, chunk, 0).astype(np.int32)[:, None])
