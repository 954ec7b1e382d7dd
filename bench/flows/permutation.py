"""Cross-rack permutation: every host sends one flow to the host a whole
number of racks ahead, so each flow leaves its rack (the SMaRTT paper's
Sec. 4 permutation, "selected so that each packet crosses the core").

Parameters: ``size_bytes``, ``shift_seed`` (``"run"`` = the run's seed).
The rack shift is drawn as ``1 + integers(0, racks - 1)`` from
``numpy.random.default_rng``."""

from __future__ import annotations

import numpy as np


def generate(tree: dict, params: dict, seed: int) -> dict:
    n = tree["racks"] * tree["nodes_per_rack"]
    rng = np.random.default_rng(seed)
    shift = tree["nodes_per_rack"] * (1 + rng.integers(0, tree["racks"] - 1))
    src = np.arange(n, dtype=np.int32)
    return dict(src=src, dst=((src + shift) % n).astype(np.int32),
                size=np.full(n, params["size_bytes"], np.int32),
                t_start=np.zeros(n, np.int32), order=np.zeros(n, np.int32))
