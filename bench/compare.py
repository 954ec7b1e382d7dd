"""The comparison that decides ``correct``: a final state of the program
against the reference's, element by element over every leaf."""

from __future__ import annotations

import numpy as np


def flatten(state) -> dict:
    """The program's final state (a host ``SimState``) as ``{path: array}``
    with nested fields as ``"cc.cwnd"``, ``"lb.next_entropy"``, ``"m.n_ack"``:
    the names the reference uses."""
    out = {}
    for k, v in zip(state._fields, state):
        if hasattr(v, "_fields"):
            for k2, v2 in zip(v._fields, v):
                out[f"{k}.{k2}"] = np.asarray(v2)
        else:
            out[k] = np.asarray(v)
    return out


def differing(got: dict, want: dict) -> dict:
    """``{leaf: elements that differ}`` over the union of both leaf sets.
    Values are compared exactly, as numbers; a leaf missing on one side or
    of another shape counts every element of the larger side."""
    out = {}
    for k in sorted(set(got) | set(want)):
        a = np.asarray(got[k]) if k in got else None
        b = np.asarray(want[k]) if k in want else None
        if a is None or b is None or a.shape != b.shape:
            out[k] = int(max(np.size(a) if a is not None else 0,
                             np.size(b) if b is not None else 0, 1))
            continue
        n = int(np.count_nonzero(a.astype(np.float64) != b.astype(np.float64)))
        if n:
            out[k] = n
    return out
