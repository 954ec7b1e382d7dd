"""Device busy microseconds per simulated tick: the union of the
intervals of the device operations that do work (``trace_reduce``: the
``while`` and ``conditional`` events that enclose them left out, so a gap
inside the run loop is not busy) in the traced slice of two whole runs,
averaged over the chips, over the ticks the slice simulated."""


def read(rec):
    ticks = sum(sum(it["ticks"]) for it in rec.trace_iterations)
    if rec.trace is None or not ticks:
        return None
    return rec.trace["busy_s"] * 1e6 / ticks
