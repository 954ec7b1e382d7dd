"""Percent of the traced slice (two consecutive runs, so the host gap
between them is inside it) in which no operation ran on the device,
averaged over the chips.  Gaps between operations inside the run loop
count (``trace_reduce``)."""


def read(rec):
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])
