"""Simulated ticks per second of host wall time: the sum over every
simulation the window ran of its own final ``now``, over the window's
wall time, which ends after the last result is on the host."""


def read(rec):
    return rec.sim_ticks / rec.window_s
