"""Seconds from the start of the process to the start of the window:
imports, building the flow table and the simulator, and the
warm-up iteration, which compiles or loads every program the window
runs."""


def read(rec):
    return rec.setup_s
