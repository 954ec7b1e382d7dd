"""Percent of a run's simulated ticks that the run loop stepped: the
counters' ``ticks_executed`` over the final ``now``, for the traced
slice's first salt re-run with ``Sim.run(..., counters=True)``, untraced
and outside the window (``scope_reduce``).  The rest the event-horizon
leap skipped."""

import scope_reduce


def read(rec):
    _, counters = scope_reduce.measured(rec)
    if counters is None or not counters["now"]:
        return None
    return 100.0 * counters["ticks_executed"] / counters["now"]
