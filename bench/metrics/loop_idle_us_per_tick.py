"""Idle device microseconds per simulated tick inside the run loop: the
gaps between two operations of the run-loop module, over the first
``scope_reduce.TRACE_TICKS`` ticks of the traced slice's first run
(``scope_reduce``); nothing where the trace holds under 99 % of the
ticks the run loop executed."""

import scope_reduce


def read(rec):
    return scope_reduce.loop_idle_us_per_tick(rec)
