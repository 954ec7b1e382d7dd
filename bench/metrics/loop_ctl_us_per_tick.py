"""Device busy microseconds per simulated tick of the run loop's own
operations: the leap (``leap``: the event horizon and its accounting,
once per superstep) and the exit predicate and per-tick gate
(``loop_ctl``), over the first ``scope_reduce.TRACE_TICKS`` ticks of the
traced slice's first run (``scope_reduce``); nothing where the trace
holds under 99 % of the ticks the run loop executed."""

import scope_reduce


def read(rec):
    return scope_reduce.us_per_tick(rec, scope_reduce.LOOP_SCOPES)
