"""Device busy microseconds per simulated tick of the operations in the
tick's ``arrivals`` phase (``jax.named_scope("arrivals")`` in
``engine.build``'s step), over the first ``scope_reduce.TRACE_TICKS``
ticks of the traced slice's first run (``scope_reduce``); nothing where
the trace holds under 99 % of the ticks the run loop executed."""

import scope_reduce


def read(rec):
    return scope_reduce.us_per_tick(rec, ("arrivals",))
