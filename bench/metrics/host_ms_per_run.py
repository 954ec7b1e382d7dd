"""Host milliseconds per single run: the benchmark's spans around
``Sim.run`` (call to return: eager init and dispatch), ``jax.device_get``
and ``RunResult.from_state``, summed over the window's runs and divided
by their number.  The wait in ``block_until_ready`` is left out."""

HOST_SPANS = ("init_dispatch", "device_get", "result")


def read(rec):
    runs = sum(1 for name, _, _ in rec.spans if name == "init_dispatch")
    if not runs:
        return None
    host = sum(b - a for name, a, b in rec.spans if name in HOST_SPANS)
    return host / runs * 1e3
