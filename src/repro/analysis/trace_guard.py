"""Named trace counters + the ``trace_guard`` context manager.

The engine's one-compile contracts ("a whole parameter grid costs exactly
one step trace", "a Study builds its lane batch from one init trace")
used to be enforced through ad-hoc module-level mutable lists
(``engine.STEP_TRACE_COUNT``, ``state.INIT_TRACE_COUNT``) that every test
snapshotted by hand.  This module replaces them with one mechanism:

* :func:`counter` returns a process-global named :class:`TraceCounter`;
  the *traced* code path calls ``.hit()`` once per trace (the call sits
  inside the traced function body, so it runs at trace time only — a
  compiled execution never re-enters Python).
* :class:`trace_guard` is a context manager that snapshots a counter on
  entry and exposes the delta as ``.count``; with ``expect=`` it raises
  ``AssertionError`` on exit when the block traced a different number of
  times::

      with trace_guard("engine.step", expect=1):
          study.run()            # the whole grid must cost ONE step trace

The jaxpr auditor (``repro.analysis.audit``) uses the same guard to
machine-check the retrace contract: folding any ``api.CFG_KEYS`` sweep
point into a config must reuse the compiled step.

This module is dependency-free (no jax, no netsim imports) so the engine
can import it without cycles.
"""

from __future__ import annotations


class TraceCounter:
    """A process-global named counter; ``hit()`` from inside the traced
    function body counts traces, not executions."""

    __slots__ = ("name", "count")

    def __init__(self, name: str):
        self.name = name
        self.count = 0

    def hit(self) -> None:
        self.count += 1

    def __repr__(self) -> str:
        return f"TraceCounter({self.name!r}, count={self.count})"


_COUNTERS: dict[str, TraceCounter] = {}


def counter(name: str) -> TraceCounter:
    """Get-or-create the global counter ``name`` (e.g. ``"engine.step"``)."""
    c = _COUNTERS.get(name)
    if c is None:
        c = _COUNTERS[name] = TraceCounter(name)
    return c


class trace_guard:
    """Snapshot counter ``name`` for a ``with`` block.

    ``.count`` is the number of traces since entry; ``expect=`` turns the
    guard into an assertion (checked on clean exit only — an exception
    inside the block propagates untouched)::

        with trace_guard("engine.step") as g:
            sweep.run()
        assert g.count == 1           # or: trace_guard(..., expect=1)
    """

    def __init__(self, name: str, expect: int | None = None):
        self._counter = counter(name)
        self._start = self._counter.count
        self.expect = expect

    def __enter__(self) -> "trace_guard":
        self._start = self._counter.count
        return self

    @property
    def count(self) -> int:
        return self._counter.count - self._start

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None and self.expect is not None \
                and self.count != self.expect:
            raise AssertionError(
                f"trace_guard({self._counter.name!r}): expected "
                f"{self.expect} trace(s) inside the block, saw {self.count}")
        return False
