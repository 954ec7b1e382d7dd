"""The traced slice split by the program's own names: device time per tick
phase, idle time inside the run loop, and the run loop's counters.

The program names the six phases of a tick (``jax.named_scope``:
``departures``, ``arrivals``, ``control``, ``grants``, ``sends``,
``metrics``) and the run loop's own work (``leap``, the horizon and its
accounting; ``loop_ctl``, the exit predicate and the per-tick gate); its
host spans are ``netsim.*`` ``TraceAnnotation``s; ``Sim.run(...,
counters=True)`` returns the loop's ``LoopCounters`` beside the state.
The readers in ``bench/metrics/`` that read these call :func:`measured`.

:func:`measured` traces the slice's first run again, the same seed's flow
table and salt through the same calls, for its first ``TRACE_TICKS``
ticks: the profiler keeps about 6 million device events of a session, and
the harness's slice of two whole runs at 1024 hosts holds some 26
million, so its later events are lost.  The same salt then runs untraced
with counters, to those ticks (for the coverage) and to its end (for
``exec_tick_share``), outside the window.  (The harness keeps neither
the slice's events nor the seed; the seed is read from the ``run_cell``
frame that asks for the metrics.)  It needs a program whose ``Sim.run``
takes ``counters``; without one every reader finds nothing.

Each device operation of the run-loop module (the ``XLA Modules`` events
whose name holds ``run_until_done``; without them, the ``while`` events)
is given the scope in its ``op_name``, read from the compiled loop's HLO
text (a v5e trace's events carry no ``op_name``); a fusion whose own
``op_name`` names no scope takes the scope most of its fused
instructions name.  Busy time is the union of the operations that do
work, as in ``trace_reduce``; each instant of it goes to the operation
that starts it, so the parts sum to the busy time.  An idle gap is
labelled by the innermost ``bench.*`` or ``netsim.*`` span open at its
midpoint; a gap inside the run-loop module adds ``.in_loop`` and the
scope of the operation after it (``wait.in_loop.arrivals``).

Trace coverage: the events the trace holds of one ``departures`` operation
(the count most of the phase's operations share: each runs once per
stepped tick) over the ``ticks_executed`` of the traced ticks.
Under 99 % the scoped readers report nothing: a trace that dropped
events must not turn into phase numbers.
"""

from __future__ import annotations

import collections
import glob
import inspect
import os
import re
import sys
import tempfile
import time

import trace_reduce

PHASES = ("departures", "arrivals", "control", "grants", "sends", "metrics")
LOOP_SCOPES = ("leap", "loop_ctl")
SCOPES = PHASES + LOOP_SCOPES
LOOP_MODULE = "run_until_done"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "netsim.")
MIN_COVERAGE = 0.99
# ticks of the scoped trace: about 320 device events each at 1024 hosts,
# well inside what the profiler keeps of one session (some 6 million
# events on a v5e: it dropped every later event of a longer run)
TRACE_TICKS = 5000


def scope_of(op_name: str) -> str | None:
    """The innermost scope named in an ``op_name`` path."""
    for part in reversed(re.split(r"[/\"= ]", op_name)):
        if part in SCOPES:
            return part
    return None


# --------------------------------------------------------------------------
# instruction -> scope, from the compiled module's HLO text
# --------------------------------------------------------------------------

_COMP = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,)}]+)")


def hlo_scopes(text: str) -> dict:
    """``{instruction: scope}`` for every instruction of a compiled HLO
    module whose ``op_name``, or (for a fusion) whose fused instructions'
    ``op_name``s, name a scope."""
    comps: dict = {}            # computation -> [(instr, scope, calls)]
    cur = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            name, rest = m.groups()
            on = _OP_NAME.search(rest)
            cur.append((name, scope_of(on.group(1)) if on else None,
                        _CALLS.findall(rest)))

    def votes(comp, seen):
        c = collections.Counter()
        for _, scope, calls in comps.get(comp, ()):
            if scope:
                c[scope] += 1
            for callee in calls:
                if callee not in seen:
                    seen.add(callee)
                    c.update(votes(callee, seen))
        return c

    out = {}
    for instrs in comps.values():
        for name, scope, calls in instrs:
            if scope is None and calls:
                c = collections.Counter()
                for callee in calls:
                    c.update(votes(callee, {callee}))
                scope = c.most_common(1)[0][0] if c else None
            if scope:
                out[name] = scope
    return out


# --------------------------------------------------------------------------
# trace -> events
# --------------------------------------------------------------------------


def extract(trace_dir: str) -> dict:
    """``trace_reduce.extract``'s operations and ``bench.`` spans, with
    the ``netsim.`` spans and the ``XLA Modules`` events as ``(device,
    name, start, end)``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops, spans, modules = [], [], []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
                dev = int(plane.name[len(trace_reduce.DEVICE_PREFIX):]
                          .split(" ")[0])
                for ln in plane.lines:
                    if ln.name == MODULES_LINE:
                        modules.extend(
                            (dev, e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in ln.events)
                    elif ln.name == trace_reduce.OPS_LINE:
                        ops.extend(
                            (dev, trace_reduce.op_name(e.name),
                             int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in ln.events)
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    for e in ln.events:
                        if e.name.startswith(SPAN_PREFIXES):
                            spans.append((e.name, int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
    return {"ops": ops, "spans": spans, "modules": modules}


def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def inside(merged, t) -> bool:
    return bool(merged) and trace_reduce._inside(merged, t)


# --------------------------------------------------------------------------
# events -> the split
# --------------------------------------------------------------------------


def _label(spans, t) -> str:
    best = None
    for name, s, e in spans:
        if name == trace_reduce.WINDOW_SPAN or not s <= t < e:
            continue
        if best is None or e - s < best[1]:
            best = (name, e - s)
    if best is None:
        return "outside_spans"
    name = best[0]
    return name[len("bench."):] if name.startswith("bench.") else name


def split(events: dict, scopes: dict) -> dict | None:
    """Busy seconds by scope (``unscoped``: a run-loop operation with no
    scope; ``outside_loop``: any other operation), idle seconds inside
    the run loop in total and by label, and the events of each
    ``departures`` operation in the first ``bench.slice`` span; averaged
    over the devices.  ``scopes`` maps run-loop operation names to
    scopes.  ``None`` without a window span or a device operation."""
    slices = sorted((s, e) for n, s, e in events["spans"]
                    if n == trace_reduce.WINDOW_SPAN)
    if not slices or not events["ops"]:
        return None
    lo, hi = slices[0][0], max(e for _, e in slices)
    by_dev: dict = {}
    for dev, name, s, e in events["ops"]:
        by_dev.setdefault(dev, []).append((s, e, name))
    mods: dict = {}
    for dev, name, s, e in events.get("modules", ()):
        if LOOP_MODULE in name:
            mods.setdefault(dev, []).append((s, e))
    busy = collections.Counter()
    idle = collections.Counter()
    loop_idle = 0
    first_run = collections.Counter()
    dev0 = min(by_dev)
    for dev, evs in by_dev.items():
        loops = merge(mods.get(dev) or
                      [(s, e) for s, e, n in evs
                       if n.split(".")[0] == "while"])

        def gap(s, e, key):
            nonlocal loop_idle
            mid = (s + e) / 2
            label = _label(events["spans"], mid)
            if inside(loops, mid):
                label += ".in_loop"
                loop_idle += e - s
                if key in SCOPES:
                    label += "." + key
            idle[label] += e - s

        cur = lo
        for s, e, name in sorted(evs):
            if trace_reduce.is_control_flow(name):
                continue
            key = (scopes.get(name, "unscoped") if inside(loops, s)
                   else "outside_loop")
            if dev == dev0 and key == "departures" and \
                    slices[0][0] <= s < slices[0][1]:
                first_run[name] += 1
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            if s > cur:
                gap(cur, s, key)
            if e > cur:
                busy[key] += e - max(s, cur)
                cur = e
        if hi > cur:
            gap(cur, hi, None)
    n = len(by_dev)
    counts = collections.Counter(first_run.values())
    seen = max(counts.items(), key=lambda kv: (kv[1], kv[0]))[0] \
        if counts else 0
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": {k: v / n * 1e-9 for k, v in busy.items()},
            "loop_idle_s": loop_idle / n * 1e-9,
            "idle_s": {k: v / n * 1e-9 for k, v in
                       sorted(idle.items(), key=lambda kv: -kv[1])},
            "departures_events": seen}


# --------------------------------------------------------------------------
# the measurement the readers share
# --------------------------------------------------------------------------


def measured(rec):
    """``(rec.scopes, rec.counters)``, measured on first use (see the
    module's docstring); ``(None, None)`` where there is nothing to
    measure."""
    if not hasattr(rec, "scopes"):
        rec.scopes, rec.counters = measure(rec)
    return rec.scopes, rec.counters


def _counts_loop() -> bool:
    from repro.netsim import engine
    return "counters" in inspect.signature(engine.Sim.run).parameters


def _run_cell_locals(rec) -> dict | None:
    f = sys._getframe(1)
    while f is not None:
        if f.f_locals.get("rec") is rec and "seed" in f.f_locals:
            return f.f_locals
        f = f.f_back
    return None


def _log(msg: str) -> None:
    print(f"bench: scopes: {msg}", file=sys.stderr, flush=True)


def measure(rec):
    """The scoped split of the slice's first salt, traced for its first
    ``TRACE_TICKS`` ticks, and the counters of that salt's whole run."""
    if rec.trace is None or not rec.trace_iterations or not _counts_loop():
        return None, None
    frame = _run_cell_locals(rec)
    if frame is None:
        return None, None
    import jax

    import harness
    from repro.netsim import engine
    seed = frame["seed"]
    mix = harness.RunsMix(rec.cell, seed, harness.Spans(),
                          frame.get("devices"))
    first = 1 + len(rec.iterations)      # warm-up, then the window's runs
    salt = harness.salts(seed, "runs", first + 1)[first]
    sim, full = mix.sim, mix.max_ticks
    mix.max_ticks = min(TRACE_TICKS, full)
    t0 = time.perf_counter()
    text = engine._run_until_done.lower(
        sim.step_fn, sim.horizon_fn if sim.dims.leap else None, sim.consts,
        jax.eval_shape(sim.init), mix.max_ticks,
        sim.dims.superstep).compile().as_text()
    scopes = hlo_scopes(text)
    _log(f"compiled loop's HLO read in {time.perf_counter() - t0:.1f} s: "
         f"{len(scopes)} instructions scoped")
    part = _counted(sim, mix.max_ticks, salt)
    jax.block_until_ready(sim.run(mix.max_ticks, seed=salt))   # compiled
    mix.salts = iter([salt])
    events, its = _traced(mix)
    out = split(events, scopes)
    counters = _counted(sim, full, salt)
    if counters["now"] != rec.trace_iterations[0]["ticks"][0]:
        _log(f"salt {salt} ran {counters['now']} ticks, the slice's first "
             f"run {rec.trace_iterations[0]['ticks'][0]}")
        return None, None
    if out is None:
        return None, counters
    out["ticks"] = its[0]["ticks"][0]
    out["ticks_executed"] = part["ticks_executed"]
    out["coverage"] = (out["departures_events"] / part["ticks_executed"]
                       if part["ticks_executed"] else None)
    _report(rec, out, events)
    return out, counters


def _traced(mix, n: int = 1):
    """``n`` iterations of ``mix`` under the profiler, as the harness's
    traced slice runs them, and the events :func:`extract` reads."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    its = []
    with tempfile.TemporaryDirectory(prefix="bench_scopes_") as d:
        t0 = time.perf_counter()
        mix.spans.tracing = True
        try:
            with jax.profiler.trace(d, profiler_options=opts):
                for _ in range(n):
                    with jax.profiler.TraceAnnotation(
                            trace_reduce.WINDOW_SPAN):
                        its.append(mix.iteration(keep=False))
                t1 = time.perf_counter()
        finally:
            mix.spans.tracing = False
        t2 = time.perf_counter()
        events = extract(d)
        _log(f"traced {[it['ticks'][0] for it in its]} ticks in "
             f"{t1 - t0:.1f} s; the profiler stopped in {t2 - t1:.1f} s; "
             f"{len(events['ops'])} device events read in "
             f"{time.perf_counter() - t2:.1f} s")
    return events, its


def _counted(sim, max_ticks: int, salt: int) -> dict:
    """A run of ``salt`` to ``max_ticks``, untraced, with counters."""
    import jax
    t0 = time.perf_counter()
    st, c = sim.run(max_ticks, seed=salt, counters=True)
    got = {k: int(v) for k, v in jax.device_get(c)._asdict().items()}
    got["now"] = int(st.now)
    _log(f"counted run to {max_ticks} ticks in "
         f"{time.perf_counter() - t0:.1f} s with its compile: supersteps "
         f"{got['supersteps']}, leaps {got['leaps']}, ticks_leapt "
         f"{got['ticks_leapt']}, ticks_executed {got['ticks_executed']} "
         f"of {got['now']}")
    return got


def _report(rec, out, events) -> None:
    ticks = out["ticks"]
    per = {k: v * 1e6 / ticks for k, v in out["busy_s"].items()}
    scoped = sum(per.get(k, 0.0) for k in SCOPES)
    rest = sum(v for k, v in per.items() if k not in SCOPES)
    cov = out["coverage"]
    _log(f"trace coverage {cov if cov is None else round(cov, 6)} "
         f"({out['departures_events']} departures events, "
         f"{out['ticks_executed']} ticks executed)")
    _log(f"busy us per tick by scope "
         f"{ {k: round(v, 3) for k, v in sorted(per.items())} }: scoped "
         f"{scoped:.3f} + unscoped remainder {rest:.3f} = "
         f"{scoped + rest:.3f}")
    _log(f"in-loop idle {out['loop_idle_s'] * 1e6 / ticks:.3f} us per tick; "
         f"idle by label (s) "
         f"{ {k: round(v, 6) for k, v in out['idle_s'].items()} }")
    slice_ticks = sum(sum(it["ticks"]) for it in rec.trace_iterations)
    kept = sum(rec.trace.get("ops_per_slice", [])) / slice_ticks
    whole = len(events["ops"]) / ticks
    _log(f"the harness's slice kept {kept:.1f} device events per tick, a "
         f"whole trace holds {whole:.1f}: {100 * kept / whole:.1f} %; its "
         f"{rec.trace['busy_s'] * 1e6 / slice_ticks:.3f} us per tick against "
         f"{scoped + rest:.3f} here")
    _log(f"wall us per tick: window "
         f"{rec.window_s / max(rec.sim_ticks, 1) * 1e6:.3f}, the harness's "
         f"traced slice {rec.trace['window_s'] * 1e6 / slice_ticks:.3f}, "
         f"this trace {out['window_s'] * 1e6 / ticks:.3f}")


# --------------------------------------------------------------------------
# what the readers report
# --------------------------------------------------------------------------


def _covered(rec) -> dict | None:
    """The split, where the program names scopes and the trace holds at
    least ``MIN_COVERAGE`` of the ticks the run loop executed."""
    out, _ = measured(rec)
    if out is None or not any(k in SCOPES for k in out["busy_s"]):
        return None
    if out["coverage"] is None or out["coverage"] < MIN_COVERAGE:
        return None
    return out


def us_per_tick(rec, keys) -> float | None:
    """Busy microseconds per simulated tick of the scopes ``keys``."""
    out = _covered(rec)
    if out is None:
        return None
    return sum(out["busy_s"].get(k, 0.0) for k in keys) * 1e6 / out["ticks"]


def loop_idle_us_per_tick(rec) -> float | None:
    """Idle microseconds per simulated tick inside the run-loop module."""
    out = _covered(rec)
    return None if out is None else out["loop_idle_s"] * 1e6 / out["ticks"]
