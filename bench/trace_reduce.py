"""From a profiler trace to the benchmark's device numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` wrote and
keeps two kinds of events, on one clock (nanoseconds):

* device operations: every event on the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane, as ``(device, name, start, end)``;
* host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` spans
  (names starting ``bench.``) on the host plane, as ``(name, start, end)``.

``reduce`` turns those into what the result line reports: the window (the
benchmark's ``bench.slice`` spans, first start to last end), the device's
busy time, the operations that took most time, and the idle time by what
the host was doing.  Busy time is the union of the intervals of the
operations that do work: a ``while``, ``conditional`` or ``call`` event
only encloses the operations of its body, so it is left out, and a gap
between two operations inside a run loop counts as idle like a gap
between runs.  Busy time is averaged over the devices.  Each idle gap is
labelled by the innermost benchmark span open at its midpoint, with
``.in_loop`` added where it lies inside a device ``while`` event; the
idle time is summed per label.  The reduction is plain Python over those
lists, so a small recorded event list (``bench/data/``) checks it without
a chip.
"""

from __future__ import annotations

import bisect
import glob
import os

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.slice"
TOP = 10
CONTROL_FLOW = ("while", "conditional", "cond", "call")


def op_name(text: str) -> str:
    """``fusion.12`` from an event named by its whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops, spans = [], []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                dev = int(plane.name[len(DEVICE_PREFIX):].split(" ")[0])
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for e in line.events:
                        ops.append((dev, op_name(e.name), int(e.start_ns),
                                    int(e.start_ns + e.duration_ns)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans.append((e.name, int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
    return {"ops": ops, "spans": spans}


def union_length(intervals, lo: int, hi: int) -> tuple:
    """Length of the union of ``[start, end)`` intervals clipped to
    ``[lo, hi)``, and the gaps between them (``(start, end)`` pairs, the
    edges of the window included)."""
    busy, gaps = 0, []
    cur = lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return busy, gaps


def is_control_flow(name: str) -> bool:
    """``while.3``, ``cond.92``: events that enclose other operations."""
    return name.split(".")[0] in CONTROL_FLOW


def _inside(merged, t: float) -> bool:
    """``t`` lies in one of the sorted, disjoint intervals ``merged``."""
    i = bisect.bisect_right(merged, (t, float("inf"))) - 1
    return i >= 0 and merged[i][0] <= t < merged[i][1]


def self_times(evs) -> dict:
    """Exclusive device time per operation name: an event's duration less
    the part its nested events (the ops inside a ``while`` or a
    ``conditional``) cover.  ``evs`` are ``(name, start, end)``."""
    out: dict = {}
    stack = []                     # [name, start, end, covered by children]

    def close(node):
        name, s, e, kids = node
        out[name] = out.get(name, 0) + (e - s) - kids

    for name, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return out


def _label(spans, t: float) -> str:
    best = None
    for name, s, e in spans:
        if name == WINDOW_SPAN or not s <= t < e:
            continue
        if best is None or e - s < best[1]:
            best = (name[len(SPAN_PREFIX):], e - s)
    return best[0] if best else "outside_spans"


def reduce(events: dict) -> dict | None:
    """Busy and window seconds, top operations, idle time by host span,
    and the operations counted in each traced iteration (a profiler that
    drops events shows as a count that falls from one iteration to the
    next).  ``None`` where the trace holds no window span or no device
    operation in it (a reader then reports nothing)."""
    slices = sorted((s, e) for n, s, e in events["spans"] if n == WINDOW_SPAN)
    if not slices:
        return None
    lo, hi = slices[0][0], max(e for _, e in slices)
    by_dev: dict = {}
    for dev, name, s, e in events["ops"]:
        by_dev.setdefault(dev, []).append((name, s, e))
    busy_total, idle, per_op = 0, {}, {}
    per_slice = [0] * len(slices)
    for dev, evs in sorted(by_dev.items()):
        work = [(s, e) for n, s, e in evs if not is_control_flow(n)]
        loops = []
        for s, e in sorted((s, e) for n, s, e in evs
                           if n.split(".")[0] == "while"):
            if loops and s <= loops[-1][1]:
                loops[-1] = (loops[-1][0], max(loops[-1][1], e))
            else:
                loops.append((s, e))
        busy, gaps = union_length(work, lo, hi)
        busy_total += busy
        for s, e in gaps:
            mid = (s + e) / 2
            label = _label(events["spans"], mid)
            if _inside(loops, mid):
                label += ".in_loop"
            n, total, longest = idle.get(label, (0, 0, 0))
            idle[label] = (n + 1, total + e - s, max(longest, e - s))
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if min(e, hi) > max(s, lo) and not is_control_flow(n)]
        for name, d in self_times(inside).items():
            per_op[name] = per_op.get(name, 0) + d
        for s, _ in work:
            i = bisect.bisect_right(slices, (s, float("inf"))) - 1
            if i >= 0 and s < slices[i][1]:
                per_slice[i] += 1
    if busy_total == 0:
        return None
    n = len(by_dev)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    by_idle = sorted(idle.items(), key=lambda kv: -kv[1][1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / n * 1e-9,
        "devices": n,
        "top_ops": [[name, d / n * 1e-9] for name, d in top],
        "idle_gaps": [[label, t / n * 1e-9] for label, (_, t, _) in
                      by_idle[:TOP]],
        "idle_detail": {label: {"gaps": g, "longest_s": m * 1e-9}
                        for label, (g, _, m) in by_idle},
        "ops_per_slice": per_slice,
    }
