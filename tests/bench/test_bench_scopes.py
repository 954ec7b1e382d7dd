"""The split of the traced slice by the program's scopes
(``bench/scope_reduce.py``): instruction scopes from HLO text, busy time
by scope that sums to the busy time, idle gaps labelled by the program's
spans and by the scope of the operation after them, the readers of the
new metrics and their silences, and the measurement on a small cell."""

import json
from pathlib import Path

import jax
import pytest

import harness
import scope_reduce
import trace_reduce

DATA = Path(__file__).resolve().parents[2] / "bench" / "data"
SCOPED = sorted(DATA.glob("trace_*_scoped.json"))
SMALL = {"racks": 4, "nodes_per_rack": 4, "uplinks": 2, "pods": 2,
         "core_uplinks": 1}

HLO = """\
HloModule jit__run_until_done

%fused_computation.1 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %c = s32[] constant(0), metadata={op_name="jit(f)/while/body"}
  %b = s32[8]{0} broadcast(%c), metadata={op_name="jit(f)/while/body/cond/branch_1_fun/arrivals/broadcast_in_dim"}
  ROOT %add.1 = s32[8]{0} add(%param_0, %b), metadata={op_name="jit(f)/while/body/cond/branch_1_fun/arrivals/add"}
}

%region_0.2 (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %add.9 = s32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused_computation.2 (param_0.1: s32[8]) -> s32[] {
  %param_0.1 = s32[8]{0} parameter(0)
  %z = s32[] constant(0)
  ROOT %reduce.3 = s32[] reduce(%param_0.1, %z), dimensions={0}, to_apply=%region_0.2
}

ENTRY %main.5 (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/closed_call"}
  %fusion.2 = s32[] fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %gather.4 = s32[8]{0} gather(%fusion.1, %p), metadata={op_name="jit(f)/while/body/departures/gather"}
  %lt.5 = pred[] compare(%fusion.2, %fusion.2), direction=LT, metadata={op_name="jit(f)/while/body/loop_ctl/lt"}
  ROOT %copy.6 = s32[8]{0} copy(%gather.4)
}
"""


def test_scope_of_takes_the_innermost_scope():
    name = "jit(_run_until_done)/while/body/leap/jit(f)/departures/gather"
    assert scope_reduce.scope_of(name) == "departures"
    assert scope_reduce.scope_of("jit(f)/while/body/closed_call") is None
    assert scope_reduce.scope_of("reduce_sum") is None


def test_hlo_scopes_attribute_fusions_by_their_instructions():
    got = scope_reduce.hlo_scopes(HLO)
    assert got["fusion.1"] == "arrivals"
    assert got["gather.4"] == "departures"
    assert got["lt.5"] == "loop_ctl"
    # no instruction inside names a scope, nor the copy XLA put in
    assert "fusion.2" not in got and "copy.6" not in got


def _events():
    """Two slices; a run-loop module on device 0 from 10 to 90 and from
    110 to 190; the host waits in both, and the program dispatches."""
    return {
        "spans": [("bench.slice", 0, 100), ("bench.slice", 100, 200),
                  ("bench.wait", 5, 95), ("netsim.init_state", 0, 5),
                  ("bench.wait", 105, 195)],
        "modules": [(0, "jit__run_until_done", 10, 90),
                    (0, "jit__run_until_done", 110, 190),
                    (0, "jit_broadcast_in_dim", 2, 4)],
        "ops": [(0, "broadcast.1", 2, 4),
                (0, "while.1", 10, 90),
                (0, "fusion.1", 10, 20), (0, "fusion.2", 25, 40),
                (0, "fusion.3", 40, 50), (0, "fusion.1", 60, 70),
                (0, "fusion.2", 75, 80), (0, "copy.9", 82, 88),
                (0, "fusion.1", 110, 130), (0, "fusion.2", 150, 160)]}


SCOPES = {"fusion.1": "departures", "fusion.2": "arrivals",
          "fusion.3": "leap"}


def test_split_sums_to_the_busy_time_and_labels_gaps():
    ev = _events()
    out = scope_reduce.split(ev, SCOPES)
    r = trace_reduce.reduce({"ops": ev["ops"], "spans": ev["spans"]})
    assert sum(out["busy_s"].values()) == pytest.approx(r["busy_s"])
    busy = {k: v * 1e9 for k, v in out["busy_s"].items()}
    assert busy == pytest.approx({"departures": 10 + 10 + 20,
                                  "arrivals": 15 + 5 + 10, "leap": 10,
                                  "unscoped": 6, "outside_loop": 2})
    idle = {k: v * 1e9 for k, v in out["idle_s"].items()}
    assert idle == pytest.approx({
        "netsim.init_state": 2,                  # 0..2
        "wait": 6,                               # 4..10, before the loop
        "wait.in_loop.arrivals": 5 + 5 + 20,     # 20..25, 70..75, 130..150
        "wait.in_loop.departures": 10,           # 50..60
        "wait.in_loop": 2 + 40,                  # before the copy; 160..200
        "outside_spans": 22})                    # 88..110
    assert out["loop_idle_s"] * 1e9 == pytest.approx(30 + 10 + 42)
    assert sum(out["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # departures in the first slice: fusion.1 twice
    assert out["departures_events"] == 2


def test_split_without_modules_takes_the_while_events():
    ev = _events()
    ev["modules"] = []
    out = scope_reduce.split(ev, SCOPES)
    # the second run has no while event: its operations are outside
    assert out["busy_s"]["departures"] * 1e9 == pytest.approx(20)
    assert scope_reduce.split({"spans": [], "ops": ev["ops"]}, {}) is None


def _rec(scopes=None, counters=None):
    cell = harness.load_cell("perm1024.run")
    its = [dict(ticks=[100], done=[True])]
    rec = harness.Record(cell=cell, setup_s=1.0, window_s=1.0,
                         iterations=its, spans=[],
                         trace={"busy_s": 0.5, "window_s": 1.0},
                         trace_iterations=its)
    rec.scopes, rec.counters = scopes, counters
    return rec


def _split(coverage=1.0):
    return {"ticks": 200, "coverage": coverage, "loop_idle_s": 4e-4,
            "busy_s": {"departures": 2e-4, "arrivals": 6e-4,
                       "control": 3e-4, "sends": 1e-4, "metrics": 1e-5,
                       "leap": 2e-5, "loop_ctl": 4e-5, "unscoped": 1e-5,
                       "outside_loop": 3e-5}}


READS = {"departures_us_per_tick": 1.0, "arrivals_us_per_tick": 3.0,
         "control_us_per_tick": 1.5, "grants_us_per_tick": 0.0,
         "sends_us_per_tick": 0.5, "metrics_us_per_tick": 0.05,
         "loop_ctl_us_per_tick": 0.3, "loop_idle_us_per_tick": 2.0}


def _read(name, rec):
    return harness._load_module("metrics", name).read(rec)


@pytest.mark.parametrize("name", sorted(READS))
def test_scoped_readers(name):
    rec = _rec(_split(), {"ticks_executed": 30, "now": 120})
    assert _read(name, rec) == pytest.approx(READS[name])
    for silent in (_rec(_split(coverage=0.98)), _rec(_split(None)),
                   _rec(None)):
        assert _read(name, silent) is None
    unscoped = _split()
    unscoped["busy_s"] = {"unscoped": 1e-3, "outside_loop": 1e-4}
    assert _read(name, _rec(unscoped)) is None


def test_exec_tick_share_reader():
    counted = {"ticks_executed": 30, "ticks_leapt": 90, "now": 120}
    assert _read("exec_tick_share", _rec(None, counted)) == 25.0
    assert _read("exec_tick_share", _rec(_split(), None)) is None


def test_nothing_measured_without_a_trace_or_counters(monkeypatch):
    rec = _rec()
    del rec.scopes, rec.counters
    rec.trace = None
    assert scope_reduce.measured(rec) == (None, None)
    rec = _rec()
    del rec.scopes, rec.counters
    monkeypatch.setattr(scope_reduce, "_counts_loop", lambda: False)
    assert scope_reduce.measured(rec) == (None, None)


def test_measure_repeats_the_slice_and_counts(monkeypatch):
    """On the CPU, with no device events: the repeated slice runs the
    slice's salts (the same ticks) and the counted run of the first salt
    is read; the scoped split finds nothing."""
    cell = harness.load_cell("perm1024.run")
    cell.config["tree"] = dict(SMALL)
    cell.config["flows"]["size_bytes"] = 64 * 1024
    seed = 2**33 + 5
    devices = jax.devices()[:1]
    mix = harness.RunsMix(cell, seed, harness.Spans(), devices)
    mix.iteration(keep=False)
    window = [mix.iteration(keep=False)]
    sliced = [mix.iteration(keep=False) for _ in range(2)]
    rec = harness.Record(cell=cell, setup_s=1.0, window_s=1.0,
                         iterations=window, spans=[],
                         trace={"busy_s": 0.5, "window_s": 1.0},
                         trace_iterations=sliced)
    assert scope_reduce.measured(rec)[0] is None
    c = rec.counters
    assert c["now"] == sliced[0]["ticks"][0]
    assert c["ticks_executed"] + c["ticks_leapt"] == c["now"]
    assert _read("exec_tick_share", rec) == pytest.approx(
        100.0 * c["ticks_executed"] / c["now"])


def _load(path):
    rec = json.loads(path.read_text())
    names = rec["names"]
    ops = [(d, names[i], s, e) for d, i, s, e in rec["ops"]]
    spans = [tuple(x) for x in rec["spans"] + rec["program_spans"]]
    return rec, {"ops": ops, "spans": spans,
                 "modules": [tuple(m) for m in rec["modules"]]}


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.stem)
def test_recorded_scoped_trace(path):
    rec, events = _load(path)
    out = scope_reduce.split(events, rec["scopes"])
    want = rec["scoped"]
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert out["idle_s"] == pytest.approx(want["idle_s"])
    assert out["departures_events"] == want["departures_events"]
    # the parts sum to the busy time of the harness's rule, and busy and
    # window are what that rule stored
    r = trace_reduce.reduce({"ops": events["ops"],
                             "spans": [tuple(x) for x in rec["spans"]]})
    assert sum(out["busy_s"].values()) == pytest.approx(r["busy_s"])
    assert r["busy_s"] == pytest.approx(rec["reduced"]["busy_s"])
    assert r["window_s"] == pytest.approx(rec["reduced"]["window_s"])
    assert set(out["busy_s"]) - {"unscoped", "outside_loop"} <= \
        set(scope_reduce.SCOPES)
    assert {"departures", "arrivals", "control"} <= set(out["busy_s"])
    labels = set(out["idle_s"])
    assert any(k.startswith("netsim.") for k in labels)
    assert any(".in_loop." in k for k in labels)
    # the trace of a tiny run holds every tick the loop executed
    assert out["departures_events"] == rec["counters"]["ticks_executed"]
