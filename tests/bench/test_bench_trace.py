"""The reduction from a profiler trace to the device numbers: busy time
as the union of the intervals of the operations that do work (the run
loop's enclosing events left out), the idle share, the top operations
and the idle time labelled by the benchmark's host spans."""

import json
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).resolve().parents[2] / "bench" / "data"


def test_union_and_gaps():
    busy, gaps = trace_reduce.union_length(
        [(5, 10), (8, 12), (20, 25), (30, 50)], 0, 40)
    assert busy == 7 + 5 + 10
    assert gaps == [(0, 5), (12, 20), (25, 30)]


def test_self_times_split_nested_operations():
    evs = [("while.1", 0, 100), ("fusion.2", 10, 30), ("fusion.3", 40, 50),
           ("cond.4", 60, 90), ("fusion.2", 65, 70)]
    assert trace_reduce.self_times(evs) == {
        "while.1": 100 - 20 - 10 - 30, "fusion.2": 25, "fusion.3": 10,
        "cond.4": 25}


def test_op_name():
    assert trace_reduce.op_name("%fusion.12 = f32[8]{0} fusion(%p)") == \
        "fusion.12"


def test_reduce_synthetic():
    ev = {"spans": [("bench.slice", 0, 100), ("bench.slice", 100, 200),
                    ("bench.wait", 10, 90), ("bench.device_get", 90, 120),
                    ("bench.init_dispatch", 120, 130)],
          "ops": [(0, "fusion.1", 10, 60), (0, "while.2", 60, 90),
                  (0, "fusion.5", 65, 80), (0, "fusion.1", 130, 190),
                  (1, "fusion.1", 10, 190), (0, "copy.3", 300, 400)]}
    r = trace_reduce.reduce(ev)
    assert r["window_s"] == pytest.approx(200e-9)
    # device 0: 10..60, 65..80 and 130..190 = 125 (the while encloses
    # 60..90 but works only where fusion.5 runs); device 1: 10..190 = 180
    assert r["busy_s"] == pytest.approx((125 + 180) / 2 * 1e-9)
    assert r["top_ops"][0][0] == "fusion.1"
    assert r["top_ops"][0][1] == pytest.approx((50 + 60 + 180) / 2 * 1e-9)
    ops = dict(r["top_ops"])
    assert "while.2" not in ops and "copy.3" not in ops
    assert ops["fusion.5"] == pytest.approx(15 / 2 * 1e-9)
    idle = dict(r["idle_gaps"])
    # device 0: 60..65 in the loop while the host waits, 80..130 from the
    # loop's end into device_get; both devices 0..10 and 190..200, outside
    # the host's spans
    assert idle["wait.in_loop"] == pytest.approx(5 / 2 * 1e-9)
    assert idle["device_get"] == pytest.approx(50 / 2 * 1e-9)
    assert idle["outside_spans"] == pytest.approx(40 / 2 * 1e-9)
    assert r["idle_detail"]["wait.in_loop"]["gaps"] == 1
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["ops_per_slice"] == [3, 1]


def test_reduce_finds_nothing_without_window_or_ops():
    assert trace_reduce.reduce({"spans": [], "ops": [(0, "f", 0, 1)]}) is None
    assert trace_reduce.reduce(
        {"spans": [("bench.slice", 0, 10)], "ops": []}) is None


@pytest.mark.parametrize("path", sorted(DATA.glob("trace_*.json")),
                         ids=lambda p: p.stem)
def test_recorded_trace(path):
    rec = json.loads(path.read_text())
    events = {"ops": [(d, rec["names"][i], s, e) for d, i, s, e in rec["ops"]],
              "spans": [tuple(x) for x in rec["spans"]]}
    r = trace_reduce.reduce(events)
    want = rec["reduced"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert [o[0] for o in r["top_ops"]] == [o[0] for o in want["top_ops"]]
    assert [o[1] for o in r["top_ops"]] == pytest.approx(
        [o[1] for o in want["top_ops"]])
    assert [g[0] for g in r["idle_gaps"]] == [g[0] for g in want["idle_gaps"]]
    assert r["ops_per_slice"] == want["ops_per_slice"]
    # the run loops' events enclose work and gaps: busy time leaves them
    # out, so it is less than the union of every event
    lo = min(s for n, s, _ in events["spans"] if n == "bench.slice")
    hi = max(e for n, _, e in events["spans"] if n == "bench.slice")
    every, _ = trace_reduce.union_length(
        [(s, e) for _, _, s, e in events["ops"]], lo, hi)
    assert 0 < r["busy_s"] < every * 1e-9 <= r["window_s"]
    assert any(k.endswith(".in_loop") for k in r["idle_detail"])
    assert not any(trace_reduce.is_control_flow(o[0]) for o in r["top_ops"])
