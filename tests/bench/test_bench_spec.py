"""BENCHMARK.json against the benchmark's contract, and every file the
harness finds by name in it."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    assert len(SPEC["command"]) <= 32


def test_names_and_units():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for group in (SPEC["configs"], SPEC["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in SPEC["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])


def test_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_moves_name_a_metric_every_listed_cell_reports():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for cell in cells:
        assert any(cell in m["workloads"] for m in SPEC["per_layer"])


def test_files_exist():
    for c in SPEC["configs"]:
        path = ROOT / c["file"]
        assert path.is_file() and path.parts[len(ROOT.parts)] == "bench"
        cfg = json.loads(path.read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / "bench" / "flows"
                / f"{cfg['flows']['generator']}.py").is_file()
    configs = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in configs
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == configs
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_at_most_half_the_cells_ask_for_four_chips():
    chips = [w["chips"] for w in SPEC["workloads"]]
    assert set(chips) <= {1, 4}
    assert sum(c == 4 for c in chips) <= max(1, len(chips) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_load_cell(cell):
    import harness
    c = harness.load_cell(cell)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer


def test_metric_readers():
    import harness
    cell = harness.load_cell("perm1024.run")
    its = [dict(ticks=[100, 80], done=[True, True]),
           dict(ticks=[90, 90], done=[True, True])]
    rec = harness.Record(
        cell=cell, setup_s=12.5, window_s=2.0, iterations=its,
        spans=[("init_dispatch", 0.0, 0.01), ("wait", 0.01, 0.5),
               ("device_get", 0.5, 0.52), ("result", 0.52, 0.53),
               ("init_dispatch", 0.53, 0.54)],
        memory_peak_bytes=3_000_000,
        trace={"busy_s": 0.9, "window_s": 1.2}, trace_iterations=its[:1])
    read = lambda name: harness._load_module("metrics", name).read(rec)
    assert read("sim_ticks_per_s") == 360 / 2.0
    assert read("setup_s") == 12.5
    assert abs(read("host_ms_per_run") - (0.01 + 0.02 + 0.01 + 0.01) / 2
               * 1e3) < 1e-9
    assert read("device_us_per_tick") == 0.9e6 / 180
    assert abs(read("device_idle_share") - 25.0) < 1e-9
    assert read("peak_hbm_mb") == 3.0
    rec.trace, rec.spans = None, []
    for name in ("device_us_per_tick", "device_idle_share",
                 "host_ms_per_run"):
        assert read(name) is None
