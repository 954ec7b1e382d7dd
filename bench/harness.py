"""The benchmark harness: one cell, one seed, one measured window.

Everything here is general.  What belongs to one configuration, one
traffic mix or one metric is a file of its own, found by its name in
``BENCHMARK.json``:

    bench/configs/<config>.json      fabric, link, algorithm, flow table
    bench/flows/<generator>.py       the flow-table generator a config names
    bench/traffic/<traffic>.json     how the cell drives the program
    bench/metrics/<metric>.py        ``read(rec) -> float | None``

A run: set-up builds the program's simulator once and warms it with one
whole iteration; the window then repeats whole iterations back to back,
closed loop, until ``--seconds`` have passed, and finishes the one in
flight.  With ``--trace 1`` two more iterations
run under the profiler after the window.  Then the program's state is
dropped, the reference re-runs a sample of what the window produced,
drawn from the seed, and the result line is printed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


# --------------------------------------------------------------------------
# the cell, as BENCHMARK.json and its files state it
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict             # bench/configs/<config>.json
    traffic: dict            # bench/traffic/<traffic>.json
    end_to_end: list         # metric entries of BENCHMARK.json
    per_layer: list


def _reported_in(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names or \
        metric["name"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _reported_in(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def _load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no {kind} file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def flow_table(config: dict, seed: int) -> dict:
    """The configuration's flow table, from its generator file."""
    gen = config["flows"]
    return _load_module("flows", gen["generator"]).generate(
        config["tree"], gen, seed)


# --------------------------------------------------------------------------
# seeds
# --------------------------------------------------------------------------


def rng_for(seed: int, purpose: str) -> np.random.Generator:
    """An independent stream per purpose; any whole ``seed`` (negative or
    wider than 64 bits too) maps to a valid numpy seed."""
    tag = sum(ord(ch) << (8 * i) for i, ch in enumerate(purpose[:8]))
    return np.random.default_rng([int(seed) % (1 << 64), tag])


def salts(seed: int, purpose: str, n: int) -> list:
    """``n`` per-run hash salts, positive i32 as the program takes them."""
    return [int(x) for x in rng_for(seed, purpose).integers(1, 2**31 - 1, n)]


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), so that only the first
    run of a cell compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        CACHE_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def check_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0]}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:chips]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


# --------------------------------------------------------------------------
# host spans
# --------------------------------------------------------------------------


class Spans:
    """Named host spans of the benchmark's own calls into the program,
    on the host clock and, while tracing, in the profiler's trace."""

    def __init__(self):
        self.tracing = False
        self.records = []            # (name, start_s, end_s)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        ann = (jax.profiler.TraceAnnotation(f"bench.{name}") if self.tracing
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t0, time.perf_counter()))


# --------------------------------------------------------------------------
# traffic mixes: how a cell drives the program
# --------------------------------------------------------------------------


def scenario(config: dict, flows: dict, name: str):
    """The program's Scenario for this configuration, built through its
    public API from the benchmark's own flow table."""
    from repro.netsim.scenarios import Scenario
    from repro.netsim.state import SimConfig
    from repro.netsim.units import FatTreeConfig, LinkConfig
    from repro.netsim.workloads import Workload

    algo = dict(config["smartt"])
    react_every = int(algo.pop("react_every"))
    p = config["params"]
    cfg = SimConfig(
        link=LinkConfig(**config["link"]), tree=FatTreeConfig(**config["tree"]),
        algo=config["algo"], lb=config["lb"], trimming=config["trimming"],
        rto_mult=p["rto_mult"], num_entropies=p["num_entropies"],
        react_every=react_every, start_cwnd_mult=p["start_cwnd_mult"],
        kmin_frac=p["kmin_frac"], kmax_frac=p["kmax_frac"],
        cc_overrides=tuple(sorted(algo.items())))
    wl = Workload(name=name, src=flows["src"], dst=flows["dst"],
                  size=flows["size"], t_start=flows["t_start"],
                  order=flows["order"], dep_par=flows.get("dep_par"),
                  dep_thr=flows.get("dep_thr"))
    return Scenario(name=name, cfg=cfg, wl=wl,
                    max_ticks=int(config["max_ticks"]))


class RunsMix:
    """Single runs through ``Sim.run``, as ``api.run`` does after its
    build, each with a fresh salt from the seed; the host pulls the final
    state and builds the program's ``RunResult``."""

    def __init__(self, cell: Cell, seed: int, spans: Spans, devices):
        from repro.netsim import api
        self.api = api
        self.spans = spans
        self.flows = flow_table(cell.config, seed)
        self.sc = scenario(cell.config, self.flows, cell.name)
        self.sim = self.sc.build()
        self.max_ticks = self.sc.max_ticks
        self.salts = iter(salts(seed, "runs", 100_000))
        self.kept = []               # (salt, host final state)

    def iteration(self, keep: bool) -> dict:
        import jax
        sp = self.spans
        salt = next(self.salts)
        with sp("init_dispatch"):
            st = self.sim.run(max_ticks=self.max_ticks, seed=salt)
        with sp("wait"):
            st.now.block_until_ready()
        with sp("device_get"):
            host = jax.device_get(st)
        with sp("result"):
            res = self.api.RunResult.from_state(
                self.sim, host, scenario=self.sc.name, seed=salt,
                max_ticks=self.max_ticks)
        if keep:
            self.kept.append((salt, host))
        return dict(ticks=[res.ticks], done=[res.all_done])

    def sample(self, rng, n: int) -> list:
        idx = rng.choice(len(self.kept), size=min(n, len(self.kept)),
                         replace=False)
        return [self.kept[i] for i in sorted(idx)]

    def release(self):
        self.sim = None


MIXES = {"runs": RunsMix}


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    """What one run of a cell measured; the metric readers take it."""

    cell: Cell
    setup_s: float
    window_s: float
    iterations: list         # per iteration: dict(ticks=[...], done=[...])
    spans: list              # (name, start_s, end_s) of the window
    memory_peak_bytes: int = 0
    trace: dict | None = None       # trace_reduce.reduce() of the slice
    trace_iterations: list = dataclasses.field(default_factory=list)
    peaks: dict | None = None

    @property
    def sims(self) -> int:
        return sum(len(it["ticks"]) for it in self.iterations)

    @property
    def sim_ticks(self) -> int:
        return sum(sum(it["ticks"]) for it in self.iterations)


def window(mix, seconds: float, spans: Spans) -> tuple:
    first = len(spans.records)
    its = []
    t0 = time.perf_counter()
    while True:
        its.append(mix.iteration(keep=True))
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    return wall, its, spans.records[first:]


def traced_slice(mix, spans: Spans, n: int = 2) -> tuple:
    """``n`` iterations under the profiler; returns the trace's events
    (``trace_reduce.extract``) and the iterations."""
    import jax

    import trace_reduce
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    its = []
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        spans.tracing = True
        try:
            with jax.profiler.trace(d, profiler_options=opts):
                for _ in range(n):
                    with jax.profiler.TraceAnnotation("bench.slice"):
                        its.append(mix.iteration(keep=False))
        finally:
            spans.tracing = False
        events = trace_reduce.extract(d)
    return events, its


# --------------------------------------------------------------------------
# correct: the reference re-runs a sample of the window's output
# --------------------------------------------------------------------------


def check(cell: Cell, mix, seed: int, rec: Record, F=None,
          samples: int | None = None) -> dict:
    """Numbers compared, each with its limit: simulations of the window
    that did not finish, and elements of the sampled final states that
    differ from the reference's.  ``samples`` final states of the window
    are drawn from the seed (the traffic's ``check_samples``, unless
    given); ``F`` is the reference's float type (float32, as the
    configurations state; ``bench/control.py`` passes the control's)."""
    import jax
    import jax.numpy as jnp

    import compare
    from reference import Reference
    unfinished = sum(not d for it in rec.iterations for d in it["done"])
    if samples is None:
        samples = int(cell.traffic.get("check_samples", 1))
    picked = mix.sample(rng_for(seed, "check"), samples)
    mix.release()
    cfg = cell.config
    ref = Reference(cfg["tree"], cfg["link"], mix.flows, cfg["smartt"],
                    cfg["params"], cfg["max_ticks"],
                    F=jnp.float32 if F is None else F)
    run = ref.jit_run()
    worst, leaves, readings = 0, {}, []
    for salt, host in picked:
        t0 = time.perf_counter()
        want = jax.device_get(run(ref.c, salt))
        diff = compare.differing(compare.flatten(host), want)
        total = sum(diff.values())
        readings.append(dict(salt=salt, now=int(host.now),
                             ref_now=int(want["now"]), differing=total,
                             leaves=diff, ref_s=time.perf_counter() - t0))
        if total > worst:
            worst, leaves = total, diff
    return dict(
        checks={"unfinished_sims": (unfinished, 0),
                "state_elems_differing": (worst, 0)},
        compared=len(picked), differing_leaves=leaves, readings=readings)


# --------------------------------------------------------------------------
# one run of one cell
# --------------------------------------------------------------------------


def metric_values(entries: list, rec: Record) -> dict:
    out = {}
    for m in entries:
        v = _load_module("metrics", m["name"]).read(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices=None) -> dict:
    """Measure one cell once.  ``devices`` None: the cell's chips, which
    must be TPUs (``NoChip`` otherwise)."""
    import jax
    if devices is None:
        devices = check_devices(cell.chips)
    spans = Spans()
    mix = MIXES[cell.traffic["kind"]](cell, seed, spans, devices)
    mix.iteration(keep=False)                     # compile and warm up
    setup_s = time.perf_counter() - t_start
    wall, its, win_spans = window(mix, seconds, spans)
    rec = Record(cell=cell, setup_s=setup_s, window_s=wall, iterations=its,
                 spans=win_spans, memory_peak_bytes=memory_peak(devices))
    if devices[0].platform == "tpu":
        rec.peaks = peaks_for(devices[0].device_kind)
    if trace:
        import trace_reduce
        events, rec.trace_iterations = traced_slice(
            mix, spans, int(cell.traffic.get("trace_iterations", 2)))
        rec.trace = trace_reduce.reduce(events)
        if rec.trace is not None:
            ticks = [sum(it["ticks"]) for it in rec.trace_iterations]
            print(f"bench: traced slice: ops per iteration "
                  f"{rec.trace['ops_per_slice']} over ticks {ticks}; idle by "
                  f"host span {json.dumps(rec.trace['idle_detail'])}",
                  file=sys.stderr)
    t_check = time.perf_counter()
    verdict = check(cell, mix, seed, rec)
    del mix
    print(f"bench: check of {verdict['compared']} final states took "
          f"{time.perf_counter() - t_check:.1f} s", file=sys.stderr)
    checks = verdict["checks"]
    correct = all(val <= lim for val, lim in checks.values())
    dev = devices[0]
    out = {
        "correct": bool(correct),
        "attempted": rec.sims,
        "failed": checks["unfinished_sims"][0],
        "metrics": metric_values(cell.per_layer if trace else cell.end_to_end,
                                 rec),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count(),
                   "memory_peak_bytes": rec.memory_peak_bytes},
    }
    if trace and rec.trace is not None:
        out["device"]["busy_s"] = rec.trace["busy_s"]
        out["device"]["window_s"] = rec.trace["window_s"]
        out["breakdown"] = {"device_ops": rec.trace["top_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    runs = [t for it in rec.iterations for t in it["ticks"]]
    out["runs"] = {"sims": len(runs), "ticks_median": float(np.median(runs)),
                   "ticks_max": int(max(runs)), "window_s": rec.window_s,
                   "states_compared": verdict["compared"]}
    out["differing_leaves"] = verdict["differing_leaves"]
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(args, t_start: float) -> int:
    try:
        import repro  # noqa: F401
    except ImportError:
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    use_compile_cache()
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, float(args.seconds), bool(args.trace),
                       t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    r = out["runs"]
    print(f"bench: {cell.name} seed {args.seed}: {r['sims']} simulations, "
          f"ticks median {r['ticks_median']:g} max {r['ticks_max']}, "
          f"window {r['window_s']:.3f} s", file=sys.stderr)
    if out["differing_leaves"]:
        print(f"bench: leaves differing from the reference: "
              f"{out['differing_leaves']}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
