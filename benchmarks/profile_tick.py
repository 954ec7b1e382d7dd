"""Per-phase tick profiler: where does a tick's wall time go as N grows?

Times each of the six tick phases (and the composed tick, and the leap
horizon reduction) in isolation under jit on permutation scenarios at
N ∈ {32, 128, 512, 1024}, by running R phase applications inside one
``lax.fori_loop`` (so per-call dispatch amortizes away and XLA cannot
dead-code the phase).  JAX op cost is shape-dependent, not
data-dependent, so timing a self-composed phase on a mid-run state is
representative of the phase inside the real tick.

This is the measurement that ranks phases for kernelization (DESIGN.md
Sec. 6.4) and later audits that the kernel choices still match the
profile.  Two sections land in BENCH_netsim.json:

- ``phase_profile``: one row per (scenario, phase) with us/tick and the
  phase's share of the composed tick.
- ``roofline``: per scenario, the resident SimState footprint, a
  measured STREAM-triad bandwidth, and the implied memory-bound
  ticks/sec ceiling next to the measured composed-tick rate — how far
  the tick is from "every state byte touched twice at stream speed"
  (methodology: DESIGN.md Sec. 6.4).

Usage:
  PYTHONPATH=src python -m benchmarks.profile_tick [--quick]
      [--ns 32,128,512,1024] [--reps N] [--json-path PATH]
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from benchmarks.common import BENCH_JSON, LINK, TREE_FLAT, emit, \
    write_bench_json
from repro.netsim import workloads
from repro.netsim.scenarios import Scenario, scenario
from repro.netsim.state import SimConfig

KiB = 1024


def _perm32():
    wl = workloads.permutation(TREE_FLAT, size_bytes=256 * KiB, seed=7)
    return Scenario(name="perm_32n_flat",
                    cfg=SimConfig(link=LINK, tree=TREE_FLAT), wl=wl,
                    max_ticks=60_000)


# N -> scenario factory; 128/512/1024 are the three-tier ledger scenarios
SCENARIOS = {
    32: _perm32,
    128: lambda: scenario("perm_128n_3t"),
    512: lambda: scenario("perm_512n_3t"),
    1024: lambda: scenario("perm_1024n_3t"),
}


def _phases(sim):
    """The six tick phases with this sim's resolved backends and consts
    bound — read straight off ``sim.phases`` (the exact closures
    ``engine.build`` composes into the step), so the profile can never
    drift from the real tick composition."""
    consts = sim.consts
    return {name: functools.partial(fn, consts) for name, fn in sim.phases}


@functools.partial(jax.jit, static_argnums=(0, 2))
def _loop(fn, st, iters):
    return jax.lax.fori_loop(0, iters, lambda _, s: fn(s), st)


def _time_phase(fn, st, iters, reps):
    """Best-of wall seconds per application of ``fn`` (R applications
    fused in one fori_loop per timed call)."""
    _loop(fn, st, iters).now.block_until_ready()     # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        _loop(fn, st, iters).now.block_until_ready()
        best = min(best, time.time() - t0)
    return best / iters


def _state_bytes(st) -> int:
    return int(sum(jnp.asarray(leaf).nbytes for leaf in jax.tree.leaves(st)))


def stream_gbps(reps: int = 3, mb: int = 256) -> float:
    """Measured STREAM-triad bandwidth (GB/s): a = b + s*c over arrays
    sized far beyond LLC, 3 streams of traffic per element."""
    n = mb * 1024 * 1024 // 4
    b = jnp.ones((n,), jnp.float32)
    c = jnp.ones((n,), jnp.float32)
    triad = jax.jit(lambda b, c: b + 1.5 * c)
    triad(b, c).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        triad(b, c).block_until_ready()
        best = min(best, time.time() - t0)
    return 3 * n * 4 / best / 1e9


def profile_scenario(n: int, reps: int):
    """Profile one scenario: per-phase rows + a roofline row."""
    sc = SCENARIOS[n]()
    sim = sc.build()
    # a mid-run state (rings populated, flows active); content does not
    # change op cost, but it keeps the profile honest if that ever changes
    st = sim.init()
    for _ in range(16):
        st = sim.step(st)
    st.now.block_until_ready()

    iters = 100 if n <= 128 else 25
    rows, total_us = [], 0.0
    walls = {label: _time_phase(fn, st, iters, reps)
             for label, fn in _phases(sim).items()}
    tick_wall = _time_phase(sim.step, st, iters, reps)
    hor_wall = _time_phase(
        lambda s: s._replace(now=s.now + 0 * sim.horizon(s)), st, iters, reps)
    for label, wall in list(walls.items()) + [("horizon", hor_wall),
                                              ("full_tick", tick_wall)]:
        us = wall * 1e6
        share = wall / tick_wall
        emit(f"phase_{sc.name}_{label}", wall,
             f"us_per_tick={us:.1f};share_of_tick={share:.2f}")
        rows.append(dict(name=f"{sc.name}/{label}", scenario=sc.name,
                         n=n, phase=label, us_per_tick=round(us, 2),
                         share_of_tick=round(share, 3)))
        if label not in ("horizon", "full_tick"):
            total_us += us

    sb = _state_bytes(st)
    bw = stream_gbps()
    # memory-bound ceiling: every resident state byte read + written once
    # per tick at stream speed (touch factor 2)
    ceil_tps = bw * 1e9 / (2.0 * sb)
    meas_tps = 1.0 / tick_wall
    roof = dict(name=f"roofline/{sc.name}", scenario=sc.name, n=n,
                state_bytes=sb, stream_gbps=round(bw, 2),
                memory_bound_ticks_per_sec=round(ceil_tps, 1),
                measured_ticks_per_sec=round(meas_tps, 1),
                roofline_fraction=round(meas_tps / ceil_tps, 4),
                phase_sum_us=round(total_us, 1))
    emit(f"roofline_{sc.name}", tick_wall,
         f"state_mb={sb/1e6:.1f};ceiling_tps={ceil_tps:.0f};"
         f"measured_tps={meas_tps:.0f};frac={meas_tps/ceil_tps:.3f}")
    return rows, roof


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="N in {32,128} only (CI smoke)")
    p.add_argument("--ns", default=None,
                   help="comma-separated N list, e.g. '512,1024'")
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--json-path", default=BENCH_JSON, metavar="PATH")
    args = p.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    if args.ns:
        ns = [int(x) for x in args.ns.split(",") if x]
    else:
        ns = [32, 128] if args.quick else [32, 128, 512, 1024]

    t0 = time.time()
    print("name,us_per_call,derived")
    phase_rows, roof_rows = [], []
    for n in ns:
        rows, roof = profile_scenario(n, args.reps)
        phase_rows.extend(rows)
        roof_rows.append(roof)
    meta = dict(jax=jax.__version__, device=str(jax.devices()[0].platform))
    write_bench_json("phase_profile", phase_rows, path=args.json_path,
                     meta=meta)
    path = write_bench_json("roofline", roof_rows, path=args.json_path,
                            meta=meta)
    print(f"\n# total wall: {time.time()-t0:.1f}s -> {path}")


if __name__ == "__main__":
    main()
