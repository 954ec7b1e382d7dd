"""Superstep execution-engine throughput benchmark (ticks/second).

Measures the aggregate run loop on the standard scenarios (incast,
permutation, windowed alltoall) across CC backends and superstep sizes,
against an *ungated* K=1 while-loop reference — the pre-superstep engine
loop whose all-done exit reduction runs every tick.  Variants are measured
interleaved (round-robin over reps, best-of) so machine-load drift does
not bias one variant.

The sparse/large-message scenarios (``sparse_heavy``/``sparse_large``,
DESIGN.md Sec. 6.3) are additionally measured with event-horizon time
leaping on vs off: the trajectory is bit-for-bit identical (asserted in
tests/test_engine_leap.py), so the ticks/sec ratio isolates the leap.

Prints the usual ``name,us_per_call,derived`` CSV rows and always records
a machine-readable ``perf`` section into ``BENCH_netsim.json`` (see
``benchmarks.common.write_bench_json``) so ticks/sec is tracked
PR-over-PR.

Usage:
  PYTHONPATH=src python -m benchmarks.perf [--quick] [--json-path PATH]
      [--reps N] [--backends jnp,pallas]
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from benchmarks.common import BENCH_JSON, emit, write_bench_json
from repro.netsim.scenarios import scenario


@functools.partial(jax.jit, static_argnums=(0, 2))
def _run_k1_ungated(step, state0, max_ticks):
    """Reference loop: the pre-superstep engine hot loop (one tick per
    while_loop iteration, exit reduction evaluated every tick)."""
    def cond(st):
        return (st.now < max_ticks) & ~jnp.all(st.done)

    return jax.lax.while_loop(cond, step, state0)


def _legacy_baseline(cfg, wl, max_ticks):
    """The full pre-PR engine: legacy tick op structure (benchmarks.legacy)
    under the ungated K=1 while loop."""
    from benchmarks.legacy import build_legacy
    sim = build_legacy(cfg, wl)
    return lambda: _run_k1_ungated(sim.step, sim.init(), max_ticks)


def scenarios(quick: bool):
    """(registry scenario name, backends) per standard dense scenario —
    the names double as ledger row keys (``repro.netsim.scenarios``).

    A ``pallas`` row runs *all* the registered kernels on that backend
    (cc_update + the fused enqueue-rank/arbitration + the packed ring
    drain) in interpret mode on CPU (orders of magnitude slower per
    tick), so it only gets the smallest scenario of each mode;
    compiled-TPU runs lift that restriction.
    """
    if quick:
        return [("tiny_incast3", ("jnp", "pallas")),
                ("tiny_perm4", ("jnp",))]
    return [("incast8_32n", ("jnp", "pallas")),
            ("perm64", ("jnp",)),
            ("alltoall16_w4", ("jnp",))]


def leap_scenarios(quick: bool):
    """Registry names of the sparse/large-message scenarios measured
    leap-on vs leap-off — sized so the fabric idles for most of the
    simulated span (heavy-tailed sizes with spread-out arrivals; few
    large staggered transfers)."""
    if quick:
        return ["tiny_sparse"]
    return ["sparse_heavy_32n", "sparse_large_32n"]


def tier3_scenarios(quick: bool):
    """(registry scenario name, backends) per three-tier (core-plane)
    scenario: the paper-scale fabrics.  Big per-tick working sets
    (512-1024 nodes, 1.8k-3.6k emitters), so they run the production
    superstep only (plus the legacy k1 baseline) rather than the whole
    superstep ladder.  The pallas kernel backends (interpret mode on
    CPU) run only on the tiny 3-tier fabric, same policy as the dense
    list."""
    if quick:
        return [("tiny_3t", ("jnp", "pallas")),
                ("perm_512n_3t_degraded", ("jnp",))]
    return [("perm_512n_3t", ("jnp",)),
            ("perm_1024n_3t", ("jnp",)),
            ("incast_256x1_3t", ("jnp",)),
            ("alltoall_3t", ("jnp",)),
            ("perm_512n_3t_degraded", ("jnp",)),
            ("tiny_3t", ("jnp", "pallas"))]


def superstep_sizes(brtt: int, quick: bool):
    ks = [1, brtt] if quick else [1, 8, brtt, 2 * brtt]
    return sorted(set(ks))


def _measure(variants, reps):
    """Warm every variant (compile + first run), then time them interleaved
    (round-robin over reps, best-of) so machine-load drift does not bias
    one variant.  Returns ({label: best wall}, {label: simulated ticks})."""
    walls, ticks = {}, {}
    for label, fn in variants.items():
        st = fn()
        st.now.block_until_ready()
        ticks[label] = int(st.now)
        walls[label] = float("inf")
    for _ in range(reps):
        for label, fn in variants.items():
            t0 = time.time()
            fn().now.block_until_ready()
            walls[label] = min(walls[label], time.time() - t0)
    return walls, ticks


def bench_scenario(name, backend, reps, quick, ksizes=None):
    """Measure the ungated reference and every superstep size, interleaved.
    Returns one row dict per variant.  The k-variants run the *production
    default* engine config (time leaping included — a no-op jump on these
    dense scenarios beyond the per-superstep horizon cost); each row
    records its ``leap`` flag so ledger comparisons are labeled.
    ``ksizes`` overrides the measured superstep ladder: a list of sizes,
    or ``"production"`` for just the auto size (one base RTT — the
    three-tier rows measure only that).

    A ``pallas`` row runs every registered kernel on that backend —
    cc_update *and* the fabric enqueue-rank/arbitration and transport
    ring-drain kernels — so the label means "the pallas hot loop", not
    one kernel in isolation."""
    sc = scenario(name, cc_backend=backend, fabric_backend=backend,
                  transport_backend=backend)
    max_ticks = sc.max_ticks
    base_sim = sc.build()
    # baseline: the pre-PR engine — legacy tick op structure under the
    # ungated one-tick-per-iteration while loop (see benchmarks/legacy.py)
    variants = {"k1_ungated": _legacy_baseline(sc.cfg, sc.wl, max_ticks)}
    sims = {}
    if ksizes is None:
        ksizes = superstep_sizes(base_sim.dims.brtt_inter, quick)
    elif ksizes == "production":
        ksizes = [base_sim.dims.brtt_inter]
    for k in ksizes:
        sim = sc.with_(superstep=k).build()
        sims[f"k{k}"] = sim
        variants[f"k{k}"] = (lambda s=sim: s.run(max_ticks))

    walls, ticks = _measure(variants, reps)
    base_tps = ticks["k1_ungated"] / walls["k1_ungated"]
    rows = []
    for label in variants:
        tps = ticks[label] / walls[label]
        speedup = tps / base_tps
        k = 0 if label == "k1_ungated" else int(label[1:])
        emit(f"perf_{name}_{backend}_{label}", walls[label],
             f"ticks={ticks[label]};ticks_per_sec={tps:.0f};"
             f"speedup_vs_k1_ungated={speedup:.2f}")
        rows.append(dict(
            name=f"{name}/{backend}/{label}", scenario=name, backend=backend,
            superstep=k,
            leap=bool(sims[label].dims.leap) if label in sims else False,
            ticks=ticks[label], wall_s=round(walls[label], 6),
            ticks_per_sec=round(tps, 1),
            speedup_vs_k1_ungated=round(speedup, 3)))
    # per-scenario best-k record: which fused superstep size wins, and —
    # loudly — whether fusion *lost* to the ungated k=1 reference (the
    # regression mode this ledger exists to catch; a fused k>1 loop
    # re-running a too-expensive tick body can sit below the legacy
    # baseline, as perm_512n_3t did before the large-N scatter work)
    fused = {lbl: ticks[lbl] / walls[lbl] for lbl in sims
             if int(lbl[1:]) > 1}
    if fused:
        best_lbl = max(fused, key=fused.get)
        best_tps = fused[best_lbl]
        regression = bool(best_tps < base_tps)
        emit(f"perf_{name}_{backend}_best_k", walls[best_lbl],
             f"best_k={best_lbl[1:]};ticks_per_sec={best_tps:.0f};"
             f"fusion_regression={regression}")
        if regression:
            print(f"# !! FUSION REGRESSION {name}/{backend}: best fused "
                  f"{best_lbl} = {best_tps:.0f} ticks/s < k1_ungated = "
                  f"{base_tps:.0f} ticks/s", flush=True)
        rows.append(dict(
            name=f"{name}/{backend}/best_k", scenario=name, backend=backend,
            kind="best_k", best_k=int(best_lbl[1:]),
            ticks_per_sec=round(best_tps, 1),
            speedup_vs_k1_ungated=round(best_tps / base_tps, 3),
            fusion_regression=regression))
    return rows


def bench_leap_scenario(name, reps):
    """Measure leap-on vs leap-off (superstep auto, jnp backend) on one
    sparse scenario, interleaved best-of.  Returns one row per variant."""
    sc = scenario(name)
    max_ticks = sc.max_ticks
    variants, sims = {}, {}
    for label, leap in (("leap_off", False), ("leap_on", True)):
        sim = sc.with_(leap=leap).build()
        sims[label] = sim
        variants[label] = (lambda s=sim: s.run(max_ticks))

    walls, ticks = _measure(variants, reps)
    base_tps = ticks["leap_off"] / walls["leap_off"]
    rows = []
    for label in variants:
        tps = ticks[label] / walls[label]
        emit(f"perf_{name}_jnp_{label}", walls[label],
             f"ticks={ticks[label]};ticks_per_sec={tps:.0f};"
             f"speedup_vs_leap_off={tps / base_tps:.2f}")
        rows.append(dict(
            name=f"{name}/jnp/{label}", scenario=name, backend="jnp",
            superstep=sims[label].dims.superstep,
            leap=bool(sims[label].dims.leap),
            ticks=ticks[label], wall_s=round(walls[label], 6),
            ticks_per_sec=round(tps, 1),
            speedup_vs_leap_off=round(tps / base_tps, 3)))
    return rows


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="tiny topology smoke run (CI)")
    p.add_argument("--json-path", default=BENCH_JSON, metavar="PATH",
                   help="BENCH_netsim.json path (always written)")
    p.add_argument("--reps", type=int, default=None,
                   help="timing repetitions per variant (best-of)")
    p.add_argument("--backends", default=None,
                   help="comma-separated override, e.g. 'jnp'")
    p.add_argument("--only", default=None, metavar="NAMES",
                   help="comma-separated scenario-name filter (applies to "
                        "the dense, leap, and three-tier lists)")
    args = p.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    reps = args.reps or (2 if args.quick else 4)
    only = set(args.only.split(",")) if args.only else None

    def picked(name):
        return only is None or name in only

    t0 = time.time()
    print("name,us_per_call,derived")
    rows = []
    # three-tier rows run FIRST: the large-N numbers are the ledger's
    # headline and in-process memory pressure from the earlier dense /
    # interpret-mode pallas suites suppresses later timings by ~10-12%
    # (allocator fragmentation + compiled-workspace residue), which is
    # measurement pollution, not engine speed.  The small dense/leap
    # scenarios are far less sensitive to heap state.
    for name, backends in tier3_scenarios(args.quick):
        if not picked(name):
            continue
        if args.backends:
            backends = [b for b in args.backends.split(",") if b]
        for backend in backends:
            rows.extend(bench_scenario(name, backend, min(reps, 2),
                                       args.quick, ksizes="production"))
    for name, backends in scenarios(args.quick):
        if not picked(name):
            continue
        if args.backends:
            backends = [b for b in args.backends.split(",") if b]
        for backend in backends:
            rows.extend(bench_scenario(name, backend, reps, args.quick))
    for name in leap_scenarios(args.quick):
        if picked(name):
            rows.extend(bench_leap_scenario(name, min(reps, 2)))
    path = write_bench_json(
        "perf", rows, path=args.json_path,
        meta=dict(quick=bool(args.quick), reps=reps, jax=jax.__version__,
                  device=str(jax.devices()[0].platform)))
    print(f"\n# total wall: {time.time()-t0:.1f}s; {len(rows)} rows -> {path}")


if __name__ == "__main__":
    main()
