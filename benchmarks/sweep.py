"""Study-grid benchmark: paper-style tuning sweeps x seed batches for one
compile per (scenario, algorithm).

Reproduces the Fig. 4-7-shaped studies through the experiment API
(DESIGN.md Sec. 7): an incast and a core-crossing permutation scenario,
each evaluated across {smartt, swift, mprdma, eqds} over an 8-point grid
of (start_cwnd_mult x react_every) plus RED threshold variants, crossed
with decorrelation seeds — every {point x seed} lane of a grid rides one
compiled step (``api.study``), the kind of many-config evaluation loop
that UEC-style tuning studies and spraying/congested-path analyses need.

Prints ``name,us_per_call,derived`` CSV rows (one per lane, plus a
per-grid compile/wall summary).  With ``--json`` the typed
``StudyResult.rows()`` land in the ``studies`` section of
``BENCH_netsim.json`` (compare PR-over-PR via
``benchmarks.check_regression --section studies --metric completion``).

Usage:
  PYTHONPATH=src python -m benchmarks.sweep [--seeds N] [--quick] [--json]
      [--json-path PATH] [incast perm ...]
"""

from __future__ import annotations

import argparse
import time

from repro.analysis import trace_guard
from repro.netsim import api
from repro.netsim.scenarios import scenario

SCENARIOS = ("incast8_16n", "perm_16n")
ALGOS = ("smartt", "swift", "mprdma", "eqds")
MAX_TICKS = 60000

# 8-point grid: initial window x reaction granularity, plus RED variants
GRID = (
    [{"start_cwnd_mult": a, "react_every": r}
     for a in (0.5, 1.0, 1.25) for r in (1, 4)]
    + [{"kmin_frac": 0.1, "kmax_frac": 0.4},
       {"kmin_frac": 0.3, "kmax_frac": 0.9}]
)


def run_study(sc_name: str, algo: str, seeds, grid=GRID,
              max_ticks=MAX_TICKS) -> tuple:
    """One fused {grid x seeds} study; returns (ledger rows, csv rows)."""
    sc = scenario(sc_name, algo=algo, max_ticks=max_ticks)
    t0 = time.time()
    st = api.study(sc, points=grid, seeds=seeds)
    with trace_guard("engine.step") as g:
        res = st.run()
    build_wall = time.time() - t0
    compiles = g.count
    csv = []
    for r in res:
        csv.append(f"study_{sc_name}_{algo}[{r.point_tag}]s{r.seed},"
                   f"{build_wall / len(res) * 1e6:.0f},"
                   f"completion={r.completion};jain={r.jain:.3f};"
                   f"slowdown_p99={r.slowdown_p99:.2f};trims={r.trims};"
                   f"done={r.n_done}")
    csv.append(f"study_{sc_name}_{algo}_total,{build_wall * 1e6:.0f},"
               f"lanes={len(res)};points={st.n_points};seeds={st.n_seeds};"
               f"step_compiles={compiles};run_wall_s={res.wall_s:.2f}")
    rows = res.rows()
    for row in rows:
        row["wall_s"] = round(res.wall_s / len(rows), 6)
    return rows, csv


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("filters", nargs="*", help="substring filters "
                   "(incast perm smartt ...)")
    p.add_argument("--seeds", type=int, default=2,
                   help="decorrelation seeds per grid point (default 2)")
    p.add_argument("--quick", action="store_true",
                   help="smoke run: one scenario x {smartt,eqds} over a "
                        "4-point grid, 1 seed, scaled ticks; rows go to "
                        "section 'studies_quick', never 'studies'")
    p.add_argument("--json", action="store_true",
                   help="record StudyResult rows into BENCH_netsim.json "
                        "(section 'studies')")
    p.add_argument("--json-path", default=None, metavar="PATH",
                   help="ledger path (implies --json)")
    args = p.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    if args.quick:
        scenarios_, algos = ("incast8_16n",), ("smartt", "eqds")
        grid, seeds, max_ticks = GRID[:4], (0,), MAX_TICKS // 4
    else:
        scenarios_, algos = SCENARIOS, ALGOS
        grid, seeds, max_ticks = GRID, tuple(range(args.seeds)), MAX_TICKS

    print("name,us_per_call,derived")
    ledger_rows = []
    for sc_name in scenarios_:
        for algo in algos:
            tag = f"{sc_name}_{algo}"
            if args.filters and not any(w in tag for w in args.filters):
                continue
            rows, csv = run_study(sc_name, algo, seeds, grid, max_ticks)
            ledger_rows.extend(rows)
            for line in csv:
                print(line)

    if args.json or args.json_path:
        from benchmarks.common import write_bench_json
        path = write_bench_json(
            "studies_quick" if args.quick else "studies", ledger_rows,
            path=args.json_path,
            meta=dict(grid=len(grid), seeds=len(seeds)))
        print(f"# {len(ledger_rows)} study rows -> {path}")


if __name__ == "__main__":
    main()
