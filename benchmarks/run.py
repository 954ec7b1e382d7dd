"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  The netsim figures always
run (each through the experiment API — ``common.run_scenario`` returns a
typed ``api.RunResult``); the roofline table is appended when the
dry-run sweeps' JSON outputs exist (see repro.launch.dryrun).  With
``--json`` the rows are also recorded into the machine-readable
``BENCH_netsim.json`` ledger (section ``figs``) via
``benchmarks.common.write_bench_json``.

``--studies`` additionally runs the fused tuning-grid studies
(``benchmarks.sweep``: {scenario x algo x GRID x seeds}, one compile per
grid) and, with ``--json``, records their ``StudyResult`` rows into the
``studies`` ledger section — compare PR-over-PR via
``benchmarks.check_regression --section studies --metric completion``.

``--quick`` is plumbed through to every netsim figure (sizes and tick
budgets scaled down for smoke runs); quick rows land in the separate
ledger section ``figs_quick`` so they never overwrite the full-size
figures.

Usage:
  PYTHONPATH=src python -m benchmarks.run [--json] [--json-path PATH]
      [--quick] [--studies] [fig2 fig6 ...]
"""

from __future__ import annotations

import argparse
import inspect
import os
import sys
import time
import traceback


def _row_dicts(rows, errors):
    out = []
    for r in rows:
        name, us, derived = r.split(",", 2)
        out.append(dict(name=name, us_per_call=float(us), derived=derived))
    out.extend(dict(name=name, error=err) for name, err in errors)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("figs", nargs="*", help="substring filters (fig2 fig6 ...)")
    p.add_argument("--json", action="store_true",
                   help="also record rows into BENCH_netsim.json")
    p.add_argument("--json-path", default=None, metavar="PATH",
                   help="ledger path (implies --json)")
    p.add_argument("--quick", action="store_true",
                   help="scaled-down smoke run (rows go to section "
                        "'figs_quick', never the full-size 'figs')")
    p.add_argument("--studies", action="store_true",
                   help="also run the fused tuning-grid studies "
                        "(benchmarks.sweep) and record their StudyResult "
                        "rows (section 'studies')")
    args = p.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    t0 = time.time()
    from benchmarks import fig_benchmarks as F

    wanted = set(args.figs)

    def selected(fn):
        return not wanted or any(w in fn.__name__ for w in wanted)

    print("name,us_per_call,derived")
    rows, errors = [], []
    for fn in F.ALL_FIGS:
        if not selected(fn):
            continue
        try:
            kw = ({"quick": True} if args.quick
                  and "quick" in inspect.signature(fn).parameters else {})
            rows.extend(fn(**kw))
        except Exception as e:  # noqa: BLE001
            # keep the CSV row shape but never swallow the diagnosis:
            # the traceback goes to stderr and the exit code is non-zero
            traceback.print_exc(file=sys.stderr)
            errors.append((fn.__name__, f"{type(e).__name__}:{e}"))
            print(f"{fn.__name__},0,ERROR:{type(e).__name__}:{e}")

    if args.json or args.json_path:
        from benchmarks.common import write_bench_json
        write_bench_json("figs_quick" if args.quick else "figs",
                         _row_dicts(rows, errors), path=args.json_path)

    if args.studies:
        from benchmarks import sweep as S
        sweep_argv = []
        if args.json or args.json_path:
            sweep_argv.append("--json")
        if args.json_path:
            sweep_argv.extend(["--json-path", args.json_path])
        if args.quick:
            # scaled-down grid; rows go to section 'studies_quick' so a
            # smoke run never touches the reviewed 'studies' baseline
            sweep_argv.append("--quick")
        print()
        S.main(sweep_argv)

    # roofline table if the sweep artifacts exist
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if (not wanted or "roofline" in " ".join(wanted)) and \
            os.path.exists(os.path.join(here, "roofline_results.json")):
        from benchmarks import roofline
        print()
        roofline.main()

    print(f"\n# total wall: {time.time()-t0:.1f}s; {len(rows)} rows")
    if errors:
        print(f"# {len(errors)} figure(s) failed: "
              f"{', '.join(name for name, _ in errors)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
