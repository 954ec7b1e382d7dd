"""Collectives benchmark: the dependency-driven collective scenarios
(ring/tree allreduce, all-gather, pipeline — DESIGN.md Sec. 11) run
across congestion-control algorithms, reporting collective completion
time (CCT) next to the flow-level metrics.

CCT is the metric training traffic actually experiences: the ticks from
a collective's earliest ``t_start`` to its *last* flow's delivery — a
single straggler chunk stalls the whole operation, which per-flow FCT
percentiles hide.  Row names are ``<scenario>/<algo>``; rows land in
ledger section ``collectives`` and compare PR-over-PR via::

  python -m benchmarks.check_regression --fresh fresh.json \
      --ledger BENCH_netsim.json --section collectives \
      --metric cct --direction down --require tiny_allreduce_ring

``--quick`` runs only the tiny scenarios on smartt for the CI
collectives job — same names and tick budgets as the full table, so the
quick rows compare directly against the committed ledger.

Usage:
  PYTHONPATH=src python -m benchmarks.collectives [--quick] [--json-path PATH]
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import BENCH_JSON, emit, write_bench_json
from repro.netsim import api, scenarios

TINY = ("tiny_allreduce_ring", "tiny_allgather", "tiny_pipeline")
FULL = ("allreduce_ring_128n_3t", "allreduce_tree_128n_3t",
        "allgather_64n_3t", "pipeline_32n")
ALGOS = ("smartt", "swift", "mprdma")


def variants(quick: bool):
    """(scenario name, algo) pairs — one ledger row each."""
    if quick:
        return [(name, ALGOS[0]) for name in TINY]
    return [(name, algo) for name in TINY + FULL for algo in ALGOS]


def run_variant(name: str, algo: str) -> dict:
    label = f"{name}/{algo}"
    sc = scenarios.scenario(name).with_(name=label, algo=algo)
    t0 = time.time()
    r = api.run(sc)
    row = r.row()
    emit(label, time.time() - t0,
         f"done={r.n_done}/{r.n_flows} cct={row.get('cct', -1)} "
         f"completion={r.completion} trims={r.trims}")
    return row


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="tiny scenarios on smartt only (CI smoke)")
    p.add_argument("--json-path", default=BENCH_JSON, metavar="PATH",
                   help="ledger path (default: repo BENCH_netsim.json)")
    args = p.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    print("name,us_per_call,derived")
    rows = [run_variant(name, algo) for name, algo in variants(args.quick)]

    path = write_bench_json(
        "collectives", rows, path=args.json_path,
        meta=dict(quick=bool(args.quick)))
    print(f"wrote {len(rows)} rows -> {path} section=collectives",
          file=sys.stderr)


if __name__ == "__main__":
    main()
