"""Failover benchmark: the registered dynamic-fault scenarios
(``corefail_128n_3t`` / ``flap_128n_3t`` / ``switchkill_128n_3t``) run
across congestion-control backends, each with and without the
failure-recovery transport knobs (capped exponential RTO backoff +
REPS timeout entropy eviction, ISSUE 8).

This is the paper's Fig. 7 degraded-fabric comparison re-shaped around
*dynamic* schedules: the fault fails mid-flight and (except the flap)
repairs before the budget, so the rows carry the recovery metrics —
``fault_ticks``, ``delivered_fault_frac``, ``ttr_max``, ``dip_depth``,
``dip_ticks`` — next to completion.  Row names are
``<scenario>[+recovery]/<algo>``; rows land in ledger section
``failover`` and compare PR-over-PR via::

  python -m benchmarks.check_regression --fresh fresh.json \
      --ledger BENCH_netsim.json --section failover \
      --metric completion --direction down --require corefail_128n_3t

``--quick`` runs only the corefail scenario on smartt (both recovery
variants) for the CI chaos job — a same-named subset of the full table
(same scenarios, same tick budgets), so the quick rows compare directly
against the committed ledger.

Usage:
  PYTHONPATH=src python -m benchmarks.failover [--quick] [--json-path PATH]
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import BENCH_JSON, emit, write_bench_json
from repro.netsim import api, scenarios

SCENARIOS = ("corefail_128n_3t", "flap_128n_3t", "switchkill_128n_3t")
ALGOS = ("smartt", "swift", "mprdma")

# the recovery configuration under test: retry up to 4x the base RTO and
# evict the cached REPS entropy on every timeout (see DESIGN.md Sec. 9)
RECOVERY = dict(rto_backoff_max=2, evict_on_timeout=True)


def variants(quick: bool):
    """(scenario name, algo, recovery?) triples — one ledger row each."""
    names = SCENARIOS[:1] if quick else SCENARIOS
    algos = ALGOS[:1] if quick else ALGOS
    return [(name, algo, rec)
            for name in names for algo in algos for rec in (False, True)]


def run_variant(name: str, algo: str, recovery: bool) -> dict:
    label = f"{name}+recovery/{algo}" if recovery else f"{name}/{algo}"
    over = dict(name=label, algo=algo)
    if recovery:
        over.update(RECOVERY)
    sc = scenarios.scenario(name).with_(**over)
    t0 = time.time()
    r = api.run(sc)
    row = r.row()
    emit(label, time.time() - t0,
         f"done={r.n_done}/{r.n_flows} completion={r.completion} "
         f"black={r.blackholed} ttr={row.get('ttr_max', -1)}")
    return row


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="corefail/smartt only (CI smoke)")
    p.add_argument("--json-path", default=BENCH_JSON, metavar="PATH",
                   help="ledger path (default: repo BENCH_netsim.json)")
    args = p.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    print("name,us_per_call,derived")
    rows = [run_variant(name, algo, rec)
            for name, algo, rec in variants(args.quick)]

    path = write_bench_json(
        "failover", rows, path=args.json_path,
        meta=dict(quick=bool(args.quick)))
    print(f"wrote {len(rows)} rows -> {path} section=failover",
          file=sys.stderr)


if __name__ == "__main__":
    main()
