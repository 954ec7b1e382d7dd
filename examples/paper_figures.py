"""Paper-figure reproductions: one function per figure or table of the
SMaRTT-REPS evaluation (Sec. 4), each at CPU scale.

Each ``figN_*`` function reproduces the *claim* of its figure and returns
``name,us_per_call,derived`` CSV rows; EXPERIMENTS.md Sec.
Paper-validation records the comparison against the paper's own
numbers.  Scenarios are scaled to CPU (32-128 nodes, 256 KiB - 2 MiB
flows) from the paper's 1024-node 800 Gb/s setup; the *relative*
behavior between algorithms is the reproduction target.

  PYTHONPATH=src python examples/paper_figures.py [--quick] [fig2 fig6 ...]

Figure-name substrings select figures (none = all, several minutes).
``--quick`` scales messages and tick budgets down for a smoke run.  A
figure that fails prints an ``ERROR`` row and its traceback, the others
still run, and the exit code is non-zero.
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

import numpy as np

from repro.netsim import api, workloads
from repro.netsim.scenarios import (LINK, TREE_2TO1, TREE_4TO1, TREE_8TO1,
                                    TREE_FLAT, Scenario)
from repro.netsim.state import SimConfig

KiB = 1024
MiB = 1024 * 1024


def _sz(size_bytes: int, quick: bool) -> int:
    """Quick-mode message scaling (smoke runs)."""
    return max(16 * KiB, size_bytes // 8) if quick else size_bytes


def _mt(max_ticks: int, quick: bool) -> int:
    return max(4000, max_ticks // 8) if quick else max_ticks


def _sc(tree, wl, max_ticks, *, algo="smartt", lb="reps", **cfg_kw):
    """An ad-hoc (tree, workload) Scenario for ``api.run``/``api.study``."""
    return Scenario(name=wl.name,
                    cfg=SimConfig(link=LINK, tree=tree, algo=algo, lb=lb,
                                  **cfg_kw),
                    wl=wl, max_ticks=max_ticks)


def emit(name: str, wall_s: float, derived) -> str:
    row = f"{name},{wall_s*1e6:.0f},{derived}"
    print(row)
    return row


def fig2_signals(quick=False):
    """Fig. 2/3a: ECN reacts faster, delay is fairer, SMaRTT gets both.

    Reports both the FCT outcome and the Fig. 2 quantity itself: the tick
    at which the mean congestion window first converges to within 1.5x of
    the fair share (cwnd trace via the engine's trace mode)."""
    rows = []
    wl = workloads.incast(TREE_FLAT, degree=8,
                          size_bytes=_sz(512 * KiB, quick), seed=0)
    fair = 26 * 4096 / 8 * 1.25          # BDP share of the bottleneck
    for algo in ("ecn_only", "delay_only", "smartt"):
        sc = _sc(TREE_FLAT, wl, _mt(60000, quick), algo=algo)
        s = api.run(sc)
        t0 = time.time()
        _, ys = sc.build().run_trace(128 if quick else 512, trace_flows=8)
        mean_cwnd = np.asarray(ys["cwnd"]).mean(axis=1)
        conv = np.argmax(mean_cwnd <= 1.5 * fair)
        if mean_cwnd.min() > 1.5 * fair:
            conv = -1
        rows.append(emit(f"fig2_incast8to1_{algo}",
                         s.wall_s + (time.time() - t0),
                         f"completion={s.completion};jain={s.jain:.3f};"
                         f"trims={s.trims};cwnd_conv_tick={conv}"))
    return rows


def fig3b_granularity(quick=False):
    """Fig. 3b: reacting every N ACKs (N<=50) stays within ~5% of per-packet."""
    rows = []
    wl = workloads.incast(TREE_FLAT, degree=8,
                          size_bytes=_sz(512 * KiB, quick), seed=0)
    res = api.study(_sc(TREE_FLAT, wl, _mt(60000, quick)),
                    points=[{"react_every": n} for n in (1, 8, 50)]).run()
    base = res[0].completion
    for s in res:
        rows.append(emit(f"fig3b_react_every_{dict(s.point)['react_every']}",
                         res.wall_s / len(res),
                         f"completion={s.completion};"
                         f"vs_perpacket={s.completion/base:.3f}"))
    return rows


def fig5b_wtd(quick=False):
    """Fig. 5b: Wait-to-Decrease cuts FCT on a non-oversubscribed
    permutation (transient ECMP imbalance left to REPS, not the window)."""
    rows = []
    wl = workloads.permutation(TREE_FLAT, size_bytes=_sz(1 * MiB, quick),
                               seed=2)
    for name, ovr in (("wtd_on", ()), ("wtd_off", (("wtd_thresh", 0.0),))):
        s = api.run(_sc(TREE_FLAT, wl, _mt(60000, quick), cc_overrides=ovr))
        rows.append(emit(f"fig5b_{name}", s.wall_s,
                         f"completion={s.completion};jain={s.jain:.3f}"))
    return rows


def fig6_reps(quick=False):
    """Fig. 6: REPS vs oblivious spray vs per-flow ECMP vs PLB."""
    rows = []
    wl = workloads.permutation(TREE_4TO1, size_bytes=_sz(1 * MiB, quick),
                               seed=3)
    for lb in ("reps", "spray", "plb", "ecmp"):
        s = api.run(_sc(TREE_4TO1, wl, _mt(60000, quick), lb=lb))
        rows.append(emit(f"fig6_lb_{lb}", s.wall_s,
                         f"completion={s.completion};jain={s.jain:.3f};"
                         f"trims={s.trims}"))
    return rows


def fig7_faults(quick=False):
    """Fig. 7: asymmetric (half-rate) link and link failure — REPS routes
    around; oblivious spray keeps hitting the bad path."""
    rows = []
    tree = TREE_FLAT
    wl = workloads.permutation(tree, size_bytes=_sz(1 * MiB, quick), seed=4)
    for lb in ("reps", "spray"):
        s = api.run(_sc(tree, wl, _mt(60000, quick), lb=lb,
                        faults=((0, 3, 2),), fault_start=0))
        rows.append(emit(f"fig7a_degraded_{lb}", s.wall_s,
                         f"completion={s.completion};trims={s.trims}"))
    for lb in ("reps", "spray"):
        s = api.run(_sc(tree, wl, _mt(60000, quick), lb=lb,
                        faults=((0, 3, 0),), fault_start=200))
        rows.append(emit(f"fig7c_linkdown_{lb}", s.wall_s,
                         f"completion={s.completion};"
                         f"blackholed={s.blackholed}"))
    return rows


def fig9_trimming(quick=False):
    """Fig. 8/9: losing trimming costs ~a base RTT or two, not more."""
    rows = []
    brtt = 26
    cases = [
        ("incast16_512K", TREE_FLAT,
         workloads.incast(TREE_FLAT, degree=16,
                          size_bytes=_sz(512 * KiB, quick), seed=5)),
        ("perm_4to1_1M", TREE_4TO1,
         workloads.permutation(TREE_4TO1, size_bytes=_sz(1 * MiB, quick),
                               seed=5)),
    ]
    for name, tree, wl in cases:
        base = api.run(_sc(tree, wl, _mt(60000, quick), trimming=True))
        noto = api.run(_sc(tree, wl, _mt(60000, quick), trimming=False))
        delta = (noto.completion - base.completion) / brtt
        rows.append(emit(f"fig9_{name}", base.wall_s + noto.wall_s,
                         f"trim={base.completion};timeout={noto.completion};"
                         f"delta_brtt={delta:.2f};"
                         f"spurious={noto.spurious_frac:.4f}"))
    return rows


def fig10_incast(quick=False):
    """Fig. 10: incast across degrees/sizes — EQDS near-perfect, SMaRTT
    within a few %, MPRDMA less fair, BBR slow for mid sizes."""
    rows = []
    for degree, size in ((8, 256 * KiB), (24, 512 * KiB)):
        size = _sz(size, quick)
        wl = workloads.incast(TREE_FLAT, degree=degree, size_bytes=size, seed=6)
        ideal = degree * (size // 4096) + 26
        for algo in ("smartt", "swift", "mprdma", "bbr", "eqds"):
            s = api.run(_sc(TREE_FLAT, wl, _mt(60000, quick), algo=algo))
            rows.append(emit(
                f"fig10_incast{degree}_{size//KiB}K_{algo}", s.wall_s,
                f"completion={s.completion};vs_ideal="
                f"{s.completion/ideal:.3f};jain={s.jain:.3f}"))
    return rows


def fig11_permutation(quick=False):
    """Fig. 1/11: permutations under oversubscription — SMaRTT fastest &
    fair; EQDS wastes bandwidth on trims; one-big-flow favors FastIncrease."""
    rows = []
    for name, tree in (("8to1", TREE_8TO1), ("4to1", TREE_4TO1),
                       ("2to1", TREE_2TO1)):
        wl = workloads.permutation(tree, size_bytes=_sz(512 * KiB, quick),
                                   seed=7)
        for algo in ("smartt", "swift", "mprdma", "bbr", "eqds"):
            s = api.run(_sc(tree, wl, _mt(120000, quick), algo=algo))
            rows.append(emit(
                f"fig11_perm_{name}_{algo}", s.wall_s,
                f"completion={s.completion};jain={s.jain:.3f};"
                f"trims={s.trims}"))
    # Fig 11c: multiple concurrent permutations
    wl = workloads.permutation(TREE_4TO1, size_bytes=_sz(512 * KiB, quick),
                               seed=8, n_perms=2)
    for algo in ("smartt", "eqds"):
        s = api.run(_sc(TREE_4TO1, wl, _mt(120000, quick), algo=algo))
        rows.append(emit(f"fig11c_multiperm_{algo}", s.wall_s,
                         f"completion={s.completion};trims={s.trims}"))
    # Fig 11d: one bigger flow — FastIncrease reclaims bandwidth
    wl = workloads.permutation(TREE_4TO1, size_bytes=_sz(512 * KiB, quick),
                               seed=9, big_flow=(0, _sz(1 * MiB, quick)))
    for algo in ("smartt", "swift"):
        s = api.run(_sc(TREE_4TO1, wl, _mt(120000, quick), algo=algo))
        rows.append(emit(f"fig11d_bigflow_{algo}", s.wall_s,
                         f"completion={s.completion}"))
    return rows


def fig12_alltoall(quick=False):
    """Fig. 12: windowed alltoall (MoE traffic) — sender-based CC wins as
    parallel connections grow."""
    rows = []
    tree = TREE_4TO1
    wl = workloads.alltoall(tree, size_bytes=_sz(64 * KiB, quick), window=4,
                            nodes=16)
    for algo in ("smartt", "swift", "eqds"):
        s = api.run(_sc(tree, wl, _mt(200000, quick), algo=algo))
        rows.append(emit(f"fig12_alltoall_w4_{algo}", s.wall_s,
                         f"completion={s.completion};trims={s.trims};"
                         f"done={s.n_done}"))
    return rows


def fig13_eqds(quick=False):
    """Fig. 13 / Sec. 5.1: EQDS augmented with SMaRTT fixes fabric
    congestion that vanilla EQDS cannot manage."""
    rows = []
    wl = workloads.permutation(TREE_8TO1, size_bytes=_sz(512 * KiB, quick),
                               seed=10)
    for algo in ("eqds", "eqds_smartt", "smartt"):
        s = api.run(_sc(TREE_8TO1, wl, _mt(120000, quick), algo=algo))
        rows.append(emit(f"fig13_{algo}", s.wall_s,
                         f"completion={s.completion};trims={s.trims};"
                         f"jain={s.jain:.3f}"))
    return rows


ALL_FIGS = (fig2_signals, fig3b_granularity, fig5b_wtd, fig6_reps,
            fig7_faults, fig9_trimming, fig10_incast, fig11_permutation,
            fig12_alltoall, fig13_eqds)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("figs", nargs="*", help="substring filters (fig2 fig6 ...)")
    p.add_argument("--quick", action="store_true",
                   help="scaled-down smoke run")
    args = p.parse_args(argv)

    t0 = time.time()
    print("name,us_per_call,derived")
    rows, failed = [], []
    for fn in ALL_FIGS:
        if args.figs and not any(w in fn.__name__ for w in args.figs):
            continue
        try:
            kw = ({"quick": True} if args.quick
                  and "quick" in inspect.signature(fn).parameters else {})
            rows.extend(fn(**kw))
        except Exception as e:  # noqa: BLE001
            # keep the CSV row shape but never swallow the diagnosis:
            # the traceback goes to stderr and the exit code is non-zero
            traceback.print_exc(file=sys.stderr)
            failed.append(fn.__name__)
            print(f"{fn.__name__},0,ERROR:{type(e).__name__}:{e}")

    print(f"\n# total wall: {time.time()-t0:.1f}s; {len(rows)} rows")
    if failed:
        print(f"# {len(failed)} figure(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
