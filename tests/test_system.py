"""End-to-end behaviour tests for the whole system: the paper's headline
result on the netsim, and the training stack's learn+restart loop."""

import importlib.util
from pathlib import Path

import numpy as np

from repro.netsim import api, workloads
from repro.netsim.engine import SimConfig, build, jain_fairness, summarize
from repro.netsim.scenarios import Scenario
from repro.netsim.units import FatTreeConfig, LinkConfig


def test_headline_smartt_beats_baselines_on_oversubscribed_permutation():
    """Paper Sec. 4.4 headline: on an oversubscribed fat tree SMaRTT
    completes a permutation at least as fast as Swift/MPRDMA while being
    the fairest, and EQDS burns an order of magnitude more trims."""
    link = LinkConfig()
    tree = FatTreeConfig(racks=4, nodes_per_rack=16, uplinks=4)
    wl = workloads.permutation(tree, size_bytes=512 * 1024, seed=1)
    res = {}
    for algo in ("smartt", "swift", "mprdma", "eqds"):
        sim = build(SimConfig(link=link, tree=tree, algo=algo, lb="reps"), wl)
        st = sim.run(max_ticks=60000)
        s = summarize(sim, st)
        fct = s["fct_ticks"][np.asarray(st.done)]
        res[algo] = dict(c=s["fct_max"], j=jain_fairness(fct), t=s["trims"],
                         done=s["all_done"])
    assert all(r["done"] for r in res.values())
    assert res["smartt"]["c"] <= min(res["swift"]["c"], res["mprdma"]["c"])
    assert res["smartt"]["j"] >= max(res["swift"]["j"], res["mprdma"]["j"],
                                     res["eqds"]["j"]) - 1e-9
    assert res["eqds"]["t"] > 3 * res["smartt"]["t"]


def test_batched_runs_are_decorrelated_and_complete():
    link = LinkConfig()
    tree = FatTreeConfig(racks=2, nodes_per_rack=4, uplinks=2)
    wl = workloads.permutation(tree, size_bytes=64 * 4096, seed=2)
    sc = Scenario(name=wl.name, wl=wl,
                  cfg=SimConfig(link=link, tree=tree, algo="smartt", lb="reps"))
    st = api.study(sc, seeds=range(4)).run_states(max_ticks=30000)
    assert bool(np.all(np.asarray(st.done)))
    fcts = [int(np.asarray(st.fct)[i].max()) for i in range(4)]
    assert len(set(fcts)) > 1          # per-seed salts decorrelate runs


def test_train_learns_and_restarts(tmp_path):
    """The end-to-end driver: loss falls, a second invocation resumes from
    the checkpoint instead of restarting."""
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig
    from repro.optim.adamw import AdamWConfig
    from repro.train.loop import LoopConfig, train
    from repro.train.step import TrainConfig

    cfg = get_config("qwen3-0.6b", reduced=True)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8,
                      structure=16)
    tcfg = TrainConfig(adam=AdamWConfig(lr=2e-2, warmup_steps=5,
                                        total_steps=40), microbatches=2)
    ckpt = str(tmp_path / "ck")
    _, _, losses = train(cfg, tcfg,
                         LoopConfig(steps=25, ckpt_dir=ckpt, ckpt_every=10,
                                    log_every=100),
                         dcfg, log=lambda *_: None)
    assert losses[-1] < losses[0] - 0.5
    _, _, losses2 = train(cfg, tcfg,
                          LoopConfig(steps=30, ckpt_dir=ckpt, ckpt_every=10,
                                     log_every=100),
                          dcfg, log=lambda *_: None)
    assert len(losses2) == 5           # resumed at 25, ran 5 more


def test_bench_run_exits_nonzero_when_a_figure_fails(monkeypatch, capsys):
    """examples/paper_figures.py prints every figure's error row, keeps
    going, and exits non-zero if any figure failed."""
    path = Path(__file__).resolve().parents[1] / "examples" / "paper_figures.py"
    spec = importlib.util.spec_from_file_location("paper_figures", path)
    figures = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(figures)

    def fig_fine():
        return ["fig_fine,1.0,ok"]

    def fig_broken():
        raise ValueError("boom")

    monkeypatch.setattr(figures, "ALL_FIGS", [fig_broken, fig_fine])
    assert figures.main([]) == 1
    out = capsys.readouterr().out
    assert "fig_broken,0,ERROR:ValueError:boom" in out
    assert "# total wall" in out and "; 1 rows" in out   # fig_fine ran
    monkeypatch.setattr(figures, "ALL_FIGS", [fig_fine])
    assert figures.main([]) == 0
