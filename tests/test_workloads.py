"""Workload generators: alltoall pair coverage and engine-level window
semantics, permutation derangement properties, and the sparse/heavy-tailed
generators feeding the leap scenarios."""

import numpy as np
import pytest

from repro.netsim import workloads
from repro.netsim.engine import SimConfig, build
from repro.netsim.units import FatTreeConfig, LinkConfig

SMALL = FatTreeConfig(racks=2, nodes_per_rack=4, uplinks=4)
LINK = LinkConfig()


# ---------------------------------------------------------------- alltoall


def test_alltoall_covers_all_pairs_once():
    n = 6
    wl = workloads.alltoall(SMALL, size_bytes=4 * 4096, window=3, nodes=n)
    pairs = set(zip(wl.src.tolist(), wl.dst.tolist()))
    assert len(pairs) == wl.n_flows == n * (n - 1)
    assert pairs == {(s, d) for s in range(n) for d in range(n) if s != d}
    # per-source order is the 0..n-2 schedule the window gate keys on
    for s in range(n):
        assert sorted(wl.order[wl.src == s].tolist()) == list(range(n - 1))
    assert wl.window == 3


def test_alltoall_window_limits_concurrency():
    """Engine-level window semantics: with window=w, at most w flows of a
    source are in progress (delivered some but not all bytes) at any tick,
    and a flow's successors only start as predecessors finish; yet all
    pairs are eventually issued and complete."""
    n, w, pkts = 6, 2, 4
    size = pkts * 4096
    wl = workloads.alltoall(SMALL, size_bytes=size, window=w, nodes=n)
    sim = build(SimConfig(link=LINK, tree=SMALL), wl)
    nsrc0 = n - 1                           # flows 0..n-2 belong to source 0
    ticks = 4000
    _, ys = sim.run_trace(ticks, trace_flows=nsrc0)
    g = np.asarray(ys["goodput"])           # [ticks, n-1], source 0's flows
    assert g[-1].min() == size              # all of source 0's pairs issued

    in_progress = (g > 0) & (g < size)
    assert in_progress.sum(axis=1).max() <= w

    # order-w flow must not deliver before some predecessor finished
    first_byte = np.argmax(g > 0, axis=0)          # first tick with data
    done_tick = np.argmax(g >= size, axis=0)
    assert first_byte[w] > min(done_tick[:w])

    # full run completes every pair
    st = sim.run(max_ticks=200000)
    assert bool(np.asarray(st.done).all())
    np.testing.assert_array_equal(np.asarray(st.goodput), wl.size)


def test_alltoall_window_one_serializes_each_source():
    """window=1 degenerates to one flow at a time per source: completion
    times are strictly ordered by the per-source schedule."""
    n = 5
    wl = workloads.alltoall(SMALL, size_bytes=2 * 4096, window=1, nodes=n)
    sim = build(SimConfig(link=LINK, tree=SMALL), wl)
    st = sim.run(max_ticks=200000)
    assert bool(np.asarray(st.done).all())
    fct = np.asarray(st.fct) + wl.t_start
    for s in range(n):
        mask = wl.src == s
        by_order = fct[mask][np.argsort(wl.order[mask], kind="stable")]
        assert np.all(np.diff(by_order) > 0), (s, by_order)


# ------------------------------------------------------------- permutation


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_permutation_is_derangement(seed):
    wl = workloads.permutation(SMALL, size_bytes=4 * 4096, seed=seed,
                               cross_rack=False)
    assert sorted(wl.dst.tolist()) == list(range(SMALL.n_nodes))
    assert np.all(wl.dst != wl.src)


@pytest.mark.parametrize("seed", [0, 5, 9])
def test_cross_rack_permutation_crosses_the_core(seed):
    m = SMALL.nodes_per_rack
    wl = workloads.permutation(SMALL, size_bytes=4 * 4096, seed=seed,
                               cross_rack=True)
    assert np.all(wl.dst // m != wl.src // m)
    assert sorted(wl.dst.tolist()) == list(range(SMALL.n_nodes))


def test_multi_permutation_stacks_independent_rounds():
    wl = workloads.permutation(SMALL, size_bytes=4 * 4096, seed=1, n_perms=3)
    n = SMALL.n_nodes
    assert wl.n_flows == 3 * n
    for p in range(3):
        sl = slice(p * n, (p + 1) * n)
        assert np.all(wl.dst[sl] != wl.src[sl])
        assert np.all(wl.order[sl] == p)


# ------------------------------------------------ sparse / heavy-tailed


def test_heavy_tailed_shape_and_sparsity():
    wl = workloads.heavy_tailed(SMALL, 64, size_base=16 * 1024,
                                size_cap=512 * 1024, gap_mean=500.0, seed=0)
    assert np.all(wl.src != wl.dst)
    assert np.all((wl.src >= 0) & (wl.src < SMALL.n_nodes))
    assert np.all((wl.size >= 1) & (wl.size <= 512 * 1024))
    assert wl.size.max() > 4 * wl.size.min()       # the tail is heavy
    assert wl.t_start[0] == 0
    assert np.all(np.diff(wl.t_start) >= 0)        # arrivals in time order
    # sparse: mean inter-arrival near the requested gap (law of large nums)
    mean_gap = float(wl.t_start[-1]) / (wl.n_flows - 1)
    assert 250.0 < mean_gap < 1000.0


def test_heavy_tailed_seed_reproducible():
    a = workloads.heavy_tailed(SMALL, 16, seed=7)
    b = workloads.heavy_tailed(SMALL, 16, seed=7)
    c = workloads.heavy_tailed(SMALL, 16, seed=8)
    np.testing.assert_array_equal(a.size, b.size)
    np.testing.assert_array_equal(a.t_start, b.t_start)
    assert not np.array_equal(a.size, c.size)


# -------------------------------------------------------------- validate


def _table(**overrides):
    base = dict(
        name="t", src=np.array([0, 1, 2], np.int32),
        dst=np.array([4, 5, 6], np.int32),
        size=np.array([4096, 8192, 4096], np.int32),
        t_start=np.array([0, 10, 20], np.int32),
        order=np.zeros(3, np.int32))
    base.update(overrides)
    return workloads.Workload(**base)


def test_validate_accepts_good_tables_and_chains():
    wl = _table()
    assert wl.validate(n_nodes=SMALL.n_nodes) is wl
    # every generator in this module produces a valid table
    for gen in (workloads.incast(SMALL, degree=4, size_bytes=4096),
                workloads.permutation(SMALL, size_bytes=4096),
                workloads.alltoall(SMALL, size_bytes=4096, window=2, nodes=4),
                workloads.heavy_tailed(SMALL, 8),
                workloads.staggered_large(SMALL, 3, 4096, 100)):
        gen.validate(n_nodes=SMALL.n_nodes)


def test_validate_rejects_self_talk_with_flow_index():
    wl = _table(dst=np.array([4, 1, 6], np.int32))       # flow 1: src == dst
    with pytest.raises(ValueError, match=r"\[1\].*src == dst"):
        wl.validate()


def test_validate_rejects_bad_sizes_and_starts():
    with pytest.raises(ValueError, match="non-positive size"):
        _table(size=np.array([4096, 0, 4096], np.int32)).validate()
    with pytest.raises(ValueError, match="negative t_start"):
        _table(t_start=np.array([0, -5, 20], np.int32)).validate()


def test_validate_rejects_out_of_range_nodes():
    with pytest.raises(ValueError, match="different topology"):
        _table(dst=np.array([4, 5, 99], np.int32)).validate(n_nodes=8)
    with pytest.raises(ValueError, match="different topology"):
        _table(src=np.array([-1, 1, 2], np.int32)).validate()


def test_validate_rejects_misaligned_and_empty_tables():
    with pytest.raises(ValueError, match="must align"):
        _table(size=np.array([4096, 4096], np.int32)).validate()
    with pytest.raises(ValueError, match="empty flow table"):
        _table(src=np.zeros(0, np.int32), dst=np.zeros(0, np.int32),
               size=np.zeros(0, np.int32), t_start=np.zeros(0, np.int32),
               order=np.zeros(0, np.int32)).validate()


def test_validate_rejects_windowed_start_order_mismatch():
    """With an active eligibility window, a later-ordered flow that starts
    earlier than its predecessor would sit blocked past its start tick —
    reject with the offending sender/flows named."""
    wl = _table(src=np.array([0, 0, 0], np.int32),
                dst=np.array([4, 5, 6], np.int32),
                t_start=np.array([0, 20, 10], np.int32),
                order=np.array([0, 1, 2], np.int32), window=2)
    with pytest.raises(ValueError, match="windowed sender 0"):
        wl.validate()
    # same table without windowing is fine (start order is free)
    _table(src=np.array([0, 0, 0], np.int32),
           t_start=np.array([0, 20, 10], np.int32),
           order=np.array([0, 1, 2], np.int32)).validate()
    # a decrease among a sender's first `window` flows is fine — those
    # can never accumulate `window` unfinished predecessors
    _table(src=np.array([0, 0, 0], np.int32),
           t_start=np.array([20, 10, 30], np.int32),
           order=np.array([0, 1, 2], np.int32), window=2).validate()
    # a sender the window cannot gate (<= window flows) may start in any
    # order, even while another sender's flow count activates windowing
    workloads.Workload(
        name="t", src=np.array([0, 0, 0, 1, 1], np.int32),
        dst=np.array([4, 5, 6, 7, 4], np.int32),
        size=np.full(5, 4096, np.int32),
        t_start=np.array([0, 10, 20, 30, 5], np.int32),
        order=np.array([0, 1, 2, 0, 1], np.int32), window=2).validate()


def test_engine_rejects_invalid_workload_via_derive():
    wl = _table(dst=np.array([0, 5, 6], np.int32))       # flow 0: src == dst
    with pytest.raises(ValueError, match="src == dst"):
        build(SimConfig(link=LINK, tree=SMALL), wl)


def test_staggered_large_disjoint_and_spaced():
    wl = workloads.staggered_large(SMALL, 4, 64 * 4096, gap_ticks=1000,
                                   seed=0)
    assert len(set(wl.src.tolist())) == 4          # distinct senders
    assert np.all(wl.src != wl.dst)
    m = SMALL.nodes_per_rack
    assert np.all(wl.dst // m != wl.src // m)      # cross-rack transfers
    np.testing.assert_array_equal(wl.t_start, 1000 * np.arange(4))
    with pytest.raises(ValueError):
        workloads.staggered_large(SMALL, SMALL.n_nodes, 4096, 10)
