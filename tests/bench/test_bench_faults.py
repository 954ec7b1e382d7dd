"""A run of a cell with the timed path broken underneath must come out
not correct.  The harness's look for a chip is skipped (the CPU stands
in, at a small size); everything else is the benchmark's own run:
set-up, window, sampling from the seed, the reference, the verdict.

Faults of the run loop, ``engine._run_until_done``: it returns its state
unchanged; it leaves half of the flows out (their per-flow state stays as
it started); one number of the answer is altered where it is produced.
(No cell has an exchange between chips to leave out.)"""

import time

import jax
import jax.numpy as jnp
import pytest

import harness

SMALL = dict(racks=4, nodes_per_rack=4, uplinks=1, pods=2, core_uplinks=1)


def small_cell():
    """``perm1024.run``'s configuration on a 16-host tree."""
    cell = harness.load_cell("perm1024.run")
    cell.config["tree"] = dict(SMALL)
    cell.config["flows"]["size_bytes"] = 64 * 1024
    return cell


def run(cell):
    return harness.run_cell(cell, 2**33 + 17, 0.3, False, time.perf_counter(),
                            devices=jax.devices()[:1])


def _state(args, kw):
    return args[3] if len(args) > 3 else kw["state0"]


def unchanged(orig):
    del orig

    def loop(*args, **kw):
        return jax.tree.map(jnp.copy, _state(args, kw))
    return loop


def half_flows(orig):
    def loop(*args, **kw):
        init = jax.tree.map(jnp.copy, _state(args, kw))
        out = orig(*args, **kw)
        nf = out.done.shape[0]

        def keep_half(o, i):
            if o.ndim and o.shape[0] == nf:
                return jnp.concatenate([o[:nf // 2], i[nf // 2:]])
            return o
        return jax.tree.map(keep_half, out, init)
    return loop


def altered(orig):
    def loop(*args, **kw):
        out = orig(*args, **kw)
        cwnd = out.cc.cwnd
        cwnd = cwnd.at[..., 0].add(1.0)
        return out._replace(cc=out.cc._replace(cwnd=cwnd))
    return loop


def test_sound_runs_are_correct():
    out = run(small_cell())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_single_run_faults(monkeypatch, fault):
    from repro.netsim import engine
    orig = engine._run_until_done
    patch = {"unchanged": unchanged, "half": half_flows,
             "altered": altered}[fault](orig)
    monkeypatch.setattr(engine, "_run_until_done", patch)
    out = run(small_cell())
    assert not out["correct"], out["checks"]
