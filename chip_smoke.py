"""Drive the simulator's main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: phases A, B, C and D
    python chip_smoke.py --chips 4   # only the Study sharded over 4 chips

A  ``perm_1024n_3t``, the paper's 1024-endpoint three-tier permutation, runs
   to completion twice on one compiled simulator.  Every flow must finish
   inside the tick budget and the packet-conservation ledger must close.
B  The same scenario, and ``alltoall_3t`` (31 flows per sender, so the
   round-robin pick runs), with the Pallas kernels compiled for the chip,
   against the jnp backends over the whole final state.  The integer kernels
   (``enqueue_arb``, ``ring_drain``) must match bit for bit; the leaves the
   f32 ``cc_update`` kernel changes are printed, not checked.
C  An 8-lane ``perm_512n_3t`` Study: every lane must finish, and lane 0 must
   equal the standalone run of seed 0.
D  Whether ``perm_1024n_3t`` at its fixture budget reproduces the digest
   recorded on the CPU in ``tests/data/scenario_digests.json`` (printed only).

``--chips 4`` runs only the Study of phase C with its lanes sharded over four
chips, against the same Study on one device: the final states must be equal
and the lanes must land on four devices.

Everything runs in this one process, and any failed check raises.  The script
refuses a machine whose first JAX device is not a TPU.  Wall times are printed
for information.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.kernels import interpret_mode  # noqa: E402
from repro.netsim import api, cache, scenarios, shard, state  # noqa: E402
from repro.netsim.metrics import conservation_ledger  # noqa: E402

PALLAS_INT = dict(fabric_backend="pallas", transport_backend="pallas")
PALLAS_ALL = dict(PALLAS_INT, cc_backend="pallas")
DIGESTS = ROOT / "tests" / "data" / "scenario_digests.json"


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def differing_leaves(a, b) -> list:
    """Paths of the leaves whose dtype, shape or bytes differ between two
    host state pytrees of the same structure."""
    out = []
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape \
                or x.tobytes() != y.tobytes():
            out.append(jax.tree_util.keystr(path))
    return out


def timed_run(sim, max_ticks: int, seed: int = 0):
    """``(final host state, wall seconds)`` of one ``Sim.run``."""
    t0 = time.perf_counter()
    st = sim.run(max_ticks=max_ticks, seed=seed)
    st.now.block_until_ready()
    return jax.device_get(st), time.perf_counter() - t0


def kernel_calls(sim) -> int:
    """Compiled Pallas kernels in one lowered tick (0 when interpreted)."""
    return jax.jit(sim.step).lower(
        state.ring_loop_form(sim.init())).as_text().count(
        "tpu_custom_call")


def phase_a(sc):
    """Run ``sc`` to completion twice on one compiled simulator.
    Returns ``(sim, final host state)``."""
    sim = sc.build()
    st, first = timed_run(sim, sc.max_ticks)
    again, second = timed_run(sim, sc.max_ticks)
    res = api.RunResult.from_state(sim, st, scenario=sc.name,
                                   max_ticks=sc.max_ticks)
    sent, accounted = conservation_ledger(sim.dims, st)
    print(f"A {sc.name}: first call {first:.3f} s (compile included), "
          f"second call {second:.3f} s, {res.ticks} ticks, "
          f"{res.n_done}/{res.n_flows} flows done, "
          f"{sent} packets sent / {accounted} accounted")
    check(res.all_done and res.ticks < sc.max_ticks,
          f"{sc.name}: {res.n_done}/{res.n_flows} flows done at tick "
          f"{res.ticks} of {sc.max_ticks}")
    check(sent == accounted, f"{sc.name}: conservation ledger open "
          f"({sent} sent, {accounted} accounted)")
    check(not differing_leaves(st, again),
          f"{sc.name}: the second run differs from the first")
    return sim, st


def phase_b(sc, st_jnp=None) -> dict:
    """Pallas backends against jnp on ``sc`` (``st_jnp``: its jnp final
    state, run here when not given).  Returns the kernel count per tick
    and the leaves that differ with all three kernels on."""
    if st_jnp is None:
        st_jnp, _ = timed_run(sc.build(), sc.max_ticks)
    sim = sc.with_(**PALLAS_INT).build()
    st, wall = timed_run(sim, sc.max_ticks)
    diff = differing_leaves(st_jnp, st)
    print(f"B {sc.name} (FMAX={sim.dims.FMAX}): enqueue_arb + ring_drain "
          f"pallas, {kernel_calls(sim)} kernels per tick, {wall:.3f} s: "
          f"{'bit-identical to jnp' if not diff else f'differs in {diff}'}")
    check(not diff, f"{sc.name}: integer kernels differ from jnp in {diff}")

    sim = sc.with_(**PALLAS_ALL).build()
    st, wall = timed_run(sim, sc.max_ticks)
    diff = differing_leaves(st_jnp, st)
    calls = kernel_calls(sim)
    print(f"B {sc.name}: cc_update + enqueue_arb + ring_drain pallas, "
          f"{calls} kernels per tick, {wall:.3f} s: "
          + (f"{len(diff)} state leaves differ from jnp: {diff}" if diff
             else "bit-identical to jnp"))
    return dict(kernel_calls=calls, cc_diff=diff)


def phase_c(sc, seeds) -> str:
    """A ``len(seeds)``-lane Study of ``sc``; lane 0 against the standalone
    run of ``seeds[0]``.  Returns the Study's final-state digest."""
    study = api.study(sc, seeds=seeds)
    res = study.run()
    solo = api.run(sc, seed=seeds[0])
    lane0 = jax.tree.map(lambda x: x[0], res.states)
    diff = differing_leaves(solo.state, lane0)
    done = sum(r.all_done for r in res)
    print(f"C {sc.name}: {len(res)}-lane Study {res.wall_s:.3f} s "
          f"(compile included), {done}/{len(res)} lanes finished, lane 0 "
          f"{'equals' if not diff else 'differs from'} the standalone run")
    check(done == len(res), f"{sc.name}: only {done}/{len(res)} lanes "
          f"finished")
    check(not diff, f"{sc.name}: lane 0 differs from the standalone run "
          f"in {diff}")
    return cache.state_digest(res.states)


def cpu_digest(sim, name: str) -> bool:
    """Does ``name`` at its fixture budget reproduce the recorded CPU
    digest (computed as tests/test_collectives.py computes it)?"""
    doc = json.loads(DIGESTS.read_text())
    st, _ = timed_run(sim, doc["budgets"][name], seed=doc["seed"])
    same = cache.state_digest(st) == doc["digests"][name]
    print(f"D {name} at {doc['budgets'][name]} ticks "
          f"{'reproduces' if same else 'does not reproduce'} the digest "
          f"recorded on jax {doc['env']['jax']} "
          f"{doc['env']['platform']} CPU")
    return same


def phase_sharded(sc, seeds, devices) -> str:
    """The Study of ``sc`` with its lanes sharded over ``devices`` against
    the same Study on one device.  Returns the common digest."""
    study = api.study(sc, seeds=seeds)
    t0 = time.perf_counter()
    one = jax.device_get(study.run_states())
    wall_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = study.run_states(mesh=shard.lane_mesh(devices))
    out.now.block_until_ready()
    wall_many = time.perf_counter() - t0
    placed = {s.device for s in out.now.addressable_shards}
    out = jax.device_get(out)
    d_one, d_many = cache.state_digest(one), cache.state_digest(out)
    lanes_done = int(np.all(out.done, axis=1).sum())
    print(f"S {sc.name}: {len(seeds)} lanes on 1 device {wall_one:.3f} s, "
          f"on {len(placed)} devices {wall_many:.3f} s (compile included), "
          f"{lanes_done}/{len(seeds)} lanes finished, digests "
          f"{d_one[:16]} / {d_many[:16]}")
    check(placed == set(devices), f"lanes landed on "
          f"{sorted(map(str, placed))}, not on {sorted(map(str, devices))}")
    check(lanes_done == len(seeds), f"only {lanes_done} lanes finished")
    check(d_one == d_many, "sharded Study differs from the one-device Study")
    return d_many


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the Study sharded over four chips")
    args = p.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} seen",
              file=sys.stderr)
        return 1
    use_compile_cache()
    print(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"Pallas interpret={interpret_mode()}")

    if args.chips == 4:
        phase_sharded(scenarios.scenario("perm_512n_3t"), tuple(range(8)),
                      devices[:4])
    else:
        perm = scenarios.scenario("perm_1024n_3t")
        sim, st = phase_a(perm)
        for sc, st_jnp in ((perm, st),
                           (scenarios.scenario("alltoall_3t"), None)):
            b = phase_b(sc, st_jnp)
            check(b["kernel_calls"] > 0,
                  f"{sc.name}: no compiled Pallas kernel in the tick")
        phase_c(scenarios.scenario("perm_512n_3t"), tuple(range(8)))
        cpu_digest(sim, perm.name)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
