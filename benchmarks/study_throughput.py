"""Fleet-scale Study throughput: lanes/sec against the number of devices
the lanes are sharded over, plus cold/warm result-cache wall time
(DESIGN.md Sec. 7).

The parent never imports JAX: it starts ONE worker process and writes
the ledger.  The worker builds a lane mesh over ``jax.devices()[:d]``
for each requested ``d`` (capped at the devices it sees) and then runs
the cache measurement, all in that one process, so one process holds the
chip.  The worker is started with
``--xla_force_host_platform_device_count=max(d)``: on the CPU that gives
it ``max(d)`` host devices to shard over; the flag touches only the CPU
backend, so on a TPU the worker shards over the chips it sees.

Two row families land in ``BENCH_netsim.json`` under
``sections.study_throughput``, each naming the device it ran on:

- ``<scenario>/d<D>``: one Study (base point x S seeds) sharded over D
  devices — steady-state (post-compile) wall, lanes/sec, and the full
  final-state pytree digest.  The parent *hard-fails* unless every D
  produces the same digest as the first: bit-identical sharding is an
  acceptance property, not a perf number.
- ``<scenario>/cache/{cold,warm}``: the same Study run against a fresh
  content-addressed cache (cold: every lane computed + written back)
  and then re-run (warm: every lane a hit, zero recomputed).  The warm
  row records ``speedup_vs_cold``; the acceptance floor is 10x.

Usage:
  PYTHONPATH=src python -m benchmarks.study_throughput            # full
  PYTHONPATH=src python -m benchmarks.study_throughput --quick    # CI
      [--scenario NAME] [--seeds N] [--devices 1,2,4,8]
      [--json-path PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MARK = "STUDY_THROUGHPUT_RESULT "


# --------------------------------------------------------------------------
# worker side (the one process that touches JAX)
# --------------------------------------------------------------------------


def _worker(scenario: str, n_seeds: int, devices: list) -> dict:
    import jax

    from repro.compile_cache import use_compile_cache
    from repro.netsim import api, cache, shard

    use_compile_cache()
    devs = jax.devices()
    st = api.study(scenario, seeds=tuple(range(n_seeds)))
    shard_rows = []
    for d in sorted({min(d, len(devs)) for d in devices}):
        mesh = shard.lane_mesh(devs[:d]) if d > 1 else None
        first = st.run(mesh=mesh)           # compile + run
        steady = st.run(mesh=mesh)          # reuses the jit cache
        shard_rows.append(dict(
            devices=d, lanes=st.n_lanes,
            wall_first_s=round(first.wall_s, 4),
            wall_s=round(steady.wall_s, 4),
            lanes_per_sec=round(st.n_lanes / steady.wall_s, 3),
            digest=cache.state_digest(steady.states)))

    root = tempfile.mkdtemp(prefix="netsim_cache_bench_")
    try:
        rc = cache.ResultCache(root)
        cold = st.run(cache=rc)
        warm = st.run(cache=rc)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return dict(
        platform=devs[0].platform, device_kind=devs[0].device_kind,
        shard=shard_rows,
        cache=dict(
            lanes=st.n_lanes,
            cold_wall_s=round(cold.wall_s, 4),
            warm_wall_s=round(warm.wall_s, 4),
            cold_hits=cold.cache_hits, cold_misses=cold.cache_misses,
            warm_hits=warm.cache_hits, warm_misses=warm.cache_misses,
            speedup=round(cold.wall_s / max(warm.wall_s, 1e-9), 2),
            cold_digest=cache.state_digest(cold.states),
            warm_digest=cache.state_digest(warm.states)))


def _run_worker(scenario: str, n_seeds: int, devices: list) -> dict:
    """Launch the measurement process and parse its
    ``STUDY_THROUGHPUT_RESULT`` line."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count="
                        f"{max(devices)}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO_ROOT, "src"),
                    env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, "-m", "benchmarks.study_throughput", "--worker",
           "--scenario", scenario, "--seeds", str(n_seeds),
           "--devices", ",".join(map(str, devices))]
    proc = subprocess.run(cmd, cwd=REPO_ROOT, env=env, text=True,
                          capture_output=True, timeout=3600)
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(
        f"worker produced no result line (exit {proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")


# --------------------------------------------------------------------------
# parent side
# --------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="CI smoke: tiny scenario, D in {1,2}")
    p.add_argument("--scenario", default=None)
    p.add_argument("--seeds", type=int, default=None)
    p.add_argument("--devices", default=None,
                   help="comma-separated device counts")
    p.add_argument("--json-path", default=None)
    p.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    scenario = args.scenario or ("tiny_3t" if args.quick else "perm_512n_3t")
    n_seeds = args.seeds or (3 if args.quick else 8)
    devices = ([int(d) for d in args.devices.split(",")] if args.devices
               else ([1, 2] if args.quick else [1, 2, 4, 8]))

    if args.worker:
        print(_MARK + json.dumps(_worker(scenario, n_seeds, devices)))
        return 0

    t0 = time.time()
    w = _run_worker(scenario, n_seeds, devices)
    # imported only now: benchmarks.common imports JAX, and the parent
    # must not touch it while the worker holds the device
    from benchmarks.common import emit, write_bench_json

    where = dict(platform=w["platform"], device_kind=w["device_kind"])
    rows = []
    base_digest = None
    for r in w["shard"]:
        name = f"{scenario}/d{r['devices']}"
        rows.append(dict(name=name, scenario=scenario, **where,
                         devices=r["devices"], lanes=r["lanes"],
                         wall_s=r["wall_s"], wall_first_s=r["wall_first_s"],
                         lanes_per_sec=r["lanes_per_sec"],
                         digest=r["digest"]))
        emit(name, r["wall_s"], f"{r['lanes_per_sec']:.2f} lanes/s on "
             f"{r['devices']} {w['device_kind']}")
        if base_digest is None:
            base_digest = r["digest"]
        elif r["digest"] != base_digest:
            print(f"::error title=shard parity::{name} final-state digest "
                  f"{r['digest'][:12]} != {rows[0]['name']} "
                  f"{base_digest[:12]} — sharded run is NOT bit-identical")
            raise SystemExit(1)
    print(f"# shard parity: {len(w['shard'])} device counts, one digest "
          f"{base_digest[:12]}…")

    c = w["cache"]
    if c["cold_digest"] != c["warm_digest"] or \
            c["cold_digest"] != base_digest:
        print("::error title=cache parity::cold/warm digests diverge from "
              "the uncached run")
        raise SystemExit(1)
    if c["warm_misses"] != 0:
        print(f"::error title=cache resume::warm run recomputed "
              f"{c['warm_misses']} lane(s); expected 0")
        raise SystemExit(1)
    rows.append(dict(name=f"{scenario}/cache/cold", scenario=scenario,
                     **where, lanes=c["lanes"], wall_s=c["cold_wall_s"],
                     lanes_per_sec=round(c["lanes"] / c["cold_wall_s"], 3),
                     cache_hits=c["cold_hits"],
                     cache_misses=c["cold_misses"]))
    rows.append(dict(name=f"{scenario}/cache/warm", scenario=scenario,
                     **where, lanes=c["lanes"], wall_s=c["warm_wall_s"],
                     lanes_per_sec=round(c["lanes"] / c["warm_wall_s"], 3),
                     cache_hits=c["warm_hits"],
                     cache_misses=c["warm_misses"],
                     speedup_vs_cold=c["speedup"]))
    emit(f"{scenario}/cache/cold", c["cold_wall_s"],
         f"{c['cold_misses']} lanes computed")
    emit(f"{scenario}/cache/warm", c["warm_wall_s"],
         f"{c['warm_hits']} hits, {c['speedup']}x vs cold")
    if c["speedup"] < 10.0:
        print(f"::warning title=cache speedup::warm cache only "
              f"{c['speedup']}x faster than cold (acceptance floor: 10x)")

    path = write_bench_json("study_throughput", rows, path=args.json_path,
                            meta=dict(scenario=scenario, seeds=n_seeds,
                                      **where,
                                      note="one worker process; lanes "
                                           "sharded over jax.devices()[:d]"))
    print(f"# wrote {len(rows)} rows to {path} in {time.time()-t0:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
