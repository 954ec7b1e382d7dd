"""Record a small trace with the program's scopes, for the tests of
``scope_reduce``.

    python3 bench/record_scoped_trace.py --out bench/data/trace_tiny_scoped.json

On a TPU, runs the traced slice of a ``runs`` cell on an 8-host fat tree
(two whole runs) and writes what ``bench/record_trace.py`` writes (the
operations and the ``bench.`` spans, with ``trace_reduce.reduce`` of
them), and beside it what the scoped split needs: the ``netsim.`` spans,
the ``XLA Modules`` events, the scope of each run-loop operation (from
the compiled loop's HLO text), the counters of the slice's first salt
re-run with ``counters=True``, and ``scope_reduce.split`` of it all.
``tests/bench/test_bench_scopes.py`` checks the split against it.
"""

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

TINY = {"racks": 4, "nodes_per_rack": 2, "uplinks": 2, "pods": 2,
        "core_uplinks": 2}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)

    import jax

    import harness
    import scope_reduce
    import trace_reduce
    from repro.netsim import engine

    harness.use_compile_cache()
    cell = harness.load_cell("perm1024.run")
    cell.config["tree"] = dict(TINY)
    cell.config["flows"]["size_bytes"] = 16 * 1024
    devices = harness.check_devices(1)
    mix = harness.RunsMix(cell, 1, harness.Spans(), devices)
    mix.iteration(keep=False)
    salts = harness.salts(1, "runs", 3)[1:]
    sim = mix.sim
    text = engine._run_until_done.lower(
        sim.step_fn, sim.horizon_fn if sim.dims.leap else None, sim.consts,
        jax.eval_shape(sim.init), mix.max_ticks,
        sim.dims.superstep).compile().as_text()
    scopes = scope_reduce.hlo_scopes(text)
    mix.salts = iter(salts)
    events, its = scope_reduce._traced(mix, 2)
    counters = scope_reduce._counted(sim, mix.max_ticks, salts[0])

    ops, mods = events["ops"], events["modules"]
    bench_spans = [s for s in events["spans"] if s[0].startswith("bench.")]
    prog_spans = [s for s in events["spans"] if s[0].startswith("netsim.")]
    names = sorted({o[1] for o in ops})
    idx = {n: i for i, n in enumerate(names)}
    t0 = min([o[2] for o in ops] + [s[1] for s in events["spans"]]
             + [m[2] for m in mods])
    rel = lambda spans: [[n, s - t0, e - t0] for n, s, e in spans]
    out = {"source": "bench/record_scoped_trace.py on one " +
           jax.devices()[0].device_kind + " chip: two single runs of an "
           "8-host fat tree (times in ns from the first event)",
           "device_kind": jax.devices()[0].device_kind, "names": names,
           "ops": [[d, idx[n], s - t0, e - t0] for d, n, s, e in ops],
           "spans": rel(bench_spans),
           "reduced": trace_reduce.reduce(
               {"ops": ops, "spans": bench_spans}),
           "program_spans": rel(prog_spans),
           "modules": [[d, n, s - t0, e - t0] for d, n, s, e in mods],
           "scopes": {n: scopes[n] for n in names if n in scopes},
           "ticks": [sum(it["ticks"]) for it in its],
           "counters": counters,
           "scoped": scope_reduce.split(events, scopes)}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, separators=(",", ":")))
    print(json.dumps({"ops": len(ops), "modules": len(mods),
                      "scoped": out["scoped"], "counters": counters}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
