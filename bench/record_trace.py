"""Record a small trace for the tests of the trace reduction.

    python3 bench/record_trace.py --out bench/data/trace_tiny_runs.json

On a TPU, runs the traced slice of a ``runs`` cell on an 8-host fat tree
(two whole runs), and writes the extracted events (operation names in a
table, times from the first event) with what ``trace_reduce.reduce`` made
of them.  ``tests/bench/test_bench_trace.py``
checks the reduction against every ``bench/data/trace_*.json``.
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
    import trace_reduce

    harness.use_compile_cache()
    cell = harness.load_cell("perm1024.run")
    cell.config["tree"] = dict(TINY)
    cell.config["flows"]["size_bytes"] = 16 * 1024
    devices = harness.check_devices(1)
    spans = harness.Spans()
    mix = harness.RunsMix(cell, 1, spans, devices)
    mix.iteration(keep=False)
    events, _ = harness.traced_slice(mix, spans)
    reduced = trace_reduce.reduce(events)
    ops, sp = events["ops"], events["spans"]
    names = sorted({o[1] for o in ops})
    idx = {n: i for i, n in enumerate(names)}
    t0 = min([o[2] for o in ops] + [s[1] for s in sp])
    out = {"source": "bench/record_trace.py on one " +
           jax.devices()[0].device_kind + " chip: two single runs of an "
           "8-host fat tree (times in ns from the first event)",
           "device_kind": jax.devices()[0].device_kind, "names": names,
           "ops": [[d, idx[n], s - t0, e - t0] for d, n, s, e in ops],
           "spans": [[n, s - t0, e - t0] for n, s, e in sp],
           "reduced": reduced}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, separators=(",", ":")))
    print(json.dumps({"ops": len(ops), "spans": len(sp),
                      "reduced": reduced}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
