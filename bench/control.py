"""Readings for the limits of ``correct``, on the chip, at a cell's size.

    python3 bench/control.py --workload perm1024.run --seed 7 --samples 3

Runs ``--samples`` single runs through the cell's timed path (salts drawn
from the seed as a benchmark run draws them), then ``harness.check``
compares every final state with the reference twice: in float32, the
configuration's precision (the lower reading: sound runs), and in
bfloat16, the control (the upper reading).  Prints one JSON line per
final state and precision.  Not part of a benchmark run.
"""

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--samples", type=int, default=3)
    args = p.parse_args(argv)

    import jax.numpy as jnp

    import harness

    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    devices = harness.check_devices(cell.chips)
    mix = harness.MIXES[cell.traffic["kind"]](cell, args.seed,
                                              harness.Spans(), devices)
    its = [mix.iteration(keep=True) for _ in range(args.samples)]
    rec = harness.Record(cell=cell, setup_s=0.0, window_s=0.0,
                         iterations=its, spans=[])
    for F, tag in ((jnp.float32, "lower"), (jnp.bfloat16, "upper")):
        verdict = harness.check(cell, mix, args.seed, rec, F=F,
                                samples=args.samples)
        for r in verdict["readings"]:
            print(json.dumps(dict(cell=cell.name, seed=args.seed,
                                  reading=tag, **r)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
