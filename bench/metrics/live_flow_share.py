"""Percent of the run loop's flow slots that are live on a stepped tick:
the counters' ``flow_ticks_live`` (flows ``sender.activated`` holds live,
summed over the ticks that stepped) over ``ticks_executed`` times the
slots the loop carries, for the traced slice's first salt re-run with
``Sim.run(..., counters=True)``, untraced and outside the window
(``scope_reduce``).  The slots are the built simulator's ``dims.NF``, not
the configuration's flow count.  Nothing where the program does not count
live flows."""

import harness
import scope_reduce


def read(rec):
    _, counters = scope_reduce.measured(rec)
    if not counters or not counters["ticks_executed"] or \
            "flow_ticks_live" not in counters:
        return None
    frame = scope_reduce._run_cell_locals(rec)
    if frame is None:
        return None
    cfg = rec.cell.config
    sc = harness.scenario(cfg, harness.flow_table(cfg, frame["seed"]),
                          rec.cell.name)
    slots = sc.build().dims.NF
    return 100.0 * counters["flow_ticks_live"] / \
        (counters["ticks_executed"] * slots)
