"""Gates on simulated results: each run must finish no later than a
recorded value times a tolerance, and the paper-scale tick's traced
scatter and gather counts must not grow.

The recorded values are simulated ticks (or traced op counts), so they
are the same on every machine.  A gate that trips means the simulator's
outcome or the tick's op structure changed: record the new value here in
the same change, with the reason, if the change is intended.
"""

import pytest

from repro.analysis import audit
from repro.netsim import api, scenarios

# failover completion (simulated ticks): corefail_128n_3t on smartt, seed
# 0, without and with the failure-recovery knobs (capped RTO backoff +
# REPS entropy eviction on timeout).  Without them one flow of 128 stays
# stranded, so the completion is the last of the 127 that finished.
RECOVERY = dict(rto_backoff_max=2, evict_on_timeout=True)
FAILOVER_SLACK = 1.30


@pytest.mark.parametrize("recovery,recorded", [
    (False, 1863),   # row corefail_128n_3t/smartt/smartt+reps[base]/s0
    (True, 3632),    # row corefail_128n_3t+recovery/smartt/smartt+reps[base]/s0
], ids=["corefail_128n_3t", "corefail_128n_3t+recovery"])
def test_failover_completion_within_recorded(recovery, recorded):
    r = api.run("corefail_128n_3t", algo="smartt",
                **(RECOVERY if recovery else {}))
    assert 0 <= r.completion <= FAILOVER_SLACK * recorded, (
        f"completion {r.completion} vs recorded {recorded} "
        f"({r.n_done}/{r.n_flows} flows done)")


# collective completion time (simulated ticks) of the tiny collectives on
# smartt, seed 0
CCT_SLACK = 1.10


@pytest.mark.parametrize("name,recorded", [
    ("tiny_allreduce_ring", 217),   # row tiny_allreduce_ring/smartt/.../s0
    ("tiny_allgather", 57),         # row tiny_allgather/smartt/.../s0
    ("tiny_pipeline", 44),          # row tiny_pipeline/smartt/.../s0
])
def test_collective_cct_within_recorded(name, recorded):
    r = api.run(name, algo="smartt")
    assert 0 <= r.cct <= CCT_SLACK * recorded, (
        f"{name}: CCT {r.cct} vs recorded {recorded}")


# Study completion: the incast8_16n smartt tuning grid (initial window x
# reaction granularity, plus two RED threshold variants), seed 0, as one
# Study.  Each point's recorded completion (simulated ticks) comes from
# the row incast8_16n/smartt+reps[<point>]/s0.
STUDY_SLACK = 1.30
STUDY_GRID = (
    ({"start_cwnd_mult": 0.5, "react_every": 1}, 575),
    ({"start_cwnd_mult": 0.5, "react_every": 4}, 564),
    ({"start_cwnd_mult": 1.0, "react_every": 1}, 556),
    ({"start_cwnd_mult": 1.0, "react_every": 4}, 564),
    ({"start_cwnd_mult": 1.25, "react_every": 1}, 622),
    ({"start_cwnd_mult": 1.25, "react_every": 4}, 635),
    ({"kmin_frac": 0.1, "kmax_frac": 0.4}, 616),
    ({"kmin_frac": 0.3, "kmax_frac": 0.9}, 613),
)


def test_study_grid_completion_within_recorded():
    res = api.study("incast8_16n", points=[p for p, _ in STUDY_GRID],
                    seeds=(0,), algo="smartt", max_ticks=60_000).run()
    assert len(res) == len(STUDY_GRID)
    over = [(r.name, r.completion, recorded)
            for r, (_, recorded) in zip(res, STUDY_GRID)
            if not 0 <= r.completion <= STUDY_SLACK * recorded]
    assert over == [], over


# scatter and gather ops in the traced jnp programs of perm_512n_3t (the
# jaxpr auditor's rows perm_512n_3t/jnp/<program>), recorded under jax
# 0.9.0.  Trace only: nothing is compiled.
OP_COUNTS = {
    "arrivals": dict(scatter_ops=7, gather_ops=11),
    "sends": dict(scatter_ops=2, gather_ops=7),
    "step": dict(scatter_ops=17, gather_ops=24),
}


@pytest.fixture(scope="module")
def perm512_op_rows():
    _, rows = audit.audit_scenario(scenarios.scenario("perm_512n_3t"),
                                   backends=("jnp",))
    return {r["program"]: r for r in rows}


@pytest.mark.parametrize("program", sorted(OP_COUNTS))
def test_op_counts_do_not_grow(perm512_op_rows, program):
    row = perm512_op_rows[program]
    for op, recorded in OP_COUNTS[program].items():
        assert row[op] <= recorded, (
            f"perm_512n_3t/jnp/{program}: {row[op]} {op} vs recorded "
            f"{recorded}")
