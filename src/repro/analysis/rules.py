"""Rule catalogue for the static-analysis layer (DESIGN.md Sec. 10).

Two rule families share one :class:`Finding` currency and one allowlist:

``JX0xx`` — *jaxpr rules*, applied by ``analysis/audit.py`` to traced
(never compiled) engine programs:

  JX001  64-bit leak: a float64/int64/uint64 abstract value inside a
         traced engine program.  The simulator is an x32 program by
         contract (DESIGN.md Sec. 6); any wide dtype doubles memory
         traffic on the hot tick and silently changes CC arithmetic.
  JX002  convert churn: a ``convert_element_type`` whose output feeds
         only another ``convert_element_type`` (an A->B->C chain whose
         middle dtype is never used), or one that converts a value to
         its own dtype.  Either way XLA materializes a useless pass.
  JX003  host callback: ``pure_callback`` / ``io_callback`` /
         ``debug_callback`` inside the step or init.  A callback inside
         the tick serializes the superstep loop on host round-trips.
  JX004  aliased donation: two leaves of a donated pytree share one
         buffer.  ``donate_argnums`` hands each buffer to XLA exactly
         once; an aliased leaf is a use-after-donate.
  JX005  scatter/gather budget: a tick phase exceeds its budgeted
         scatter/gather op count (:data:`PHASE_BUDGETS`).  Scatter count
         is the tick's dominant cost at paper scale (DESIGN.md Sec.
         6.4); a silent regression here is a perf bug.
  JX006  retrace guard: the empirically Dims-changing ``SimConfig``
         fields must be rejected by ``api.apply_point`` (i.e. disjoint
         from ``api.CFG_KEYS``), every ``CFG_KEYS`` field must be
         sweep-safe (same Dims, same Consts avals), and every config
         field must be classified at all.

``JX1xx`` — *AST contract rules*, applied by ``analysis/lint.py`` to
source files (stdlib ``ast``; suppress a line with ``# noqa: JX1xx``):

  JX101  kernel trio parity: ``kernels/*/ref.py`` and ``kernel.py``
         public entry points must agree on positional parameter names
         and order (``ops.py`` dispatches between them blind).
  JX103  unseeded randomness: legacy ``np.random.*`` module calls in
         simulator code (only seeded ``np.random.default_rng`` is
         reproducible across processes).
  JX104  traced truthiness: Python ``if``/``while``/``assert``/bool()
         on ``SimState``/``Consts`` values inside a tick phase module —
         a guaranteed ``TracerBoolConversionError`` at trace time, or
         worse, a silently config-frozen branch.
  JX105  host-path purity: ``jax.numpy`` use in the host-side
         Consts-building modules (topology/units/workloads/scenarios
         and the host half of faults.py).  Those paths run per sweep
         point; device math there re-introduces the per-point dispatch
         cost the Consts design exists to avoid.

Intentional deviations are allowlisted in :data:`ALLOWLIST`, keyed
``"RULE:site:token"`` (``fnmatch`` patterns) -> one-line justification.
An allowlisted finding is reported (with its justification) but does not
fail ``python -m repro.analysis``.
"""

from __future__ import annotations

import dataclasses
from fnmatch import fnmatch

# --------------------------------------------------------------------------
# findings
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at one site.

    ``site``  where: ``scenario/backend/phase`` for jaxpr rules,
              ``path:line`` for lint rules, ``kernels/<name>`` for
              kernel-parity.
    ``token`` the specific offender (a dtype, a primitive, a parameter
              list, a scenario key) — the allowlist matches on it.
    """

    rule: str
    site: str
    token: str
    message: str
    allowed_by: str | None = None   # matching ALLOWLIST key, if any

    @property
    def allowlisted(self) -> bool:
        return self.allowed_by is not None

    def __str__(self) -> str:
        tag = f" [allowed: {ALLOWLIST[self.allowed_by]}]" \
            if self.allowlisted else ""
        return f"{self.rule} {self.site} :: {self.message}{tag}"


RULES = {
    "JX001": "64-bit dtype inside a traced engine program",
    "JX002": "redundant convert_element_type (chain or self-convert)",
    "JX003": "host callback primitive inside step/init",
    "JX004": "aliased leaves in a donated pytree",
    "JX005": "per-phase scatter/gather op count over budget",
    "JX006": "SimConfig sweepability classification drift",
    "JX101": "kernel ref/kernel signature parity",
    "JX103": "unseeded legacy np.random call",
    "JX104": "Python truthiness on traced state in a phase module",
    "JX105": "jax.numpy use in a host-side Consts-building path",
}


# --------------------------------------------------------------------------
# allowlist — every entry is an *intentional* deviation with a reason
# --------------------------------------------------------------------------

ALLOWLIST: dict[str, str] = {
    # cc_update's kernel takes `now` right after the param vector so the
    # scalar-prefetch operands are contiguous; ops.py adapts the order.
    "JX101:kernels/cc_update:*":
        "kernel hoists `now` next to param_vec for scalar prefetch; "
        "ops.py owns the adaptation",
}


def allowed_by(rule: str, site: str, token: str) -> str | None:
    """The first ALLOWLIST key matching (rule, site, token), else None."""
    for key in ALLOWLIST:
        krule, ksite, ktoken = key.split(":", 2)
        if krule == rule and fnmatch(site, ksite) and fnmatch(token, ktoken):
            return key
    return None


def finding(rule: str, site: str, token: str, message: str) -> Finding:
    """Build a Finding, resolving its allowlist status."""
    return Finding(rule=rule, site=site, token=token, message=message,
                   allowed_by=allowed_by(rule, site, token))


# --------------------------------------------------------------------------
# jaxpr rule constants
# --------------------------------------------------------------------------

WIDE_DTYPES = ("float64", "int64", "uint64", "complex128")

CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback")

SCATTER_PRIMITIVES = ("scatter", "scatter-add", "scatter_add",
                      "scatter-mul", "scatter_mul", "scatter-min",
                      "scatter_min", "scatter-max", "scatter_max",
                      "scatter-apply", "scatter_apply",
                      "dynamic_update_slice")

GATHER_PRIMITIVES = ("gather", "dynamic_slice")


# --------------------------------------------------------------------------
# JX005 scatter/gather budgets
# --------------------------------------------------------------------------
#
# Budgets are per (phase, op family) *trace-time op counts* on the jnp
# backend, scenario-independent (op count is shape-independent; only
# Dims branches change it, and the audit covers every registered
# scenario, so the widest branch set is exercised).  Measured maxima
# across the catalogue at PR 9 (departures 4/3, arrivals 7/12, control
# 4/20 — the fault/sparse scenarios' table lookups dominate — grants
# 2/2 with a credit-based CC, sends 2/10, metrics 0/0, horizon 0/4)
# plus ~25% headroom: a breach means someone added
# scatters to a hot phase, which is exactly the regression this rule
# exists to catch.  Raise a budget deliberately — with a ledger diff —
# not by accident.

PHASE_BUDGETS: dict[str, dict[str, int]] = {
    "departures": {"scatter": 5, "gather": 4},
    "arrivals":   {"scatter": 9, "gather": 15},
    "control":    {"scatter": 6, "gather": 25},
    "grants":     {"scatter": 4, "gather": 4},
    "sends":      {"scatter": 3, "gather": 13},
    "metrics":    {"scatter": 1, "gather": 3},
    "horizon":    {"scatter": 1, "gather": 5},
}
