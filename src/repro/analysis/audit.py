"""Jaxpr auditor: trace the engine (never compile it) and machine-check
its compile-time invariants (DESIGN.md Sec. 10).

For every registered scenario, on each backend, the auditor traces

  * ``state.init`` (the tick-0 build),
  * each of the six tick phases (read off ``Sim.phases`` — the exact
    closures ``engine.build`` composes, so the audit can never drift
    from the real tick),
  * the composed step, and
  * the leap horizon reduction,

then walks the resulting ``ClosedJaxpr``s (recursing into control-flow
and ``pallas_call`` sub-jaxprs) and applies the ``JX00x`` rules from
``analysis/rules.py``: wide-dtype leaks, convert churn, host callbacks,
per-phase scatter/gather budgets.  Donation aliasing (JX004) is checked
eagerly on a real init state — buffer identity, not tracing.  JX006
perturbs every scalar ``SimConfig`` field through ``derive`` and
cross-checks the empirical Dims-impact against ``api.apply_point``'s
accept/reject sets.

Everything here is trace-only: no XLA compile, no device run — auditing
the full catalogue including the 1024-node paper-scale scenarios costs
seconds per scenario, not minutes.
"""

from __future__ import annotations

import dataclasses
import inspect

import jax
import numpy as np

from repro.analysis.rules import (CALLBACK_PRIMITIVES, GATHER_PRIMITIVES,
                                  PHASE_BUDGETS, SCATTER_PRIMITIVES,
                                  WIDE_DTYPES, Finding, finding)

try:  # jax >= 0.4.x
    from jax.extend import core as jex_core
    Jaxpr, ClosedJaxpr = jex_core.Jaxpr, jex_core.ClosedJaxpr
except ImportError:  # pragma: no cover - older jax
    from jax import core as jex_core
    Jaxpr, ClosedJaxpr = jex_core.Jaxpr, jex_core.ClosedJaxpr


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------


def _sub_jaxprs(params: dict):
    """Every Jaxpr/ClosedJaxpr reachable from one eqn's params (cond
    branches arrive as tuples, pallas_call as a bare Jaxpr)."""
    def from_value(v):
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from from_value(item)
    for v in params.values():
        yield from from_value(v)


def walk_eqns(jaxpr):
    """All equations of ``jaxpr``, depth-first through sub-jaxprs."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from walk_eqns(sub)


def _aval(atom):
    return getattr(atom, "aval", None)


@dataclasses.dataclass
class OpStats:
    """Aggregate trace facts of one program (sub-jaxprs included)."""

    eqns: int = 0
    scatter: int = 0
    gather: int = 0
    convert: int = 0
    est_bytes: int = 0            # sum of eqn-output aval bytes: an upper
                                  # bound on un-fused intermediate traffic
    prims: dict = dataclasses.field(default_factory=dict)

    def row(self) -> dict:
        return dict(eqns=self.eqns, scatter_ops=self.scatter,
                    gather_ops=self.gather, convert_ops=self.convert,
                    est_mb=round(self.est_bytes / 1e6, 3))


def op_stats(closed) -> OpStats:
    """Count the op families the budgets and the op-count gates track."""
    st = OpStats()
    for eqn in walk_eqns(closed):
        name = eqn.primitive.name
        st.eqns += 1
        st.prims[name] = st.prims.get(name, 0) + 1
        if name in SCATTER_PRIMITIVES:
            st.scatter += 1
        elif name in GATHER_PRIMITIVES:
            st.gather += 1
        elif name == "convert_element_type":
            st.convert += 1
        for ov in eqn.outvars:
            aval = _aval(ov)
            if aval is not None and hasattr(aval, "size"):
                st.est_bytes += int(aval.size) * aval.dtype.itemsize
    return st


# --------------------------------------------------------------------------
# JX001 / JX002 / JX003 — per-program jaxpr rules
# --------------------------------------------------------------------------


def _wide_dtype_findings(closed, site: str) -> list:
    """JX001: any 64-bit abstract value, deduped per (dtype, primitive)."""
    seen, out = set(), []
    for eqn in walk_eqns(closed):
        for atom in list(eqn.invars) + list(eqn.outvars):
            aval = _aval(atom)
            dt = str(getattr(aval, "dtype", ""))
            if dt in WIDE_DTYPES:
                token = f"{dt}@{eqn.primitive.name}"
                if token not in seen:
                    seen.add(token)
                    out.append(finding(
                        "JX001", site, token,
                        f"{dt} value at primitive {eqn.primitive.name!r} "
                        "(x32 contract: DESIGN.md Sec. 6)"))
    return out


def _float_kind(dt):
    return dt.kind == "f"


def _chain_redundant(a, b, c) -> bool:
    """Is convert a->b->c (middle used once) collapsible to a->c?

    Conservative: only when dropping b provably preserves values —
    b == c (second hop is a no-op), a round trip back to ``a`` through a
    wider-or-equal middle, or a same-kind widening then anything.
    """
    if b == c:
        return True
    if a.kind == "b":
        return True          # bool carries {0, 1}: any middle is lossless
    same_kind = a.kind == b.kind
    wider = b.itemsize >= a.itemsize
    if same_kind and wider:
        return True          # a -> wider(a) -> c  ==  a -> c
    return False


def _convert_findings(closed, site: str) -> list:
    """JX002: self-converts and collapsible convert chains."""
    out = []
    if isinstance(closed, ClosedJaxpr):
        jaxprs = [closed.jaxpr]
    else:
        jaxprs = [closed]
    # walk each (sub-)jaxpr independently: var identity is scoped
    stack = list(jaxprs)
    while stack:
        jx = stack.pop()
        consumers: dict = {}
        escaping = {id(v) for v in jx.outvars}
        for eqn in jx.eqns:
            for iv in eqn.invars:
                if _aval(iv) is not None and not hasattr(iv, "val"):
                    consumers.setdefault(id(iv), []).append(eqn)
            for sub in _sub_jaxprs(eqn.params):
                stack.append(sub)
        for eqn in jx.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            (iv,), (ov,) = eqn.invars, eqn.outvars
            src_aval, dst_aval = _aval(iv), _aval(ov)
            if src_aval is None or dst_aval is None:
                continue
            src, dst = src_aval.dtype, dst_aval.dtype
            src_weak = bool(getattr(src_aval, "weak_type", False))
            dst_weak = bool(getattr(dst_aval, "weak_type", False))
            if src == dst and src_weak == dst_weak:
                out.append(finding(
                    "JX002", site, f"{src}->{dst}",
                    f"self-convert {src}->{dst} (no-op cast materialized)"))
                continue
            uses = consumers.get(id(ov), [])
            if (id(ov) not in escaping and len(uses) == 1
                    and uses[0].primitive.name == "convert_element_type"):
                final = _aval(uses[0].outvars[0]).dtype
                if _chain_redundant(src, dst, final):
                    out.append(finding(
                        "JX002", site, f"{src}->{dst}->{final}",
                        f"convert chain {src}->{dst}->{final} collapses "
                        f"to {src}->{final}"))
    return out


def _callback_findings(closed, site: str) -> list:
    """JX003: host callback primitives anywhere in the program."""
    out, seen = [], set()
    for eqn in walk_eqns(closed):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES and name not in seen:
            seen.add(name)
            out.append(finding(
                "JX003", site, name,
                f"host callback {name!r} inside a traced engine program "
                "(serializes the superstep loop on host round-trips)"))
    return out


def check_jaxpr(closed, site: str, budgets: dict | None = None) -> list:
    """All per-program jaxpr rules (JX001/JX002/JX003, and JX005 when a
    ``{"scatter": n, "gather": n}`` budget is supplied)."""
    out = (_wide_dtype_findings(closed, site)
           + _convert_findings(closed, site)
           + _callback_findings(closed, site))
    if budgets:
        st = op_stats(closed)
        for fam, have in (("scatter", st.scatter), ("gather", st.gather)):
            cap = budgets.get(fam)
            if cap is not None and have > cap:
                out.append(finding(
                    "JX005", site, f"{fam}={have}",
                    f"{fam} op count {have} exceeds budget {cap} "
                    "(rules.PHASE_BUDGETS)"))
    return out


# --------------------------------------------------------------------------
# JX004 — donation aliasing (eager; buffer identity, not tracing)
# --------------------------------------------------------------------------


def check_donation(pytree, site: str) -> list:
    """JX004: two leaves of a to-be-donated pytree sharing one buffer."""
    out = []
    leaves_paths = jax.tree_util.tree_flatten_with_path(pytree)[0]
    seen: dict = {}
    for path, leaf in leaves_paths:
        try:
            ptr = leaf.unsafe_buffer_pointer()
        except Exception:   # non-device leaf, or a backend without the API
            continue
        label = jax.tree_util.keystr(path)
        if ptr in seen:
            out.append(finding(
                "JX004", site, label,
                f"donated leaf {label} aliases {seen[ptr]} (one buffer, "
                f"two leaves — use-after-donate under donate_argnums)"))
        else:
            seen[ptr] = label
    return out


# --------------------------------------------------------------------------
# JX006 — SimConfig sweepability classification
# --------------------------------------------------------------------------


def _perturb(value):
    """A nearby-but-different value of the same scalar type."""
    if isinstance(value, bool):
        return not value
    if isinstance(value, int):
        return value + 1
    if isinstance(value, float):
        return value * 1.5 + 0.25
    return None


def _aval_sig(consts):
    leaves, treedef = jax.tree_util.tree_flatten(consts)
    return treedef, [(np.shape(x), np.asarray(x).dtype) for x in leaves]


def classify_config(site: str = "simconfig") -> list:
    """JX006: derive the empirical Dims/aval impact of every scalar
    SimConfig field and cross-check ``api.apply_point``'s sets."""
    from repro.netsim import api, state
    from repro.netsim.scenarios import scenario

    out = []
    sc = scenario("tiny_3t")
    base_cfg = sc.cfg
    _, _, dims0, consts0 = state.derive(base_cfg, sc.wl)
    sig0 = _aval_sig(consts0)

    for field in dataclasses.fields(state.SimConfig):
        name = field.name
        value = getattr(base_cfg, name)
        new = _perturb(value)
        if new is None:
            # structural field (configs, strings, tuples): must be
            # rejected by apply_point -> STATIC_KEYS, or a backend
            # selector is silently unclassified
            if name not in api.STATIC_KEYS:
                out.append(finding(
                    "JX006", site, name,
                    f"structural field {name!r} is not in api.STATIC_KEYS "
                    f"— apply_point rejects it only via the generic "
                    f"unknown-key branch, with a misleading message"))
            continue
        try:
            _, _, dims2, consts2 = state.derive(
                dataclasses.replace(base_cfg, **{name: new}), sc.wl)
        except Exception as e:    # perturbation hit a validation wall
            out.append(finding(
                "JX006", site, name,
                f"cannot classify {name!r}: derive({value!r}->{new!r}) "
                f"raised {type(e).__name__}: {e}"))
            continue
        retraces = (dims2 != dims0) or (_aval_sig(consts2) != sig0)
        if retraces and name in api.CFG_KEYS:
            out.append(finding(
                "JX006", site, name,
                f"field {name!r} is listed sweepable (CFG_KEYS) but "
                f"changing it retraces (Dims or Consts avals change)"))
        if retraces and name not in api.STATIC_KEYS:
            out.append(finding(
                "JX006", site, name,
                f"field {name!r} changes Dims/avals but is not in "
                f"api.STATIC_KEYS — apply_point would not name it as "
                f"Dims-changing"))
        if not retraces and name not in (api.CFG_KEYS | api.STATIC_KEYS):
            out.append(finding(
                "JX006", site, name,
                f"field {name!r} is unclassified: neither sweepable "
                f"(CFG_KEYS) nor static (STATIC_KEYS)"))

    # apply_point must actually reject every static key...
    for key in sorted(api.STATIC_KEYS):
        try:
            api.apply_point(base_cfg, {key: getattr(base_cfg, key, 0)})
        except KeyError:
            pass
        else:
            out.append(finding(
                "JX006", site, key,
                f"api.apply_point accepted static key {key!r}"))
    # ...and every CC tuning key must exist on make_cc_params
    from repro.core.types import make_cc_params
    params = set(inspect.signature(make_cc_params).parameters)
    for key in sorted(api.CC_PARAM_KEYS - params):
        out.append(finding(
            "JX006", site, key,
            f"CC_PARAM_KEYS entry {key!r} is not a make_cc_params kwarg"))
    return out


# --------------------------------------------------------------------------
# scenario audits
# --------------------------------------------------------------------------


def _backend_cfg(cfg, backend: str):
    """The scenario's config with all hot-loop backends set to
    ``backend`` (CC falls back to jnp where no pallas kernel exists)."""
    from repro.core import registry
    cc = backend if (backend == "jnp"
                     or cfg.algo in registry.PALLAS_ALGORITHMS) else "jnp"
    return dataclasses.replace(cfg, cc_backend=cc, fabric_backend=backend,
                               transport_backend=backend)


def audit_scenario(sc, backends=("jnp", "pallas"), per_phase: bool = True):
    """Trace and rule-check one scenario on each backend.

    Returns ``(findings, rows)``: findings from JX001/002/003/005 over
    init, the six phases, the step, and the horizon; plus JX004 on a
    real init state.  ``rows`` are op-count rows (op counts and bytes
    per program; ``tests/test_gates.py`` gates some of them).
    """
    from repro.netsim import engine, state

    findings: list[Finding] = []
    rows: list[dict] = []
    for backend in backends:
        sim = engine.build(_backend_cfg(sc.cfg, backend), sc.wl)
        site_base = f"{sc.name}/{backend}"
        # the phases and the step take the run loops' form of the state
        st_struct = jax.eval_shape(lambda: state.ring_loop_form(sim.init()))
        consts = sim.consts

        programs = {"init": jax.make_jaxpr(sim.init)()}
        for pname, pfn in sim.phases:
            programs[pname] = jax.make_jaxpr(
                lambda s, _f=pfn: _f(consts, s))(st_struct)
        programs["step"] = jax.make_jaxpr(sim.step)(st_struct)
        programs["horizon"] = jax.make_jaxpr(sim.horizon)(st_struct)

        for pname, closed in programs.items():
            site = f"{site_base}/{pname}"
            budgets = (PHASE_BUDGETS.get(pname)
                       if backend == "jnp" else None)
            findings.extend(check_jaxpr(closed, site, budgets=budgets))
            if per_phase or pname == "step":
                stats = op_stats(closed)
                rows.append(dict(name=site, scenario=sc.name,
                                 backend=backend, program=pname,
                                 **stats.row()))
    # donation aliasing: one eager init state (backend-independent)
    findings.extend(check_donation(
        engine.build(sc.cfg, sc.wl).init(), f"{sc.name}/init"))
    return findings, rows


# per-phase op-count rows are recorded for these scenarios (the tiered
# paper-scale set); everything else contributes step-level rows only,
# keeping the rows a few hundred, not thousands
PER_PHASE_SCENARIOS = ("tiny_3t", "perm_512n_3t", "perm_1024n_3t")


def audit_catalogue(names=None, backends=("jnp", "pallas"),
                    progress=None):
    """Audit every registered scenario (aliases deduped) + JX006.

    Returns ``(findings, rows)`` over the whole catalogue.
    """
    from repro.netsim import scenarios

    if names is None:
        names = scenarios.names()
    seen, resolved = set(), []
    for name in names:
        sc = scenarios.scenario(name)
        if sc.name not in seen:      # aliases resolve to one canonical name
            seen.add(sc.name)
            resolved.append(sc)

    findings, rows = [], []
    for sc in resolved:
        if progress:
            progress(sc.name)
        f, r = audit_scenario(sc, backends=backends,
                              per_phase=sc.name in PER_PHASE_SCENARIOS)
        findings.extend(f)
        rows.extend(r)
    findings.extend(classify_config())
    return findings, rows
