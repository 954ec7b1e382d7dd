"""The static-analysis layer analyzes itself honestly: every jaxpr rule
trips on a known-bad toy program, every lint rule trips on a known-bad
source snippet, and the real catalogue passes with zero unallowlisted
findings (DESIGN.md Sec. 10).
"""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import audit, lint, rules, trace_guard
from repro.analysis.trace_guard import counter


def _rules_of(findings):
    return {f.rule for f in findings}


# --------------------------------------------------------------------------
# jaxpr rules trip on deliberately bad programs
# --------------------------------------------------------------------------


def test_jx001_f64_leak_trips():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(np.zeros(4, np.float32))
    found = audit.check_jaxpr(closed, "toy/f64")
    assert "JX001" in _rules_of(found)
    assert any("float64" in f.token for f in found)


def test_jx001_clean_x32_program():
    closed = jax.make_jaxpr(lambda x: x * 2.0)(np.zeros(4, np.float32))
    assert "JX001" not in _rules_of(audit.check_jaxpr(closed, "toy"))


def test_jx002_convert_chain_trips():
    # bool -> int32 -> float32: the middle cast is collapsible
    closed = jax.make_jaxpr(
        lambda x: x.astype(jnp.int32).astype(jnp.float32)
    )(np.zeros(4, bool))
    found = audit.check_jaxpr(closed, "toy/chain")
    assert "JX002" in _rules_of(found)


def test_jx002_lossy_chain_not_flagged():
    # f32 -> i32 -> f32 truncates: semantics, not churn
    closed = jax.make_jaxpr(
        lambda x: x.astype(jnp.int32).astype(jnp.float32)
    )(np.zeros(4, np.float32))
    assert "JX002" not in _rules_of(audit.check_jaxpr(closed, "toy"))


def test_jx003_host_callback_trips():
    def step(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((4,), np.float32),
            x)
    closed = jax.make_jaxpr(step)(np.zeros(4, np.float32))
    found = audit.check_jaxpr(closed, "toy/callback")
    assert "JX003" in _rules_of(found)
    assert any(f.token == "pure_callback" for f in found)


def test_jx004_aliased_donation_trips():
    x = jnp.zeros(8)
    found = audit.check_donation((x, x, jnp.zeros(8)), "toy/donate")
    assert "JX004" in _rules_of(found)
    assert len(found) == 1           # one alias pair, third leaf is fresh


def test_jx004_fresh_buffers_clean():
    assert audit.check_donation(
        (jnp.zeros(8), jnp.zeros(8)), "toy") == []


def test_jx005_scatter_blowup_trips():
    def blowup(x):
        for i in range(6):
            x = jax.lax.dynamic_update_slice(x, jnp.ones(1), (i,))
        return x
    closed = jax.make_jaxpr(blowup)(np.zeros(16, np.float32))
    found = audit.check_jaxpr(closed, "toy/scatter",
                              budgets={"scatter": 3})
    assert "JX005" in _rules_of(found)
    # within budget: clean
    assert audit.check_jaxpr(closed, "toy", budgets={"scatter": 6}) == []


def test_op_stats_counts_and_recurses():
    def fn(x):
        def body(_, s):
            return jax.lax.dynamic_update_slice(s, jnp.ones(1), (0,))
        return jax.lax.fori_loop(0, 4, body, x)
    st = audit.op_stats(jax.make_jaxpr(fn)(np.zeros(8, np.float32)))
    assert st.scatter >= 1           # found inside the loop body jaxpr
    assert st.eqns > 1
    assert st.est_bytes > 0


# --------------------------------------------------------------------------
# JX006 — classification drift detector
# --------------------------------------------------------------------------


def test_jx006_catches_misclassified_static_key(monkeypatch):
    from repro.netsim import api
    # pretend a Dims-changing knob were sweepable: JX006 must object
    monkeypatch.setattr(api, "CFG_KEYS",
                        frozenset(api.CFG_KEYS | {"superstep"}))
    found = audit.classify_config()
    assert any(f.rule == "JX006" and f.token == "superstep" for f in found)


def test_jx006_clean_on_real_classification():
    assert [str(f) for f in audit.classify_config()
            if not f.allowlisted] == []


# --------------------------------------------------------------------------
# lint rules trip on deliberately bad sources
# --------------------------------------------------------------------------


def test_jx101_signature_drift_trips(tmp_path):
    kdir = tmp_path / "toy_kernel"
    kdir.mkdir()
    (kdir / "ref.py").write_text(textwrap.dedent("""\
        def toy_ref(a, b, c):
            return a + b + c
    """))
    (kdir / "kernel.py").write_text(textwrap.dedent("""\
        def toy(a, c, b):
            return a + b + c
    """))
    found = lint.check_kernel_parity(tmp_path)
    assert _rules_of(found) == {"JX101"}


def test_jx101_kwonly_statics_are_parity(tmp_path):
    kdir = tmp_path / "toy_kernel"
    kdir.mkdir()
    (kdir / "ref.py").write_text("def toy_ref(a, b, cap):\n    return a\n")
    (kdir / "kernel.py").write_text(
        "def toy(a, b, *, cap, interpret=True):\n    return a\n")
    assert lint.check_kernel_parity(tmp_path) == []


def test_jx103_unseeded_random_trips(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""\
        import numpy as np
        def jitter(n):
            return np.random.rand(n)
        def ok(n, seed):
            return np.random.default_rng(seed).random(n)
    """))
    found = lint.check_random(bad)
    assert len(found) == 1
    assert found[0].rule == "JX103"
    assert "np.random.rand" in found[0].token


def test_jx104_traced_truthiness_trips(tmp_path):
    bad = tmp_path / "phase.py"
    bad.write_text(textwrap.dedent("""\
        def control(dims, consts, st):
            if st.now > 5:
                return st
            if dims.trimming:      # static branch: fine
                pass
            return st
    """))
    found = lint.check_truthiness(bad)
    assert len(found) == 1
    assert found[0].rule == "JX104"
    assert "st.now" in found[0].token


def test_jx105_device_math_on_host_path_trips(tmp_path):
    bad = tmp_path / "topo.py"
    bad.write_text(textwrap.dedent("""\
        import jax.numpy as jnp
        import numpy as np
        def build(n):
            return jnp.arange(n)
        def traced_fn(n):
            return jnp.arange(n)
    """))
    found = lint.check_host_purity(bad)
    assert _rules_of(found) == {"JX105"}
    # the traced exemption works
    assert len(lint.check_host_purity(
        bad, traced_functions=("traced_fn",))) == 1


def test_noqa_suppresses_a_lint_finding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import numpy as np\n"
                   "x = np.random.rand(3)  # noqa: JX103\n"
                   "y = np.random.rand(3)\n")
    found = lint.check_random(bad)
    assert len(found) == 1
    assert found[0].site.endswith(":3")


# --------------------------------------------------------------------------
# allowlist mechanics
# --------------------------------------------------------------------------


def test_allowlist_matches_and_justifies():
    f = rules.finding("JX101", "kernels/cc_update",
                      "cc_update_ref|cc_update", "drift")
    assert f.allowlisted and rules.ALLOWLIST[f.allowed_by]
    f2 = rules.finding("JX101", "kernels/other", "x|y", "drift")
    assert not f2.allowlisted


def test_every_allowlist_entry_has_a_justification():
    for key, why in rules.ALLOWLIST.items():
        assert len(key.split(":", 2)) == 3, key
        assert why.strip(), f"empty justification for {key}"


# --------------------------------------------------------------------------
# trace_guard — the shared trace-counting contract
# --------------------------------------------------------------------------


def test_trace_guard_counts_and_expects():
    c = counter("test.analysis.guard")
    with trace_guard("test.analysis.guard") as g:
        c.hit()
        c.hit()
    assert g.count == 2
    with pytest.raises(AssertionError, match="expected 1"):
        with trace_guard("test.analysis.guard", expect=1):
            c.hit()
            c.hit()


def test_trace_guard_nested_windows_are_independent():
    c = counter("test.analysis.nested")
    with trace_guard("test.analysis.nested") as outer:
        c.hit()
        with trace_guard("test.analysis.nested", expect=1) as inner:
            c.hit()
        assert inner.count == 1
    assert outer.count == 2


# --------------------------------------------------------------------------
# the real repository is clean
# --------------------------------------------------------------------------


def test_lint_repo_self_clean():
    bad = [f for f in lint.lint_repo() if not f.allowlisted]
    assert bad == [], "\n".join(map(str, bad))


def test_audit_small_scenarios_self_clean():
    from repro.netsim.scenarios import scenario
    for name in ("tiny_3t", "tiny_perm4"):
        findings, rows = audit.audit_scenario(scenario(name))
        bad = [f for f in findings if not f.allowlisted]
        assert bad == [], "\n".join(map(str, bad))
        # the ledger rows carry the budgeted op families
        programs = {r["program"] for r in rows}
        assert {"init", "departures", "arrivals", "control", "grants",
                "sends", "metrics", "step", "horizon"} <= programs


@pytest.mark.slow
def test_audit_full_catalogue_self_clean():
    findings, rows = audit.audit_catalogue()
    bad = [f for f in findings if not f.allowlisted]
    assert bad == [], "\n".join(map(str, bad))
    names = {r["name"] for r in rows}
    # the paper-scale scenario records per-phase budget rows
    assert "perm_512n_3t/jnp/arrivals" in names
    assert "perm_512n_3t/pallas/step" in names
