"""AST contract linter: source-level invariants the jaxpr auditor cannot
see (DESIGN.md Sec. 10).

Stdlib ``ast`` only — no third-party linter dependency.  Four contract
families (rule docs in ``analysis/rules.py``):

  JX101  ``kernels/*/ref.py`` vs ``kernel.py`` signature parity — the
         ``ops.py`` dispatchers assume the pair is call-compatible.
  JX103  no unseeded legacy ``np.random.*`` calls in simulator code.
  JX104  no Python truthiness on traced values in the tick phase
         modules.
  JX105  no ``jax``/``jax.numpy`` in the host-side Consts-building
         modules (the traced trio in ``faults.py`` is exempt).

Suppress a line-anchored finding with ``# noqa: JX1xx`` (or a bare
``# noqa``); intentional cross-file deviations go in
``rules.ALLOWLIST`` instead.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

from repro.analysis.rules import Finding, finding

REPO_ROOT = Path(__file__).resolve().parents[3]

# the six-phase tick modules: everything traced, truthiness is a bug
PHASE_MODULES = ("src/repro/netsim/fabric.py",
                 "src/repro/netsim/transport.py",
                 "src/repro/netsim/sender.py",
                 "src/repro/netsim/metrics.py")

# host-side Consts-building modules: numpy-only by design (device math
# here would run per sweep point, defeating the traced-Consts design)
HOST_MODULES = ("src/repro/netsim/topology.py",
                "src/repro/netsim/units.py",
                "src/repro/netsim/workloads.py",
                "src/repro/netsim/collectives.py",
                "src/repro/netsim/scenarios.py")
# faults.py is split: tables build on host, but these three are traced
# per tick by the fabric and legitimately use jnp
HOST_SPLIT_MODULES = {
    "src/repro/netsim/faults.py":
        ("port_period", "fault_active", "transition_horizon"),
}

# modules where unseeded randomness would silently decorrelate runs
RANDOM_SCOPE = ("src/repro",)

_NOQA_RE = re.compile(r"#\s*noqa(?::\s*(?P<codes>[A-Z0-9, ]+))?",
                      re.IGNORECASE)


def _noqa(source: str) -> dict:
    """line number -> set of suppressed rule ids ({'*'} for bare noqa)."""
    out = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _NOQA_RE.search(line)
        if m:
            codes = m.group("codes")
            out[i] = ({c.strip().upper() for c in codes.split(",")}
                      if codes else {"*"})
    return out


def _suppressed(noqa: dict, line: int, rule: str) -> bool:
    codes = noqa.get(line, ())
    return "*" in codes or rule in codes


def _parse(path: Path):
    source = path.read_text()
    return ast.parse(source, filename=str(path)), _noqa(source)


def _rel(path: Path) -> str:
    try:
        return str(path.relative_to(REPO_ROOT))
    except ValueError:
        return str(path)


# --------------------------------------------------------------------------
# JX101 — kernel trio signature parity
# --------------------------------------------------------------------------


def _public_functions(tree: ast.Module) -> dict:
    return {node.name: node for node in tree.body
            if isinstance(node, ast.FunctionDef)
            and not node.name.startswith("_")}


def _positional(fn: ast.FunctionDef) -> tuple:
    """Positional parameter names (kw-only params — block sizes,
    ``interpret`` flags — are dispatch detail, not call contract)."""
    args = fn.args
    return tuple(a.arg for a in (args.posonlyargs + args.args))


def _pair_kernels(refs: dict, kernels: dict) -> list:
    """Match ref entry points to kernel entry points: by ``_ref`` suffix
    first, else the sole-public-function convention."""
    pairs = []
    for rname, rfn in refs.items():
        base = rname[:-4] if rname.endswith("_ref") else rname
        for kname in (base, base + "_kernel"):
            if kname in kernels:
                pairs.append((rfn, kernels[kname]))
                break
    if not pairs and len(refs) == 1 and len(kernels) == 1:
        pairs.append((next(iter(refs.values())),
                      next(iter(kernels.values()))))
    return pairs


def check_kernel_parity(kernels_dir: Path | None = None) -> list:
    """JX101 over every ``kernels/<name>/`` trio directory."""
    if kernels_dir is None:
        kernels_dir = REPO_ROOT / "src" / "repro" / "kernels"
    out = []
    for kdir in sorted(p for p in kernels_dir.iterdir() if p.is_dir()):
        ref_py, kernel_py = kdir / "ref.py", kdir / "kernel.py"
        if not (ref_py.exists() and kernel_py.exists()):
            continue
        site = f"kernels/{kdir.name}"
        refs = _public_functions(_parse(ref_py)[0])
        kernels = _public_functions(_parse(kernel_py)[0])
        pairs = _pair_kernels(refs, kernels)
        if not pairs:
            out.append(finding(
                "JX101", site, "unpaired",
                "no ref/kernel entry-point pairing found "
                f"(ref: {sorted(refs)}, kernel: {sorted(kernels)})"))
            continue
        for rfn, kfn in pairs:
            rp, kp = _positional(rfn), _positional(kfn)
            kw = {a.arg for a in kfn.args.kwonlyargs}
            # contract: the pair agrees on the positional prefix; a
            # ref's trailing positionals may become kernel kw-only
            # statics (block shapes, capacities), and the kernel may
            # append defaulted positionals — either direction is a
            # call-compatible refinement, anything else is drift
            shared = min(len(rp), len(kp))
            prefix_ok = rp[:shared] == kp[:shared]
            tail_ok = set(rp[shared:]) <= kw or not rp[shared:]
            if not (prefix_ok and tail_ok):
                out.append(finding(
                    "JX101", site, f"{rfn.name}|{kfn.name}",
                    f"signature drift: {rfn.name}{rp} vs "
                    f"{kfn.name}{kp} (ops.py dispatches blind)"))
    return out


# --------------------------------------------------------------------------
# JX103 — unseeded legacy np.random
# --------------------------------------------------------------------------

_SEEDED_RANDOM_OK = {"default_rng", "Generator", "SeedSequence",
                     "PCG64", "Philox"}


def _attr_chain(node) -> list:
    """``a.b.c`` -> ["a", "b", "c"] (empty when not a pure chain)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return []


def check_random(path: Path) -> list:
    """JX103 over one file."""
    tree, noqa = _parse(path)
    rel, out = _rel(path), []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if (len(chain) >= 3 and chain[0] in ("np", "numpy")
                and chain[1] == "random"
                and chain[2] not in _SEEDED_RANDOM_OK
                and not _suppressed(noqa, node.lineno, "JX103")):
            out.append(finding(
                "JX103", f"{rel}:{node.lineno}", ".".join(chain),
                f"unseeded legacy {'.'.join(chain)}() — use a seeded "
                "np.random.default_rng(seed) generator"))
    return out


# --------------------------------------------------------------------------
# JX104 — truthiness on traced values in phase modules
# --------------------------------------------------------------------------

# names bound to traced values in phase-function signatures; ``dims`` is
# deliberately absent (static Python scalars — branching on it is the
# intended specialization mechanism)
_TRACED_ROOTS = {"st", "state", "consts"}


def _mentions_traced(expr: ast.AST) -> str | None:
    """The first traced-value mention inside ``expr``, or None."""
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute):
            chain = _attr_chain(node)
            if chain and chain[0] in _TRACED_ROOTS:
                return ".".join(chain)
        elif isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain and chain[0] == "jnp":
                return ".".join(chain) + "(...)"
    return None


def check_truthiness(path: Path) -> list:
    """JX104 over one phase module."""
    tree, noqa = _parse(path)
    rel, out = _rel(path), []
    tests = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.If, ast.While)):
            tests.append(node.test)
        elif isinstance(node, ast.Assert):
            tests.append(node.test)
        elif isinstance(node, ast.IfExp):
            tests.append(node.test)
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Name)
              and node.func.id == "bool" and node.args):
            tests.append(node.args[0])
    for test in tests:
        hit = _mentions_traced(test)
        if hit and not _suppressed(noqa, test.lineno, "JX104"):
            out.append(finding(
                "JX104", f"{rel}:{test.lineno}", hit,
                f"Python truthiness on traced value {hit} — this either "
                "raises TracerBoolConversionError or freezes a branch "
                "at trace time; use lax.cond/jnp.where"))
    return out


# --------------------------------------------------------------------------
# JX105 — host-path purity
# --------------------------------------------------------------------------


def _function_ranges(tree: ast.Module) -> list:
    """[(name, first_line, last_line)] for every top-level function."""
    out = []
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.append((node.name, node.lineno, node.end_lineno))
    return out


def check_host_purity(path: Path, traced_functions=()) -> list:
    """JX105 over one host module; ``traced_functions`` are exempt."""
    tree, noqa = _parse(path)
    rel, out = _rel(path), []
    ranges = [(n, lo, hi) for n, lo, hi in _function_ranges(tree)
              if n in traced_functions]

    def in_traced(line: int) -> bool:
        return any(lo <= line <= hi for _, lo, hi in ranges)

    seen_lines = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Name) and node.id in ("jnp", "jax")
                and isinstance(node.ctx, ast.Load)
                and not in_traced(node.lineno)
                and node.lineno not in seen_lines
                and not _suppressed(noqa, node.lineno, "JX105")):
            seen_lines.add(node.lineno)
            out.append(finding(
                "JX105", f"{rel}:{node.lineno}", node.id,
                f"{node.id} used in host-side Consts-building module — "
                "these paths run per sweep point and must stay numpy"))
    return out


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------


def lint_repo(root: Path | None = None) -> list:
    """Run the full JX1xx contract suite over the repository."""
    root = Path(root) if root else REPO_ROOT
    out: list[Finding] = []
    out.extend(check_kernel_parity(root / "src" / "repro" / "kernels"))
    for scope in RANDOM_SCOPE:
        for path in sorted((root / scope).rglob("*.py")):
            out.extend(check_random(path))
    for mod in PHASE_MODULES:
        out.extend(check_truthiness(root / mod))
    for mod in HOST_MODULES:
        out.extend(check_host_purity(root / mod))
    for mod, traced in HOST_SPLIT_MODULES.items():
        out.extend(check_host_purity(root / mod, traced_functions=traced))
    return out
