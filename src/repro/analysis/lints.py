"""Unified repo lint registry (``scripts/lint.py`` is the CLI).

One registry for every repo-convention check that used to live as ad-hoc
shell in ``scripts/run_tests.sh``:

* ``compat-surface`` — the ROADMAP compat rule: no JAX internals (the
  private ``_src`` package) and no version-sensitive JAX surface outside
  ``src/repro/compat/``.
* ``donate-jit`` — the donation rule (``scripts/check_donation.py`` is now
  a thin shim over this rule): every ``jax.jit`` in the hot layers donates
  its carried state or carries a ``# no-donate: <reason>`` marker.
* ``no-version-branch`` — no raw ``jax.__version__`` checks outside
  ``repro/compat``; version sniffing belongs in a compat probe.
* ``jit-of-plan`` — compiled plan execution has exactly one home
  (``runtime/executor.py``): no ``jax.jit`` in the ``core`` plan/
  interpreter layer, and no jitting of ``run_plan``/``stage_fns`` stages
  anywhere else — use ``plan.compile()`` so the executable cache,
  fingerprinting and donation plumbing apply.
* ``mesh-axes-literal`` — mesh axis-name tuples have exactly one home
  (``launch/mesh.py``): no hard-coded ``("pod", "data")``-style tuples
  elsewhere in ``src/`` — import ``REPLICA_AXES`` / use the mesh helpers,
  so N-level mesh factorization changes land in one file.

Suppression: append ``# lint: disable=<rule>`` (comma-separated for
several rules) to the flagged line or the line above it. ``donate-jit``
additionally keeps its own richer ``# no-donate: <reason>`` marker, which
documents *why* — prefer it for that rule.

This module is deliberately import-light (stdlib + ``findings`` only): the
lint CLI must run without loading JAX.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Dict, List, Optional, Sequence

# The compat patterns are assembled (not written literally) so this file
# does not flag itself: the rule matches raw substrings anywhere in a line.
_COMPAT_PATTERNS = ("Axis" + "Type", "cost_" + "analysis()", "jax." + "_src")
_COMPAT_DIR = "src/repro/compat/"

_SUPPRESS_RE = re.compile(r"#\s*lint:\s*disable=([\w\-, ]+)")

DONATE_KEYWORDS = {"donate_argnums", "donate_argnames"}
NO_DONATE_MARKER = "# no-donate:"


@dataclasses.dataclass(frozen=True)
class LintViolation:
    rule: str
    path: str  # repo-relative, '/'-separated
    line: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.message}"

    def to_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class LintRule:
    name: str
    description: str
    check: Callable[[str], List[LintViolation]]  # repo root -> violations


RULES: Dict[str, LintRule] = {}


def rule(name: str, description: str):
    def register(fn):
        RULES[name] = LintRule(name=name, description=description, check=fn)
        return fn

    return register


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def _py_files(*dirs: str) -> List[str]:
    out = []
    for d in dirs:
        for dirpath, _dirnames, filenames in os.walk(d):
            for name in sorted(filenames):
                if name.endswith(".py"):
                    out.append(os.path.join(dirpath, name))
    return sorted(out)


def _rel(path: str, root: str) -> str:
    return os.path.relpath(path, root).replace(os.sep, "/")


def _suppressed(lines: List[str], lineno: int, rule_name: str) -> bool:
    """``# lint: disable=<rule>`` on the flagged line or the line above."""
    for ln in (lineno - 1, lineno - 2):
        if 0 <= ln < len(lines):
            m = _SUPPRESS_RE.search(lines[ln])
            if m and rule_name in [p.strip() for p in m.group(1).split(",")]:
                return True
    return False


def run_lints(
    root: Optional[str] = None, rules: Optional[Sequence[str]] = None,
) -> List[LintViolation]:
    """Run the registry (all rules, or a subset) and filter suppressions."""
    root = root or repo_root()
    names = list(rules) if rules is not None else sorted(RULES)
    unknown = [n for n in names if n not in RULES]
    if unknown:
        raise KeyError(f"unknown lint rule(s): {unknown}; have {sorted(RULES)}")
    violations: List[LintViolation] = []
    line_cache: Dict[str, List[str]] = {}
    for name in names:
        for v in RULES[name].check(root):
            path = os.path.join(root, v.path)
            if path not in line_cache:
                try:
                    with open(path) as fh:
                        line_cache[path] = fh.read().splitlines()
                except OSError:
                    line_cache[path] = []
            if not _suppressed(line_cache[path], v.line, v.rule):
                violations.append(v)
    return violations


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


@rule(
    "compat-surface",
    "no JAX internals or version-sensitive JAX API outside repro/compat "
    "(ROADMAP compat rule)",
)
def _compat_surface(root: str) -> List[LintViolation]:
    # Raw substrings in any src/**/*.py line (comments and strings included,
    # like a grep); only files under src/repro/compat/ may hold them.
    out: List[LintViolation] = []
    for path in _py_files(os.path.join(root, "src")):
        rel = _rel(path, root)
        if rel.startswith(_COMPAT_DIR):
            continue
        with open(path) as fh:
            for lineno, line in enumerate(fh.read().splitlines(), 1):
                if not any(p in line for p in _COMPAT_PATTERNS):
                    continue
                out.append(LintViolation(
                    rule="compat-surface", path=rel, line=lineno,
                    message=(
                        "JAX internals or version-sensitive JAX API used "
                        f"outside repro/compat: {line.strip()}"
                    ),
                ))
    return out


def _is_jax_jit(call: ast.Call) -> bool:
    f = call.func
    return (
        isinstance(f, ast.Attribute)
        and f.attr == "jit"
        and isinstance(f.value, ast.Name)
        and f.value.id == "jax"
    )


@rule(
    "donate-jit",
    "every jax.jit in src/repro/{algorithms,launch} donates its carried "
    "state or carries a '# no-donate: <reason>' marker",
)
def _donate_jit(root: str) -> List[LintViolation]:
    out: List[LintViolation] = []
    scan = (
        os.path.join(root, "src", "repro", "algorithms"),
        os.path.join(root, "src", "repro", "launch"),
    )
    for path in _py_files(*scan):
        rel = _rel(path, root)
        with open(path) as fh:
            src = fh.read()
        lines = src.splitlines()
        for node in ast.walk(ast.parse(src, filename=path)):
            if not (isinstance(node, ast.Call) and _is_jax_jit(node)):
                continue
            if any(kw.arg in DONATE_KEYWORDS for kw in node.keywords):
                continue
            # opt-out marker on the call line or the line above it
            lo = max(node.lineno - 2, 0)
            hi = min(node.end_lineno, len(lines))
            if any(NO_DONATE_MARKER in ln for ln in lines[lo:hi]):
                continue
            out.append(LintViolation(
                rule="donate-jit", path=rel, line=node.lineno,
                message=(
                    "jax.jit without donate_argnums — donate the carried "
                    "state, or mark the call with "
                    f"'{NO_DONATE_MARKER} <reason>' if no arg is "
                    "round-to-round state"
                ),
            ))
    return out


@rule(
    "no-version-branch",
    "no raw jax.__version__ checks outside repro/compat (use a compat probe)",
)
def _no_version_branch(root: str) -> List[LintViolation]:
    out: List[LintViolation] = []
    for path in _py_files(os.path.join(root, "src")):
        rel = _rel(path, root)
        if "/compat/" in rel:
            continue
        with open(path) as fh:
            src = fh.read()
        for node in ast.walk(ast.parse(src, filename=path)):
            if (
                isinstance(node, ast.Attribute)
                and node.attr == "__version__"
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax"
            ):
                out.append(LintViolation(
                    rule="no-version-branch", path=rel, line=node.lineno,
                    message=(
                        "raw jax.__version__ branch outside repro/compat — "
                        "version sniffing belongs in a repro.compat probe"
                    ),
                ))
    return out


# Assembled via frozenset (an ast.Set in this file, never an ast.Tuple) so
# the rule's own definition cannot flag itself.
_MESH_AXIS_NAMES = frozenset({"pod", "data", "superpod", "stage", "model"})
_MESH_AXES_HOME = "src/repro/launch/mesh.py"


@rule(
    "mesh-axes-literal",
    "no hard-coded mesh axis-name tuples (e.g. a pod/data pair) outside "
    "launch/mesh.py — import REPLICA_AXES or use the mesh helpers",
)
def _mesh_axes_literal(root: str) -> List[LintViolation]:
    out: List[LintViolation] = []
    for path in _py_files(os.path.join(root, "src")):
        rel = _rel(path, root)
        if rel == _MESH_AXES_HOME:
            continue
        with open(path) as fh:
            src = fh.read()
        for node in ast.walk(ast.parse(src, filename=path)):
            if not isinstance(node, (ast.Tuple, ast.List)):
                continue
            if len(node.elts) < 2:
                continue
            if not all(
                isinstance(e, ast.Constant)
                and isinstance(e.value, str)
                and e.value in _MESH_AXIS_NAMES
                for e in node.elts
            ):
                continue
            names = tuple(e.value for e in node.elts)  # type: ignore[union-attr]
            out.append(LintViolation(
                rule="mesh-axes-literal", path=rel, line=node.lineno,
                message=(
                    f"hard-coded mesh axis tuple {names} — mesh axis-name "
                    "tuples live in launch/mesh.py (import REPLICA_AXES or "
                    "use level_axes_for/partition_axes_for)"
                ),
            ))
    return out


_PLAN_STAGE_NAMES = ("run_plan", "stage_fns")


@rule(
    "jit-of-plan",
    "no jax.jit in the core plan layer, and no jitting of plan stages "
    "(run_plan/stage_fns) outside runtime/executor.py — use plan.compile()",
)
def _jit_of_plan(root: str) -> List[LintViolation]:
    out: List[LintViolation] = []
    for path in _py_files(os.path.join(root, "src", "repro")):
        rel = _rel(path, root)
        if rel == "src/repro/runtime/executor.py":
            continue
        in_core = rel.startswith("src/repro/core/")
        with open(path) as fh:
            src = fh.read()
        for node in ast.walk(ast.parse(src, filename=path)):
            if not (isinstance(node, ast.Call) and _is_jax_jit(node)):
                continue
            args_src = " ".join(
                ast.unparse(a) for a in list(node.args) + [
                    kw.value for kw in node.keywords
                ]
            )
            jits_stage = any(n in args_src for n in _PLAN_STAGE_NAMES)
            if in_core:
                out.append(LintViolation(
                    rule="jit-of-plan", path=rel, line=node.lineno,
                    message=(
                        "jax.jit in the core plan/interpreter layer — "
                        "compiled plan execution lives in "
                        "runtime/executor.py (plan.compile())"
                    ),
                ))
            elif jits_stage:
                out.append(LintViolation(
                    rule="jit-of-plan", path=rel, line=node.lineno,
                    message=(
                        "jitting a plan stage outside runtime/executor.py — "
                        "use plan.compile() so the executable cache, "
                        "fingerprinting and donation plumbing apply"
                    ),
                ))
    return out
