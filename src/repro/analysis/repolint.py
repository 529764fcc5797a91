"""AST-based repository lint: enforce repro's own codebase contracts.

The repo accumulated a handful of conventions that keep the serving path
debuggable and the benchmarks honest — each previously enforced only by
review or by a runtime gate that needs test execution.  This lint checks
them statically (``make lint-repro``, a CI step), so a violation fails
the build before any test runs:

``RL001`` deprecated-shim
    No internal call to the deprecated ``core.schedules.run_layer`` /
    ``run_stack`` shims (or ``core.gru.run_layer``).  Complements the
    ``-W error::DeprecationWarning:repro\\.`` pytest gate with a static
    check: the pytest gate only fires on code paths the suite happens to
    execute; this one reads every file.  (The suffix-named per-schedule
    entry points — ``run_layer_fused`` etc. — are the supported API and
    are not flagged.)

``RL002`` serving-assert
    No bare ``assert`` statement, and no ``raise RuntimeError(...)`` /
    ``raise AssertionError(...)``, on the serving path (``dispatch/``,
    ``rnn/``, ``serving/``).  Faults there must use the structured
    ``runtime.errors`` taxonomy so callers can quarantine by slot/uid —
    and ``assert`` vanishes under ``python -O``, which would silently
    drop the check in an optimized deployment.

``RL003`` timing-outside-obs
    No ``time.*`` calls and no ``jax.block_until_ready`` outside
    ``runtime/obs.py`` (scope: ``calib/``, ``dispatch/``, ``rnn/``,
    ``serving/``, ``runtime/``).  Timing and fencing go through the obs
    module's ``measure_samples`` / ``measure_us`` / ``monotonic_s`` /
    ``fence`` so every measurement in the repo shares one fenced clock
    (the PR-4 "one benchmark timer" rule, now machine-checked) — the
    calibration replay harness included.  Launch-side modules
    (``launch/``, ``checkpoint/``) legitimately stamp wall-clock epoch
    metadata and are out of scope.

``RL004`` slot-field-read
    ``Slot.signature()``-relevant fields (``wave``, ``chunk_len``,
    ``group_b``, ``chained``, ``tile_k``, ``mvm_block``) are read only by
    the planner, the executor, the verifier (``analysis/``),
    ``runtime/obs.py``, and the calibration subsystem (``calib/`` replays
    exactly the launches those fields describe — it is the measurement
    side of the same contract).  Any other module pattern-matching on
    slot internals is coupling to the packing layout, which the planner
    is free to change under the same ``signature()``; such code must go
    through ``DispatchPlan``'s public accessors or the verifier.

Usage::

    python -m repro.analysis.repolint src/repro     # CI / make lint-repro
    violations = collect(Path("src/repro"))         # programmatic

Paths are keyed by their suffix after the last ``repro`` path component,
so the rules apply identically from a checkout root, an installed
site-packages tree, or a test's tmp dir.
"""
from __future__ import annotations

import ast
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

#: the deprecated entry points RL001 bans (exact names; the per-schedule
#: ``run_layer_*`` functions are the supported replacements)
DEPRECATED_SHIMS = ("run_layer", "run_stack")

#: exception constructors RL002 bans on the serving path
BANNED_RAISES = ("RuntimeError", "AssertionError")

#: Slot fields whose reads RL004 confines to planner/executor/analysis.
#: ("groups" is signature-relevant too but collides with ``m.groups()``
#: on regex matches — the planner's own property tests cover it.)
SLOT_FIELDS = frozenset(
    {"wave", "chunk_len", "group_b", "chained", "tile_k", "mvm_block"})

#: rule -> (path prefixes in scope, path suffixes exempt).  "" = repo-wide.
_SCOPES = {
    "RL001": (("",), ("core/schedules.py", "core/gru.py")),
    "RL002": (("dispatch/", "rnn/", "serving/"), ()),
    "RL003": (("calib/", "dispatch/", "rnn/", "serving/", "runtime/"),
              ("runtime/obs.py",)),
    "RL004": (("",), ("dispatch/planner.py", "dispatch/executor.py",
                      "runtime/obs.py", "analysis/", "calib/")),
}


@dataclass(frozen=True)
class Violation:
    rule: str       # "RL001".."RL004"
    path: str       # repo-relative path of the offending file
    line: int       # 1-based source line
    msg: str        # what was found and what to use instead

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.msg}"


def _relkey(relpath: str) -> str:
    """Key a path by its suffix after the last ``repro`` component, so
    scope prefixes match regardless of checkout layout."""
    parts = Path(relpath).as_posix().split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] == "repro":
            return "/".join(parts[i + 1:])
    return "/".join(parts)


def _in_scope(rule: str, key: str) -> bool:
    prefixes, exempt = _SCOPES[rule]
    for e in exempt:
        if key == e or (e.endswith("/") and key.startswith(e)):
            return False
    return any(key.startswith(p) for p in prefixes)


def _callee_name(call: ast.Call) -> Optional[str]:
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def _dotted(node: ast.AST) -> Optional[str]:
    """'time.monotonic' for Attribute chains rooted at a Name."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _Linter(ast.NodeVisitor):
    def __init__(self, key: str, path: str):
        self.key = key
        self.path = path
        self.out: List[Violation] = []

    def _emit(self, rule: str, line: int, msg: str) -> None:
        if _in_scope(rule, self.key):
            self.out.append(Violation(rule, self.path, line, msg))

    # -- RL002: bare assert -------------------------------------------------
    def visit_Assert(self, node: ast.Assert) -> None:
        self._emit("RL002", node.lineno,
                   "bare `assert` on the serving path — raise a "
                   "runtime.errors fault (asserts vanish under -O)")
        self.generic_visit(node)

    # -- RL002: raise RuntimeError/AssertionError ---------------------------
    def visit_Raise(self, node: ast.Raise) -> None:
        exc = node.exc
        name = None
        if isinstance(exc, ast.Call):
            name = _callee_name(exc)
        elif isinstance(exc, (ast.Name, ast.Attribute)):
            name = exc.id if isinstance(exc, ast.Name) else exc.attr
        if name in BANNED_RAISES:
            self._emit("RL002", node.lineno,
                       f"raise {name} on the serving path — use the "
                       "runtime.errors taxonomy (ServingFault subclass)")
        self.generic_visit(node)

    # -- RL001 / RL003: calls -----------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        name = _callee_name(node)
        if name in DEPRECATED_SHIMS:
            self._emit("RL001", node.lineno,
                       f"call to deprecated shim `{name}` — use the "
                       "repro.rnn facade (compile/forward)")
        dotted = _dotted(node.func)
        if dotted is not None:
            if dotted.startswith("time.") or dotted.endswith(
                    ".block_until_ready") or dotted == "block_until_ready":
                self._emit(
                    "RL003", node.lineno,
                    f"`{dotted}` outside runtime/obs.py — time/fence via "
                    "obs.measure_us / obs.monotonic_s / obs.fence")
        self.generic_visit(node)

    # -- RL004: slot-field reads --------------------------------------------
    def visit_Attribute(self, node: ast.Attribute) -> None:
        if (isinstance(node.ctx, ast.Load) and node.attr in SLOT_FIELDS
                and not (isinstance(node.value, ast.Name)
                         and node.value.id == "self")):
            self._emit(
                "RL004", node.lineno,
                f"read of Slot packing field `.{node.attr}` outside "
                "planner/executor/analysis — go through DispatchPlan's "
                "public surface")
        self.generic_visit(node)


def lint_source(src: str, relpath: str) -> List[Violation]:
    """Lint one file's source text.  ``relpath`` decides rule scope (it
    is keyed by its suffix after the last ``repro`` path component)."""
    key = _relkey(relpath)
    tree = ast.parse(src, filename=relpath)
    linter = _Linter(key, relpath)
    linter.visit(tree)
    return sorted(linter.out, key=lambda v: (v.path, v.line, v.rule))


def collect(root: Path) -> List[Violation]:
    """Lint every ``*.py`` under ``root``; returns sorted violations."""
    out: List[Violation] = []
    for path in sorted(Path(root).rglob("*.py")):
        out.extend(lint_source(path.read_text(), str(path)))
    return sorted(out, key=lambda v: (v.path, v.line, v.rule))


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    roots = [Path(a) for a in args] or [Path("src/repro")]
    violations: List[Violation] = []
    for root in roots:
        if not root.exists():
            print(f"repolint: no such path: {root}", file=sys.stderr)
            return 2
        violations.extend(collect(root))
    for v in violations:
        print(v)
    n = len(violations)
    root_names = ", ".join(str(r) for r in roots)
    if n:
        print(f"repolint: {n} violation(s) in {root_names}",
              file=sys.stderr)
        return 1
    print(f"repolint: clean ({root_names})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())


__all__ = ["Violation", "lint_source", "collect", "main",
           "DEPRECATED_SHIMS", "BANNED_RAISES", "SLOT_FIELDS"]
