"""jaxlint check base class + source-anchoring helpers.

Program-level findings still need a SOURCE location: that is where the
inline ``# dmlint: disable=<check>`` suppression lives, what the baseline
keys on, and what ``--changed`` filters by.  Three anchoring strategies,
in order of fidelity:

* ``eqn_line`` — a jaxpr equation's own traceback, filtered to the first
  frame inside the audited tree (a host callback in a scan body anchors
  at the callback call site itself);
* ``assignment_line`` / ``rule_line`` — the module-level assignment of a
  rule table (and the individual rule entry's line inside it);
* ``pattern_line`` — first source line containing a marker substring
  (the donate-tuple / builder-def fallback).

All jax imports stay inside functions: importing this module must never
initialize a backend (the AST tier's no-jax guarantee extends to
*importing* the jax tier; only *running* it pays for jax).
"""

from __future__ import annotations

import ast
import os
from typing import Any, Dict, Iterator, List, Optional, Tuple

from distributed_machine_learning_tpu.analysis.findings import Finding

PKG_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def display_path(path: str) -> str:
    abspath = os.path.abspath(path)
    rel = os.path.relpath(abspath)
    return abspath if rel.startswith("..") else rel


def _source_lines(path: str) -> List[str]:
    from distributed_machine_learning_tpu.analysis import engine

    try:
        return engine.load_context(path).lines
    except (OSError, SyntaxError):
        return []


def assignment_line(path: str, symbol: str) -> int:
    """Line of the module-level ``symbol = ...`` assignment (1 if absent)."""
    from distributed_machine_learning_tpu.analysis import engine

    try:
        tree = engine.load_context(path).tree
    except (OSError, SyntaxError):
        return 1
    for node in getattr(tree, "body", []):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == symbol:
                    return node.lineno
        elif isinstance(node, ast.AnnAssign):
            t = node.target
            if isinstance(t, ast.Name) and t.id == symbol:
                return node.lineno
    return 1


def rule_entry_lines(path: str, symbol: str) -> List[int]:
    """Per-entry line numbers of a rule-table tuple assignment: entry i of
    ``SYMBOL = ((pat, spec), ...)`` anchors dead-rule / phantom-axis
    findings at ITS line, not the table header's."""
    from distributed_machine_learning_tpu.analysis import engine

    try:
        tree = engine.load_context(path).tree
    except (OSError, SyntaxError):
        return []
    for node in getattr(tree, "body", []):
        targets: List[ast.expr] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Name) and t.id == symbol:
                value = node.value
                if isinstance(value, (ast.Tuple, ast.List)):
                    return [e.lineno for e in value.elts]
                return [node.lineno]
    return []


def pattern_line(path: str, needle: str) -> int:
    """First 1-based line containing ``needle`` (1 if absent)."""
    for i, line in enumerate(_source_lines(path), start=1):
        if needle in line:
            return i
    return 1


def eqn_line(eqn, within: str) -> Optional[Tuple[str, int]]:
    """(abs file, line) of the first traceback frame of ``eqn`` inside the
    ``within`` directory — how a jaxpr finding points at the offending
    source call instead of the audit harness."""
    try:
        from jax._src import source_info_util

        frames = source_info_util.user_frames(eqn.source_info.traceback)
    except Exception:  # noqa: BLE001 - traceback APIs are private/fluid
        return None
    within = os.path.abspath(within)
    for fr in frames:
        fn = os.path.abspath(getattr(fr, "file_name", "") or "")
        line = int(getattr(fr, "start_line", 0) or 0)
        if line > 0 and fn.startswith(within):
            return fn, line
    return None


def iter_eqns(jaxpr, _stack: Tuple[str, ...] = ()) -> Iterator[Tuple[Any, Tuple[str, ...]]]:
    """Yield ``(eqn, enclosing_primitive_names)`` over a jaxpr and every
    sub-jaxpr riding its equation params (scan/while/cond bodies, pjit
    calls, shard_map, custom_* wrappers, ...)."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn, _stack
        inner = _stack + (eqn.primitive.name,)
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v, jax):
                yield from iter_eqns(sub, inner)


def _sub_jaxprs(value, jax) -> Iterator[Any]:
    from jax.extend import core as jcore

    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v, jax)


class JaxCheck:
    """One program-level invariant.  Same metadata surface as the AST
    tier's Rule so the CLI/SARIF catalog and ``--rule`` selection treat
    both tiers uniformly."""

    name: str = ""
    rule_id: str = ""
    severity: str = "error"
    description: str = ""

    def check(self, audit: "AuditContext") -> Iterator[Finding]:
        raise NotImplementedError  # pragma: no cover - abstract

    def finding(self, path: str, line: int, message: str,
                hint: str = "") -> Finding:
        lines = _source_lines(path)
        code = lines[line - 1].strip() if 1 <= line <= len(lines) else ""
        return Finding(
            rule=self.name,
            rule_id=self.rule_id,
            severity=self.severity,
            file=display_path(path),
            line=line,
            message=message,
            hint=hint,
            code=code,
        )


class AuditContext:
    """Shared lazily-built artifacts for one jaxlint run: the fused-program
    registry traces each program ONCE (``jaxpr``/``lowered`` memoized per
    program) no matter how many checks read it."""

    def __init__(self):
        self._programs: Optional[list] = None
        self._jaxprs: Dict[str, Any] = {}
        self._lowereds: Dict[str, Any] = {}

    def programs(self) -> list:
        if self._programs is None:
            from distributed_machine_learning_tpu.analysis.jaxlint import (
                programs as programs_lib,
            )

            self._programs = programs_lib.fused_programs()
        return self._programs

    def jaxpr_of(self, prog) -> Any:
        hit = self._jaxprs.get(prog.name)
        if hit is None:
            hit = prog.make_jaxpr()
            self._jaxprs[prog.name] = hit
        return hit

    def lowered_of(self, prog) -> Any:
        hit = self._lowereds.get(prog.name)
        if hit is None:
            hit = prog.lower()
            self._lowereds[prog.name] = hit
        return hit

    def release(self) -> None:
        """Drop every traced/lowered artifact so the transient constants
        they hold (trace-time ``jnp`` literals) free — the zero-live-
        buffers claim is measured after this."""
        self._programs = None
        self._jaxprs.clear()
        self._lowereds.clear()
