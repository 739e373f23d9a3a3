"""DML104 jax-mesh-axis: phantom mesh axes in specs and collectives.

A PartitionSpec or collective that names an axis no mesh carries does not
error — ``clean_spec`` replicates the leaf, GSPMD ignores the constraint —
so a typo'd axis ("tp_" for "tp", a table imported from another stack's
"mp" convention) silently turns sharding off.  On a leased pod that is
discovered only after the pod is wedged (ROADMAP item 1's multi-host
meshes make this strictly worse: the rule table is validated on the
driver, the mesh is built on workers).

Two audits:

* **rule tables** — every axis named by a registered family's specs must
  come from the framework's axis vocabulary
  (``parallel.mesh.CANONICAL_AXES``);
* **programs** — ``sharding_constraint`` equations and collective
  primitives (``psum``/``all_gather``/``ppermute``/...) inside the fused
  sharded programs must name axes of the mesh the program was built
  under (shard_map-bound axis names count as in scope inside their
  bodies).
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from distributed_machine_learning_tpu.analysis.findings import Finding
from distributed_machine_learning_tpu.analysis.jaxlint.base import (
    PKG_DIR,
    AuditContext,
    JaxCheck,
    eqn_line,
    rule_entry_lines,
)

COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "psum2", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_gather", "all_gather_invariant", "all_to_all", "reduce_scatter",
    "axis_index", "pgather",
})


def _spec_axes(spec) -> List[str]:
    out: List[str] = []
    for entry in tuple(spec):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            out.extend(str(a) for a in entry)
        else:
            out.append(str(entry))
    return out


class MeshAxisCheck(JaxCheck):
    name = "jax-mesh-axis"
    rule_id = "DML104"
    severity = "error"
    description = (
        "A PartitionSpec, sharding constraint, or collective names a "
        "mesh axis that does not exist: clean_spec/GSPMD silently drop "
        "it, so the sharding the table claims never happens.  Rule "
        "tables are checked against the framework axis vocabulary "
        "(parallel.mesh.CANONICAL_AXES); fused sharded programs are "
        "checked against the mesh they were built under."
    )
    _HINT = (
        "use an axis from parallel.mesh.CANONICAL_AXES "
        "(dp/sp/tp/ep/pp) — or add the new axis to the vocabulary AND "
        "the meshes that must carry it"
    )

    def check(self, audit: AuditContext) -> Iterator[Finding]:
        from distributed_machine_learning_tpu.analysis.jaxlint.coverage import (
            KNOWN_FAMILY_CONFIGS,
            table_anchor,
        )
        from distributed_machine_learning_tpu.models.partition_rules import (
            PARTITION_RULE_TABLES,
        )

        for family in sorted(KNOWN_FAMILY_CONFIGS):
            rules = PARTITION_RULE_TABLES.get(family)
            if rules is None:
                continue
            path, symbol = table_anchor(family, rules)
            yield from audit_table_axes(
                rules, anchor_path=path, anchor_symbol=symbol,
                family=family, check=self,
            )
        for prog in audit.programs():
            if prog.mesh_axes:
                yield from audit_program_axes(
                    prog, audit.jaxpr_of(prog).jaxpr, check=self
                )


def audit_table_axes(
    rules,
    *,
    anchor_path: str,
    anchor_symbol: Optional[str] = None,
    known_axes: Optional[Sequence[str]] = None,
    family: str = "",
    check: Optional[MeshAxisCheck] = None,
) -> List[Finding]:
    """Every axis a rule table's specs name must be vocabulary."""
    from distributed_machine_learning_tpu.parallel.mesh import (
        CANONICAL_AXES,
    )

    check = check or MeshAxisCheck()
    known = frozenset(known_axes if known_axes is not None
                      else CANONICAL_AXES)
    lines = (
        rule_entry_lines(anchor_path, anchor_symbol) if anchor_symbol
        else []
    )
    fam = f" [{family}]" if family else ""
    findings: List[Finding] = []
    for i, (pattern, spec) in enumerate(rules):
        phantom = [a for a in _spec_axes(spec) if a not in known]
        if phantom:
            line = lines[i] if i < len(lines) else 1
            findings.append(check.finding(
                anchor_path, line,
                f"rule `{pattern}`{fam} names mesh ax"
                f"{'es' if len(phantom) > 1 else 'is'} "
                f"{', '.join(repr(a) for a in phantom)} outside the "
                f"framework vocabulary {sorted(known)} — no mesh will "
                f"ever carry it, so the spec silently replicates",
                check._HINT,
            ))
    return findings


def audit_program_axes(
    prog, jaxpr, *, check: Optional[MeshAxisCheck] = None
) -> List[Finding]:
    """Collectives / sharding constraints in a program vs its build mesh."""
    check = check or MeshAxisCheck()
    mesh_axes = frozenset(prog.mesh_axes or ())
    findings: List[Finding] = []
    seen = set()

    def emit(eqn, message: str) -> None:
        site = eqn_line(eqn, PKG_DIR)
        path, line = site if site else (prog.anchor_path, prog.anchor_line)
        if (path, line, message) in seen:
            return
        seen.add((path, line, message))
        findings.append(check.finding(path, line, message, check._HINT))

    for eqn, bound in _walk_with_bound_axes(jaxpr, frozenset()):
        name = eqn.primitive.name
        in_scope = mesh_axes | bound
        if name == "sharding_constraint":
            sharding = eqn.params.get("sharding")
            spec = getattr(sharding, "spec", None)
            if spec is None:
                continue
            phantom = [a for a in _spec_axes(spec) if a not in in_scope]
            if phantom:
                emit(eqn,
                     f"sharding constraint in program `{prog.name}` "
                     f"names ax{'es' if len(phantom) > 1 else 'is'} "
                     f"{', '.join(repr(a) for a in phantom)} not in the "
                     f"program's mesh {sorted(mesh_axes)}")
        elif name in COLLECTIVE_PRIMITIVES:
            axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
            if not isinstance(axes, (tuple, list)):
                axes = (axes,)
            phantom = [str(a) for a in axes
                       if isinstance(a, str) and str(a) not in in_scope]
            if phantom:
                emit(eqn,
                     f"collective `{name}` in program `{prog.name}` "
                     f"names ax{'es' if len(phantom) > 1 else 'is'} "
                     f"{', '.join(repr(a) for a in phantom)} not in the "
                     f"program's mesh {sorted(mesh_axes)}")
    return findings


def _walk_with_bound_axes(
    jaxpr, bound: frozenset
) -> Iterator[Tuple[object, frozenset]]:
    """Like base.iter_eqns but tracking axis names bound by enclosing
    binders (shard_map in_names; pjit meshes) — a psum over a shard_map
    axis is sound inside that body."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn, bound
        inner = bound
        if eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            names = getattr(mesh, "axis_names", ()) or ()
            inner = bound | frozenset(str(a) for a in names)
        for v in eqn.params.values():
            for sub in _subs(v, jax):
                yield from _walk_with_bound_axes(sub, inner)


def _subs(value, jax):
    from jax.extend import core as jcore

    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subs(v, jax)
