"""dmlint rules: the invariants this codebase has already been bitten by.

Every rule here is a postmortem turned executable (ISSUE 6; rule catalog
with the war stories in docs/static-analysis.md):

* DML001 ``donation-alias`` — PR 4's epoch-6 checkpoint carrying epoch-8
  optimizer counts: ``np.asarray`` on a CPU-backed ``jax.Array`` aliases
  the device buffer, and a donated buffer is overwritten in place by the
  next step.
* DML003 ``chaos-determinism`` — PR 3 shipped two flaky tests because
  fault decisions hashed run-varying absolute paths; a fault plan that
  consults wall time, PIDs, or ``random`` is a flake generator.
* DML004 ``wallclock-deadline`` — lease expiry and wait deadlines on
  ``time.time()`` break under NTP steps; ``liveness.py`` got this right,
  ``tune/cluster.py`` and ``ckpt/writer.py`` did not.
* DML005 ``pickle-checkpoint`` — checkpoint bytes must stay process- and
  framework-portable (and unpickling shared-storage bytes executes code);
  previously an ad-hoc source scan in tests/test_import_guard.py.
* DML006 ``import-trace`` — module-level jit/jnp work is hidden startup
  cost every process pays (trial children, serve replicas, workers).
* DML007 ``thread-swallow`` — a background thread whose broad ``except``
  body is just ``pass`` turns failures into silence; silence is the fault
  class the whole liveness layer exists to catch.

Rules are deliberately project-native: they encode THIS repo's idioms
(``_is_jax_array`` guards, FaultPlan decision methods) rather than generic
lint heuristics, which is what keeps the false-positive rate at zero on
the gate (tests/test_analysis.py).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Sequence, Set

from distributed_machine_learning_tpu.analysis.findings import Finding

# Modules that serialize/deserialize checkpoint or bundle bytes — the ONE
# allowlist for the pickle-free invariant (tests/test_import_guard.py
# consumes this rule instead of keeping its own copy).
CHECKPOINT_PATH_PATTERNS = (
    "ckpt/",
    "tune/checkpoint.py",
    "tune/storage.py",
    "serve/export.py",
)


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for an Attribute/Name chain; None for computed bases."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_name(node: ast.Call) -> Optional[str]:
    return _dotted(node.func)


def _identifiers(node: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
    return out


class Rule:
    """One invariant.  Subclasses set the metadata and implement check()."""

    name: str = ""
    rule_id: str = ""
    severity: str = "error"
    description: str = ""

    def applies(self, ctx) -> bool:
        return True

    def check(self, ctx) -> Iterator[Finding]:  # pragma: no cover - abstract
        raise NotImplementedError

    def finding(self, ctx, node: ast.AST, message: str,
                hint: str = "") -> Finding:
        line = getattr(node, "lineno", 1)
        code = ""
        if 1 <= line <= len(ctx.lines):
            code = ctx.lines[line - 1].strip()
        return Finding(
            rule=self.name,
            rule_id=self.rule_id,
            severity=self.severity,
            file=ctx.display_path,
            line=line,
            message=message,
            hint=hint,
            code=code,
        )


# --------------------------------------------------------------------------
# DML001 donation-alias
# --------------------------------------------------------------------------


_JAX_ARRAY_GUARD_FNS = re.compile(r"^_?is_jax_array$")


class DonationAliasRule(Rule):
    name = "donation-alias"
    rule_id = "DML001"
    severity = "error"
    description = (
        "np.asarray / np.array(copy=False) / .view() on a value that is (or "
        "may be) a jax.Array aliases the device buffer zero-copy on CPU "
        "backends; if that buffer was donated (donate_argnums) the next "
        "step overwrites it in place and the 'snapshot' silently mutates."
    )
    _HINT = (
        "take a real copy: np.array(x, copy=True) (or np.asarray(x).copy() "
        "before the next dispatch)"
    )

    def check(self, ctx) -> Iterator[Finding]:
        tree = ctx.tree
        # Pass 1 (module-wide): names bound to jit-with-donation programs,
        # then names bound to their call results.
        donated_fns: Set[str] = set()
        donated_results: Set[str] = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            value = node.value
            if not isinstance(value, ast.Call):
                continue
            callee = _call_name(value)
            targets = [
                t.id for t in node.targets if isinstance(t, ast.Name)
            ]
            if callee in ("jax.jit", "jit", "pjit", "jax.pjit") and any(
                kw.arg in ("donate_argnums", "donate_argnames")
                for kw in value.keywords
            ):
                donated_fns.update(targets)
            elif callee in donated_fns:
                donated_results.update(targets)
                for t in node.targets:  # tuple-unpacked results taint all
                    if isinstance(t, (ast.Tuple, ast.List)):
                        donated_results.update(
                            e.id for e in t.elts if isinstance(e, ast.Name)
                        )
        # Pass 2: aliasing ops on tainted or isinstance-guarded names.
        yield from self._walk_stmts(tree.body, frozenset(), donated_fns,
                                    donated_results, ctx)

    def _guarded_names(self, test: ast.AST) -> Set[str]:
        """Names proven to be jax.Arrays by this if-test."""
        out: Set[str] = set()
        tests = (
            test.values if isinstance(test, ast.BoolOp)
            and isinstance(test.op, ast.And) else [test]
        )
        for t in tests:
            if not isinstance(t, ast.Call):
                continue
            callee = _call_name(t) or ""
            arg = t.args[0] if t.args else None
            if not isinstance(arg, ast.Name):
                continue
            if callee == "isinstance" and len(t.args) == 2:
                cls = _dotted(t.args[1]) or ""
                if cls.endswith("Array") and cls.startswith("jax"):
                    out.add(arg.id)
            elif _JAX_ARRAY_GUARD_FNS.match(callee.rsplit(".", 1)[-1]):
                out.add(arg.id)
        return out

    def _walk_stmts(self, stmts: Sequence[ast.stmt], guarded: frozenset,
                    donated_fns: Set[str], donated_results: Set[str],
                    ctx) -> Iterator[Finding]:
        """Statement-list walk threading the set of names an enclosing
        ``isinstance(x, jax.Array)`` / ``_is_jax_array(x)`` test proved to
        be device arrays: the guard holds inside the if-arm (including
        nested compound statements) and is dropped in the else-arm."""
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._walk_stmts(
                    stmt.body, frozenset(), donated_fns, donated_results, ctx
                )
                continue
            if isinstance(stmt, ast.If):
                extra = frozenset(self._guarded_names(stmt.test))
                yield from self._check_expr(
                    stmt.test, guarded, donated_fns, donated_results, ctx
                )
                yield from self._walk_stmts(
                    stmt.body, guarded | extra, donated_fns,
                    donated_results, ctx
                )
                yield from self._walk_stmts(
                    stmt.orelse, guarded - extra, donated_fns,
                    donated_results, ctx
                )
                continue
            header_exprs: List[ast.AST] = []
            bodies: List[Sequence[ast.stmt]] = []
            for _, value in ast.iter_fields(stmt):
                if isinstance(value, list) and value:
                    if isinstance(value[0], ast.stmt):
                        bodies.append(value)
                    elif isinstance(value[0], ast.excepthandler):
                        bodies.extend(h.body for h in value)
                    else:
                        header_exprs.extend(
                            v for v in value if isinstance(v, ast.AST)
                        )
                elif isinstance(value, ast.AST):
                    header_exprs.append(value)
            if not bodies:  # simple statement: scan the whole subtree
                yield from self._check_expr(
                    stmt, guarded, donated_fns, donated_results, ctx
                )
                continue
            for expr in header_exprs:
                yield from self._check_expr(
                    expr, guarded, donated_fns, donated_results, ctx
                )
            for body in bodies:
                yield from self._walk_stmts(
                    body, guarded, donated_fns, donated_results, ctx
                )

    def _check_expr(self, node: ast.AST, guarded: frozenset, donated_fns,
                    donated_results, ctx) -> Iterator[Finding]:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                yield from self._check_call(
                    sub, guarded, donated_fns, donated_results, ctx
                )

    def _check_call(self, node: ast.Call, guarded: frozenset, donated_fns,
                    donated_results, ctx) -> Iterator[Finding]:
        tainted = set(guarded) | donated_results
        callee = _call_name(node) or ""

        def _is_tainted(arg: ast.AST) -> Optional[str]:
            if isinstance(arg, ast.Name) and arg.id in tainted:
                return arg.id
            if (
                isinstance(arg, ast.Call)
                and (_call_name(arg) or "") in donated_fns
            ):
                return _call_name(arg)
            return None

        arg = node.args[0] if node.args else None
        if callee in ("np.asarray", "numpy.asarray") and arg is not None:
            who = _is_tainted(arg)
            if who:
                yield self.finding(
                    ctx, node,
                    f"np.asarray({who}) may alias a donated device buffer "
                    f"({who} is a jax.Array here); the next donated step "
                    f"mutates the 'snapshot' in place",
                    self._HINT,
                )
        elif callee in ("np.array", "numpy.array") and arg is not None:
            copy_kw = next(
                (kw for kw in node.keywords if kw.arg == "copy"), None
            )
            explicit_no_copy = (
                copy_kw is not None
                and isinstance(copy_kw.value, ast.Constant)
                and copy_kw.value.value is False
            )
            who = _is_tainted(arg)
            if who and explicit_no_copy:
                yield self.finding(
                    ctx, node,
                    f"np.array({who}, copy=False) aliases a donated device "
                    f"buffer",
                    self._HINT,
                )
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "view"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in tainted
        ):
            yield self.finding(
                ctx, node,
                f"{node.func.value.id}.view() aliases a donated device "
                f"buffer",
                self._HINT,
            )


# --------------------------------------------------------------------------
# DML003 chaos-determinism
# --------------------------------------------------------------------------


_NONDET_CALLS = {
    "time.time": "wall-clock time varies per run",
    "time.time_ns": "wall-clock time varies per run",
    "os.getpid": "PIDs vary per run",
    "os.urandom": "OS entropy is nondeterministic",
    "os.getcwd": "the working directory varies per run/host",
    "os.path.abspath": "absolute paths embed run-varying directories",
    "os.path.realpath": "absolute paths embed run-varying directories",
    "uuid.uuid1": "uuid1 mixes host/time state",
    "uuid.uuid4": "uuid4 is OS entropy",
    "datetime.now": "wall-clock time varies per run",
    "datetime.datetime.now": "wall-clock time varies per run",
}
_NONDET_PREFIXES = ("random.", "secrets.", "tempfile.")
_NONDET_BUILTINS = {
    "hash": "hash() is salted per process (PYTHONHASHSEED)",
    "id": "id() is an address — varies per run",
}


def _nondet_reason(callee: str) -> Optional[str]:
    """Why a call is nondeterministic, or None.  Shared by DML003 (this
    file's sites) and DML013 (sites reached through the call graph)."""
    why = _NONDET_CALLS.get(callee)
    if why is None and callee.startswith(_NONDET_PREFIXES):
        why = f"{callee.split('.', 1)[0]} state varies per run"
    if why is None and callee in _NONDET_BUILTINS:
        why = _NONDET_BUILTINS[callee]
    return why


class ChaosDeterminismRule(Rule):
    name = "chaos-determinism"
    rule_id = "DML003"
    severity = "error"
    description = (
        "Fault-injection decisions must be a pure function of "
        "(seed, op, key, call-count): wall time, PIDs, random state, or "
        "absolute paths in a decision make the chaos schedule — and every "
        "test built on it — flaky (the PR 3 postmortem)."
    )
    _HINT = (
        "derive the decision from the seeded hash of stable keys "
        "(_hash_fraction) — normalize paths relative to the storage root "
        "before keying on them"
    )

    def applies(self, ctx) -> bool:
        if "chaos-decisions" in ctx.scopes:
            return True
        return ctx.basename == "chaos.py"

    def check(self, ctx) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _call_name(node)
            if callee is None:
                continue
            why = _nondet_reason(callee)
            if why is None:
                continue
            yield self.finding(
                ctx, node,
                f"nondeterministic `{callee}()` in fault-decision code "
                f"({why})",
                self._HINT,
            )


# --------------------------------------------------------------------------
# DML004 wallclock-deadline
# --------------------------------------------------------------------------


_DEADLINE_NAMES = re.compile(
    r"deadline|expir|lease|until|last_seen|last_beat|opened_at"
)
_DEADLINE_EXEMPT = {"leased_at", "_leased_at"}


def _is_wallclock_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    func = node.func
    if isinstance(func, ast.Attribute) and func.attr in ("time", "time_ns"):
        base = _dotted(func.value) or ""
        return base in ("time", "_time") or base.endswith(".time")
    return False


class WallclockDeadlineRule(Rule):
    name = "wallclock-deadline"
    rule_id = "DML004"
    severity = "error"
    description = (
        "time.time() feeding a deadline, lease, or liveness age breaks "
        "under NTP steps and clock slew: a backwards jump can expire a "
        "live worker's lease or stretch a wait forever.  time.monotonic() "
        "is the only clock deadlines may read; keep time.time() for "
        "logged timestamps and durations-for-metrics."
    )
    _HINT = "use time.monotonic() for deadlines/leases/liveness ages"

    def check(self, ctx) -> Iterator[Finding]:
        parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(ctx.tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        for node in ast.walk(ctx.tree):
            if not _is_wallclock_call(node):
                continue
            region = self._statement_region(node, parents)
            if region is None:
                continue
            idents = set()
            for r in region:
                idents |= _identifiers(r)
            idents -= _DEADLINE_EXEMPT
            hits = sorted(
                i for i in idents if _DEADLINE_NAMES.search(i)
            )
            if hits:
                yield self.finding(
                    ctx, node,
                    f"wall-clock time.time() used with "
                    f"{', '.join(repr(h) for h in hits)} — deadlines and "
                    f"liveness ages must survive clock steps",
                    self._HINT,
                )

    def _statement_region(
        self, node: ast.AST, parents: Dict[ast.AST, ast.AST]
    ) -> Optional[List[ast.AST]]:
        """The expressions evaluated WITH the time.time() call: the whole
        simple statement, or just the header of a compound one (examining
        a compound statement's body would charge child statements'
        identifiers to this call)."""
        cur: Optional[ast.AST] = node
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = parents.get(cur)
        if cur is None:
            return None
        if isinstance(cur, (ast.If, ast.While)):
            return [cur.test]
        if isinstance(cur, ast.For):
            return [cur.iter]
        if isinstance(cur, (ast.With, ast.AsyncWith)):
            return [i.context_expr for i in cur.items]
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return list(cur.args.defaults) + list(cur.args.kw_defaults or [])
        return [cur]


# --------------------------------------------------------------------------
# DML005 pickle-checkpoint
# --------------------------------------------------------------------------


_PICKLE_MODULES = {"pickle", "cloudpickle", "dill", "shelve"}


class PickleCheckpointRule(Rule):
    name = "pickle-checkpoint"
    rule_id = "DML005"
    severity = "error"
    description = (
        "Checkpoint/bundle bytes must stay process- and framework-portable "
        "(msgpack blob, sharded chunk+JSON, bundle manifests): pickle ties "
        "the format to one Python build, and unpickling shared-storage "
        "bytes executes code.  Pickle stays legal in the process-executor "
        "IPC frames — same host, same build, private pipe — but never in "
        "anything that writes or reads checkpoint bytes."
    )
    _HINT = (
        "serialize through ckpt/format.py (msgpack / chunk+JSON) instead"
    )

    def applies(self, ctx) -> bool:
        if "checkpoint-path" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(
            f"/{pat}" in f"/{rel}" or rel.endswith(pat.rstrip("/"))
            or f"/{pat.rstrip('/')}/" in f"/{rel}"
            for pat in CHECKPOINT_PATH_PATTERNS
        )

    def check(self, ctx) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    root = alias.name.split(".", 1)[0]
                    if root in _PICKLE_MODULES:
                        yield self.finding(
                            ctx, node,
                            f"`import {alias.name}` on a checkpoint-path "
                            f"module",
                            self._HINT,
                        )
            elif isinstance(node, ast.ImportFrom):
                root = (node.module or "").split(".", 1)[0]
                if root in _PICKLE_MODULES:
                    yield self.finding(
                        ctx, node,
                        f"`from {node.module} import ...` on a "
                        f"checkpoint-path module",
                        self._HINT,
                    )
            elif isinstance(node, ast.Call):
                callee = _call_name(node) or ""
                base, _, attr = callee.rpartition(".")
                if base in _PICKLE_MODULES and attr in (
                    "load", "loads", "dump", "dumps", "Pickler", "Unpickler",
                ):
                    yield self.finding(
                        ctx, node,
                        f"`{callee}()` on a checkpoint-path module",
                        self._HINT,
                    )


# --------------------------------------------------------------------------
# DML006 import-trace
# --------------------------------------------------------------------------


_DISPATCH_PREFIXES = ("jnp.", "jax.numpy.", "jax.random.")
_IMPORT_TRACE_EXACT = {
    "jax.device_put", "jax.device_get", "jax.devices",
    "jax.local_devices", "jax.eval_shape", "jax.make_jaxpr",
    "jax.block_until_ready",
}


class ImportTraceRule(Rule):
    name = "import-trace"
    rule_id = "DML006"
    severity = "error"
    description = (
        "Module-level jnp/jax work (array ops, key creation, device "
        "enumeration, calling a jitted program) runs at import: hidden "
        "startup cost EVERY process pays — trial children, serve replicas, "
        "cluster workers — exactly the latency compilecache/ exists to "
        "kill.  Enforced dynamically by tests/test_import_guard.py's "
        "compile-counter sweep; this rule names the offending line."
    )
    _HINT = "move the computation behind a function (lazy, per first use)"

    def check(self, ctx) -> Iterator[Finding]:
        yield from self._visit_module_level(ctx.tree, ctx)

    def _trace_worthy(self, node: ast.Call) -> Optional[str]:
        if isinstance(node.func, ast.Call):  # jitted-and-called in one go
            inner = _call_name(node.func) or ""
            if inner in ("jax.jit", "jit", "pjit", "jax.pjit", "jax.pmap"):
                return f"{inner}(...)(...)"
        callee = _call_name(node)
        if callee is None:
            return None
        if callee.startswith(_DISPATCH_PREFIXES):
            return callee
        if callee in _IMPORT_TRACE_EXACT:
            return callee
        return None

    def _visit_module_level(self, node: ast.AST, ctx) -> Iterator[Finding]:
        """Walk code that executes at import: module body, class bodies,
        module-level control flow — NOT function bodies (deferred), but
        including function DEFAULT arguments (evaluated at def time)."""
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for default in list(child.args.defaults) + [
                    d for d in (child.args.kw_defaults or []) if d is not None
                ]:
                    for sub in ast.walk(default):
                        if isinstance(sub, ast.Call):
                            what = self._trace_worthy(sub)
                            if what:
                                yield self.finding(
                                    ctx, sub,
                                    f"`{what}` in a default argument runs "
                                    f"at import",
                                    self._HINT,
                                )
                continue  # body is deferred
            if isinstance(child, ast.Lambda):
                continue
            if isinstance(child, ast.Call):
                what = self._trace_worthy(child)
                if what:
                    yield self.finding(
                        ctx, child,
                        f"module-level `{what}` runs at import — startup "
                        f"cost for every process",
                        self._HINT,
                    )
            yield from self._visit_module_level(child, ctx)


# --------------------------------------------------------------------------
# DML007 thread-swallow
# --------------------------------------------------------------------------


_BROAD_EXC = {"Exception", "BaseException"}


class ThreadSwallowRule(Rule):
    name = "thread-swallow"
    rule_id = "DML007"
    severity = "error"
    description = (
        "A bare/over-broad `except` whose body is just `pass` inside a "
        "thread target converts failures into the exact silence the "
        "liveness layer exists to detect.  Swallowing is sometimes right "
        "(observer isolation) — but then it must COUNT: increment a "
        "counter, log, or re-raise, so /metrics and snapshots can surface "
        "that it happened."
    )
    _HINT = (
        "count it (metrics counter), log it, narrow the except, or "
        "re-raise; if the swallow is deliberate, say why inline: "
        "# dmlint: disable=thread-swallow <reason>"
    )

    def check(self, ctx) -> Iterator[Finding]:
        targets = self._thread_targets(ctx.tree)
        if not targets:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name not in targets:
                continue
            # Nested defs stay in scope: a closure called by the target
            # still runs on the thread.
            for sub in ast.walk(node):
                if not isinstance(sub, ast.ExceptHandler):
                    continue
                if not self._is_broad(sub):
                    continue
                if self._body_is_silent(sub.body):
                    yield self.finding(
                        ctx, sub,
                        f"broad `except` swallowed silently inside thread "
                        f"target `{node.name}` — the thread keeps running "
                        f"with no record the failure happened",
                        self._HINT,
                    )

    def _thread_targets(self, tree: ast.AST) -> Set[str]:
        """Function names used as thread entry points in this module."""
        out: Set[str] = set()
        thread_classes: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                callee = (_call_name(node) or "").rsplit(".", 1)[-1]
                if callee in ("Thread", "Timer"):
                    for kw in node.keywords:
                        if kw.arg in ("target", "function"):
                            name = self._callable_name(kw.value)
                            if name:
                                out.add(name)
                    if callee == "Timer" and len(node.args) >= 2:
                        name = self._callable_name(node.args[1])
                        if name:
                            out.add(name)
            elif isinstance(node, ast.ClassDef):
                bases = {(_dotted(b) or "").rsplit(".", 1)[-1]
                         for b in node.bases}
                if "Thread" in bases:
                    thread_classes.add(node.name)
        if thread_classes:
            for node in ast.walk(tree):
                if (
                    isinstance(node, ast.ClassDef)
                    and node.name in thread_classes
                ):
                    out.add("run")
        return out

    @staticmethod
    def _callable_name(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            return node.attr
        return None

    @staticmethod
    def _is_broad(handler: ast.ExceptHandler) -> bool:
        if handler.type is None:
            return True
        types = (
            handler.type.elts
            if isinstance(handler.type, ast.Tuple) else [handler.type]
        )
        for t in types:
            name = (_dotted(t) or "").rsplit(".", 1)[-1]
            if name in _BROAD_EXC:
                return True
        return False

    @staticmethod
    def _body_is_silent(body: Sequence[ast.stmt]) -> bool:
        for stmt in body:
            if isinstance(stmt, (ast.Pass, ast.Continue, ast.Break)):
                continue
            if isinstance(stmt, ast.Expr) and isinstance(
                stmt.value, ast.Constant
            ):
                continue  # docstring / ellipsis
            return False
        return True


# --------------------------------------------------------------------------
# DML008 undonated-hot-jit
# --------------------------------------------------------------------------


# Hot-path modules that opted in: every train-step-shaped jit here must
# donate its state buffers (ISSUE 7's donation audit — an undonated hot
# program doubles params+opt HBM on every step and was part of the 0.31
# flagship MFU).
HOT_JIT_PATH_PATTERNS = (
    "parallel/",
    "tune/vectorized.py",
    "tune/trainable",
    "bench.py",       # the flagship measure loops ARE the MFU evidence
    "benchmarks/",
)

_PARAMS_ARG = re.compile(r"^params?$")
_OPT_ARG = re.compile(r"^(opt|opt_state|optimizer_state)$")
_JIT_NAMES = ("jax.jit", "jit", "pjit", "jax.pjit")


class UndonatedHotJitRule(Rule):
    name = "undonated-hot-jit"
    rule_id = "DML008"
    severity = "error"
    description = (
        "A jax.jit that threads BOTH params and optimizer state "
        "positionally is a train step: it must pass donate_argnums (or "
        "donate_argnames) so the old params/opt buffers are reused in "
        "place — undonated, every step holds two copies of the largest "
        "arrays in HBM and the copy shows up as step time.  Enforced in "
        "opted-in hot-path modules (parallel/, tune/vectorized.py, "
        "tune/trainable*.py) and for ANY jit with explicit "
        "in_shardings/out_shardings (a sharded program's state is by "
        "definition the big memory).  Eval-shaped programs (params only, "
        "no optimizer state) are exempt — donating read-only params "
        "would destroy them."
    )
    _HINT = (
        "add donate_argnums covering the params/opt_state arguments "
        "(and pin matching out_shardings so the alias is realizable)"
    )

    def applies(self, ctx) -> bool:
        return True  # the sharded-jit trigger is location-independent

    def _in_hot_module(self, ctx) -> bool:
        if "hot-jit" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in HOT_JIT_PATH_PATTERNS)

    @staticmethod
    def _has_kw(call: ast.Call, *names) -> bool:
        return any(kw.arg in names for kw in call.keywords)

    @staticmethod
    def _positional_params(fn) -> List[str]:
        args = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        return [a for a in args if a != "self"]

    def _is_train_step_signature(self, names: List[str]) -> bool:
        return any(_PARAMS_ARG.match(n) for n in names) and any(
            _OPT_ARG.match(n) for n in names
        )

    def _resolve_fn(self, node: ast.AST, defs: Dict[str, ast.AST]):
        """The traced callable's def, when statically resolvable: an
        inline lambda, or a Name bound to a def in this module.  Attribute
        callees (tx.init, self.step) are unresolvable -> never flagged."""
        if isinstance(node, ast.Lambda):
            return node
        if isinstance(node, ast.Name):
            return defs.get(node.id)
        if isinstance(node, ast.Call):
            # jit(make_epoch_fn(...)) — the factory's return signature is
            # not visible here; skip rather than guess.
            return None
        return None

    def check(self, ctx) -> Iterator[Finding]:
        hot = self._in_hot_module(ctx)
        defs: Dict[str, ast.AST] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, node)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                callee = _call_name(node) or ""
                if callee not in _JIT_NAMES or not node.args:
                    continue
                if self._has_kw(node, "donate_argnums", "donate_argnames"):
                    continue
                sharded = self._has_kw(node, "in_shardings", "out_shardings")
                if not (hot or sharded):
                    continue
                fn = self._resolve_fn(node.args[0], defs)
                if fn is None:
                    continue
                names = self._positional_params(fn)
                if not self._is_train_step_signature(names):
                    continue
                yield self.finding(
                    ctx, node,
                    f"`{callee}` of a train-step-shaped function "
                    f"({', '.join(names[:3])}, ...) without donate_argnums"
                    + (" on a sharded program" if sharded else
                       " in a hot-path module"),
                    self._HINT,
                )
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    callee = _dotted(target) or ""
                    if callee not in _JIT_NAMES:
                        continue
                    if isinstance(dec, ast.Call) and self._has_kw(
                        dec, "donate_argnums", "donate_argnames"
                    ):
                        continue
                    sharded = isinstance(dec, ast.Call) and self._has_kw(
                        dec, "in_shardings", "out_shardings"
                    )
                    if not (hot or sharded):
                        continue
                    names = self._positional_params(node)
                    if not self._is_train_step_signature(names):
                        continue
                    yield self.finding(
                        ctx, dec,
                        f"@{callee} on train-step-shaped `{node.name}"
                        f"({', '.join(names[:3])}, ...)` without "
                        f"donate_argnums",
                        self._HINT,
                    )


# --------------------------------------------------------------------------
# DML009 unbounded-queue
# --------------------------------------------------------------------------


# Serving request-path modules: anything a /predict request's bytes flow
# through.  export.py is deliberately absent (bundle IO, no request path).
SERVE_REQUEST_PATH_PATTERNS = (
    "serve/batcher.py",
    "serve/engine.py",
    "serve/replica.py",
    "serve/server.py",
    "serve/metrics.py",
    "serve/autoscale.py",
    "serve/swap.py",
    "serve/gang.py",
    "serve/_gang_member.py",
)

_QUEUE_CTORS = {"Queue", "LifoQueue", "PriorityQueue"}


class UnboundedQueueRule(Rule):
    name = "unbounded-queue"
    rule_id = "DML009"
    severity = "error"
    description = (
        "queue.Queue()/collections.deque() without a maxsize/maxlen bound "
        "in a serve/ request-path module: overload then accumulates "
        "instead of shedding — admission control cannot refuse what an "
        "unbounded queue already swallowed, latency grows without limit, "
        "and the process OOMs instead of answering 429.  Every request-"
        "path queue must carry an explicit bound (SimpleQueue has none "
        "and is always flagged)."
    )
    _HINT = (
        "bound it: Queue(maxsize=N) / deque(maxlen=N), and shed at "
        "admission (QueueFull -> 429 + Retry-After) when it fills"
    )

    def applies(self, ctx) -> bool:
        if "serve-request-path" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in SERVE_REQUEST_PATH_PATTERNS)

    @staticmethod
    def _is_unbounded_const(node: ast.AST) -> bool:
        """maxsize=0 / maxsize=-1 / maxlen=None are spelled-out
        unboundedness, not bounds."""
        return isinstance(node, ast.Constant) and node.value in (0, None) \
            or (
                isinstance(node, ast.UnaryOp)
                and isinstance(node.op, ast.USub)
                and isinstance(node.operand, ast.Constant)
            )

    def check(self, ctx) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _call_name(node) or ""
            base, _, attr = callee.rpartition(".")
            if attr == "SimpleQueue" or (
                not base and callee == "SimpleQueue"
            ):
                yield self.finding(
                    ctx, node,
                    "SimpleQueue has no capacity bound at all — a "
                    "request-path queue must be boundable",
                    self._HINT,
                )
                continue
            name = attr or callee
            if name in _QUEUE_CTORS:
                bound = node.args[0] if node.args else next(
                    (kw.value for kw in node.keywords
                     if kw.arg == "maxsize"), None,
                )
                if bound is None or self._is_unbounded_const(bound):
                    yield self.finding(
                        ctx, node,
                        f"`{callee}()` without a positive maxsize on the "
                        f"serve request path",
                        self._HINT,
                    )
            elif name == "deque":
                bound = node.args[1] if len(node.args) >= 2 else next(
                    (kw.value for kw in node.keywords
                     if kw.arg == "maxlen"), None,
                )
                if bound is None or self._is_unbounded_const(bound):
                    yield self.finding(
                        ctx, node,
                        f"`{callee}()` without a maxlen bound on the "
                        f"serve request path",
                        self._HINT,
                    )


# --------------------------------------------------------------------------
# DML010 host-sync-in-scan
# --------------------------------------------------------------------------


# Vectorized hot-loop modules: anything whose scan bodies carry
# population-stacked state (the fused epoch scans, the PBT generation
# scan, the sharded fused epoch program).  Opt-in like DML008.
VECTORIZED_HOT_LOOP_PATTERNS = (
    "tune/vectorized.py",
    "tune/_regression_program.py",
    "tune/trainable",
    "parallel/",
)

_HOST_SYNC_CALLS = {
    "np.asarray", "numpy.asarray", "np.array", "numpy.array",
    "jax.device_get",
}
_SCAN_NAMES = ("jax.lax.scan", "lax.scan")


class HostSyncInScanRule(Rule):
    name = "host-sync-in-scan"
    rule_id = "DML010"
    severity = "error"
    description = (
        "float() / .item() / np.asarray / jax.device_get inside a "
        "lax.scan body: the body is TRACED, so a host conversion on a "
        "population-stacked tracer either crashes at trace time "
        "(ConcretizationTypeError) or silently constant-folds stale "
        "values into the compiled program — and any survivor is a host "
        "round-trip in the one loop the in-device design exists to keep "
        "on device (the PBT generation scan dispatches ONCE per chunk "
        "precisely because nothing inside it syncs).  Enforced in "
        "opted-in vectorized hot-loop modules."
    )
    _HINT = (
        "keep the scan body pure jnp (where/gather/cumsum replace host "
        "logic); sync AFTER the dispatch returns — np.asarray on the "
        "stacked outputs at the dispatch boundary is the supported place"
    )

    def applies(self, ctx) -> bool:
        if "vectorized-hot-loop" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in VECTORIZED_HOT_LOOP_PATTERNS)

    def _scan_bodies(self, scope: ast.AST) -> List[ast.AST]:
        """Function defs / lambdas passed as a scan's body WITHIN one
        enclosing scope (this codebase's idiom: the body is a nested def
        right next to its lax.scan call)."""
        local_defs: Dict[str, ast.AST] = {}
        for node in ast.walk(scope):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local_defs.setdefault(node.name, node)
        bodies: List[ast.AST] = []
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call):
                continue
            if (_call_name(node) or "") not in _SCAN_NAMES or not node.args:
                continue
            fn = node.args[0]
            if isinstance(fn, ast.Lambda):
                bodies.append(fn)
            elif isinstance(fn, ast.Name) and fn.id in local_defs:
                bodies.append(local_defs[fn.id])
        return bodies

    def check(self, ctx) -> Iterator[Finding]:
        seen: Set[int] = set()
        for body in self._scan_bodies(ctx.tree):
            if id(body) in seen:
                continue
            seen.add(id(body))
            for node in ast.walk(body):
                if not isinstance(node, ast.Call):
                    continue
                callee = _call_name(node) or ""
                what = None
                if callee == "float" and node.args:
                    what = "float(...)"
                elif callee in _HOST_SYNC_CALLS:
                    what = f"{callee}(...)"
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and not node.args
                ):
                    what = ".item()"
                if what:
                    yield self.finding(
                        ctx, node,
                        f"host sync `{what}` inside a lax.scan body — "
                        f"population-stacked values are tracers here; this "
                        f"either fails to trace or bakes a stale constant "
                        f"into the compiled hot loop",
                        self._HINT,
                    )


# --------------------------------------------------------------------------
# DML011 blocking-transfer-in-loop
# --------------------------------------------------------------------------


# Hot input-path modules: anywhere an epoch/step loop moves training bytes
# host->device.  Opt-in like DML008/DML010.  tune/vectorized.py is
# deliberately absent: its in-loop transfers are dispatch-BOUNDARY control
# ops (row selectors, per-row lr/wd vectors, population re-pins after a
# compaction), a few KB between whole-population programs — not per-batch
# training data the device waits on.
HOT_INPUT_LOOP_PATTERNS = (
    "tune/trainable",
    "data/pipeline.py",
    "bench.py",
    "benchmarks/",
)

_TRANSFER_CALLS = {
    "jax.device_put",
    "jnp.asarray", "jax.numpy.asarray",
    "jnp.array", "jax.numpy.array",
}


class BlockingTransferInLoopRule(Rule):
    name = "blocking-transfer-in-loop"
    rule_id = "DML011"
    severity = "error"
    description = (
        "jax.device_put / jnp.asarray of host data inside a for/while "
        "epoch loop in a hot input-path module: every iteration pays a "
        "BLOCKING host->device transfer the device must wait on — zero "
        "host/device overlap, exactly the duty-cycle leak the streaming "
        "prefetch ring (data/pipeline.py) exists to close.  Enforced in "
        "opted-in hot input-path modules."
    )
    _HINT = (
        "stage through the prefetch ring (data/pipeline.ChunkPrefetcher "
        "device_puts chunk k+1 on a producer thread while the device "
        "consumes chunk k) or hoist the transfer above the loop"
    )

    def applies(self, ctx) -> bool:
        if "hot-input-loop" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in HOT_INPUT_LOOP_PATTERNS)

    @staticmethod
    def _loop_body_nodes(loop: ast.AST) -> Iterator[ast.AST]:
        """Nodes lexically inside the loop body, NOT descending into
        nested function defs or lambdas — those are traced program bodies
        or producer sources, where the transfer runs off the consumer's
        critical path (the prefetch-ring idiom itself)."""
        stack: List[ast.AST] = list(loop.body) + list(loop.orelse)
        while stack:
            node = stack.pop()
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    @staticmethod
    def _generator_loops(tree: ast.AST) -> Set[int]:
        """Loops inside GENERATOR functions are exempt: a ``yield``-ing
        source that device_puts per chunk IS the prefetch-ring idiom —
        the producer thread pulls it while the consumer computes, so the
        transfer is off the critical path by construction."""
        exempt: Set[int] = set()
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            has_yield = any(
                isinstance(n, (ast.Yield, ast.YieldFrom))
                for n in ast.walk(fn)
            )
            if has_yield:
                exempt.update(
                    id(n) for n in ast.walk(fn)
                    if isinstance(n, (ast.For, ast.While))
                )
        return exempt

    def check(self, ctx) -> Iterator[Finding]:
        seen: Set[int] = set()
        exempt_loops = self._generator_loops(ctx.tree)
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            if id(loop) in exempt_loops:
                continue
            for node in self._loop_body_nodes(loop):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                callee = _call_name(node) or ""
                if callee in _TRANSFER_CALLS:
                    seen.add(id(node))
                    yield self.finding(
                        ctx, node,
                        f"blocking `{callee}(...)` inside a for/while loop "
                        f"— a per-iteration host->device transfer the "
                        f"device waits on (no overlap)",
                        self._HINT,
                    )


# --------------------------------------------------------------------------
# DML015 bare-counter-increment
# --------------------------------------------------------------------------


# Modules already wired into the unified metrics registry (obs/registry.py):
# new telemetry there must register, not grow a seventh private family.
OBS_INSTRUMENTED_PATTERNS = (
    "serve/",
    "liveness.py",
    "data/pipeline.py",
    "obs/",
    "perf/",
    "ckpt/metrics.py",
    "compilecache/counters.py",
    "chaos.py",
)

# Names that read as telemetry counters (not loop indices, not data rows).
_COUNTER_NAME_RE = re.compile(
    r"(?:_total|_totals|_count|_counts|_errors|_failures|_hits|_misses|"
    r"_flushes|_dumps|_skips|_stalls|_crashes|_kills|_requeues|_retries|"
    r"_drops|_dropped|_expiries)$"
    r"|^(?:errors|failures|hits|misses|sheds|timeouts|redispatches|"
    r"restarts|requeues|recoveries|stalls|kills|crashes|rejected|rejects|"
    r"drops|dropped|swaps|exports|dumps)$"
)

_PROVIDER_METHOD_RE = re.compile(r"^(?:snapshot|stats|to_dict)$|_stats$")


class BareCounterIncrementRule(Rule):
    name = "bare-counter-increment"
    rule_id = "DML015"
    severity = "error"
    description = (
        "ad-hoc `self.<counter> += 1`-style telemetry in an obs-"
        "instrumented module, outside any metrics-provider class: before "
        "obs/registry.py, six subsystems each grew a private counter "
        "family exactly this way — invisible to flight dumps, /metrics, "
        "and the cluster head until someone hand-plumbed it.  A counter "
        "that bypasses the registry cannot be aggregated, dumped, or "
        "asserted on.  Enforced in opted-in modules "
        "(`# dmlint-scope: obs-metrics` or OBS_INSTRUMENTED_PATTERNS)."
    )
    _HINT = (
        "count through the plane: obs.get_registry().add(name) for "
        "one-off counters, or put it in a family class (one exposing "
        "snapshot()/stats()/to_dict()) registered via register_family()"
    )

    def applies(self, ctx) -> bool:
        if "obs-metrics" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in OBS_INSTRUMENTED_PATTERNS)

    @staticmethod
    def _provider_classes(tree: ast.AST) -> Set[int]:
        """Statement ids inside classes that ARE metrics providers — they
        expose an aggregate view (snapshot/stats/to_dict), which is the
        registry's family contract; their internal increments are the
        implementation OF the plane, not a bypass of it."""
        exempt: Set[int] = set()
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            if any(
                isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
                and _PROVIDER_METHOD_RE.search(m.name)
                for m in cls.body
            ):
                exempt.update(id(n) for n in ast.walk(cls))
        return exempt

    def check(self, ctx) -> Iterator[Finding]:
        exempt = self._provider_classes(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.AugAssign) or id(node) in exempt:
                continue
            if not isinstance(node.op, ast.Add):
                continue
            target = node.target
            if not (
                isinstance(target, ast.Attribute)
                and isinstance(target.value, ast.Name)
                and target.value.id == "self"
            ):
                continue
            attr = target.attr
            if attr.startswith("_"):  # private state, not exported telemetry
                continue
            if not _COUNTER_NAME_RE.search(attr):
                continue
            yield self.finding(
                ctx, node,
                f"`self.{attr} += ...` grows a private telemetry counter "
                f"outside any registered family — invisible to the "
                f"metrics registry, flight dumps, and head aggregation",
                self._HINT,
            )


# --------------------------------------------------------------------------
# DML016 local-global-device-confusion
# --------------------------------------------------------------------------

# Modules that run (or may run) under a multi-process jax.distributed
# runtime, where jax.devices() is the GLOBAL view and jax.local_devices()
# the per-host one — conflating them works on one process and breaks the
# moment a mesh spans two.
MULTIHOST_SCOPED_PATTERNS = (
    "multihost/",
)

_LOCAL_NAME_RE = re.compile(r"(?:^|_)(?:local|per_host|host)(?:_|$)")


class LocalGlobalDeviceConfusionRule(Rule):
    name = "local-global-device-confusion"
    rule_id = "DML016"
    severity = "error"
    description = (
        "multihost-scoped code conflating the GLOBAL device/process view "
        "with the per-host one: len(jax.devices()) bound to a per-host "
        "name, jax.devices() sliced by jax.local_device_count() (the "
        "global list is not ordered local-first), or a host-data slice "
        "sized from jax.process_count() in a function that never consults "
        "jax.process_index() — every host would load shard 0.  All three "
        "are single-process-invisible: they pass every test until a mesh "
        "actually spans two processes (ISSUE 14's failure class)."
    )
    _HINT = (
        "per-host sizing: jax.local_device_count()/jax.local_devices(); "
        "per-host data slices: offset by jax.process_index() (or derive "
        "the slice from the sharding — multihost.stage_global does)"
    )

    def applies(self, ctx) -> bool:
        if "multihost" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in MULTIHOST_SCOPED_PATTERNS)

    @staticmethod
    def _is_call_to(node: ast.AST, *names: str) -> bool:
        return (
            isinstance(node, ast.Call)
            and (_call_name(node) or "").rsplit(".", 1)[-1] in names
        )

    def _global_count_expr(self, node: ast.AST) -> bool:
        """len(jax.devices()) or jax.device_count()."""
        if self._is_call_to(node, "device_count"):
            return True
        return (
            self._is_call_to(node, "len")
            and node.args
            and self._is_call_to(node.args[0], "devices")
        )

    def check(self, ctx) -> Iterator[Finding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Module)):
                continue
            body = fn.body if isinstance(fn, ast.Module) else [fn]
            yield from self._check_scope(ctx, fn, body)

    def _check_scope(self, ctx, fn, body) -> Iterator[Finding]:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # Module level: only the assignment checks apply (a module-
            # level slice has no process_index discipline to inherit).
            for node in body:
                if isinstance(node, ast.Assign):
                    yield from self._check_assign(ctx, node)
            return
        local_nodes = list(self._walk_local(fn))
        calls = {
            (_call_name(n) or "").rsplit(".", 1)[-1]
            for n in local_nodes if isinstance(n, ast.Call)
        }
        uses_process_count = "process_count" in calls
        uses_process_index = "process_index" in calls
        # Names sized from the process count — a slice bounded by one of
        # these is a per-host data load.
        per_host_names: Set[str] = set()
        for node in local_nodes:
            if isinstance(node, ast.Assign) and any(
                self._is_call_to(c, "process_count")
                for c in ast.walk(node.value)
            ):
                per_host_names.update(
                    t.id for t in node.targets if isinstance(t, ast.Name)
                )
        for node in local_nodes:
            if isinstance(node, ast.Assign):
                yield from self._check_assign(ctx, node)
            elif isinstance(node, ast.Subscript):
                yield from self._check_subscript(
                    ctx, node, per_host_names,
                    uses_process_count, uses_process_index,
                )

    @staticmethod
    def _walk_local(fn):
        """Walk one function's OWN statements: a nested def is its own
        scope (it gets its own process_index discipline) and is visited
        as its own top-level function by check()."""
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield n
            stack.extend(ast.iter_child_nodes(n))

    def _check_assign(self, ctx, node: ast.Assign) -> Iterator[Finding]:
        if not self._global_count_expr(node.value):
            return
        for t in node.targets:
            if isinstance(t, ast.Name) and _LOCAL_NAME_RE.search(t.id):
                yield self.finding(
                    ctx, node,
                    f"`{t.id}` is sized from the GLOBAL device count "
                    f"(len(jax.devices())/jax.device_count()) — on a "
                    f"multi-process runtime that is every host's devices, "
                    f"not this host's",
                    self._HINT,
                )

    def _check_subscript(self, ctx, node: ast.Subscript, per_host_names,
                         uses_process_count, uses_process_index
                         ) -> Iterator[Finding]:
        if not isinstance(node.slice, ast.Slice):
            return
        # B: jax.devices()[...local_device_count()...] — slicing the
        # global list by the local count assumes local devices come first.
        if self._is_call_to(node.value, "devices"):
            bound_calls = [
                c for b in (node.slice.lower, node.slice.upper) if b
                for c in ast.walk(b)
            ]
            if any(self._is_call_to(c, "local_device_count")
                   for c in bound_calls):
                yield self.finding(
                    ctx, node,
                    "jax.devices() sliced by jax.local_device_count(): "
                    "the global device list is ordered by process index, "
                    "not local-first — this is only this host's devices "
                    "on process 0",
                    "use jax.local_devices()",
                )
                return
        # C: a per-host-sized data slice in a function that divides by
        # process_count but never consults process_index — every host
        # loads the SAME shard.
        if not uses_process_count or uses_process_index:
            return
        for bound in (node.slice.lower, node.slice.upper):
            if bound is None:
                continue
            if any(
                isinstance(n, ast.Name) and n.id in per_host_names
                for n in ast.walk(bound)
            ):
                yield self.finding(
                    ctx, node,
                    "host-data slice sized from jax.process_count() with "
                    "no jax.process_index() offset in scope: every "
                    "process would load the same (first) shard",
                    self._HINT,
                )
                return


# ==========================================================================
# Cross-file rules (dmlint v2): symbol table + call graph + dataflow
# ==========================================================================
#
# Everything below reasons over the WHOLE linted tree at once
# (analysis/callgraph.py builds the project view from the engine's shared
# parse cache; analysis/dataflow.py answers order questions inside one
# function).  The per-file visitors above are structurally blind across a
# function call — PR 4's donation-alias corruption and PR 7's fencing race
# both crossed file boundaries before they bit.

from distributed_machine_learning_tpu.analysis import (  # noqa: E402
    callgraph as callgraph_lib,
    dataflow as dataflow_lib,
)


class ProjectRule(Rule):
    """A rule that runs ONCE over the whole project, not per file.

    The engine builds a single :class:`callgraph.Project` from every
    parsed file and hands it to :meth:`check_project`; findings land in
    whatever file each site lives in and go through the same suppression
    / baseline machinery as per-file findings."""

    def check(self, ctx) -> Iterator[Finding]:
        return iter(())  # per-file entry point intentionally empty

    def check_project(self, project) -> Iterator[Finding]:
        raise NotImplementedError  # pragma: no cover - abstract


def _positions_from(node: ast.AST, module_consts: Dict[str, ast.AST]
                    ) -> Optional[tuple]:
    """A donate_argnums value as a tuple of ints, when statically known:
    a constant int, a tuple/list of constant ints, or a Name bound to one
    at module level (the ``_EPOCH_DONATE = (0, 1, 2)`` idiom)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if not (
                isinstance(e, ast.Constant) and isinstance(e.value, int)
            ):
                return None
            out.append(e.value)
        return tuple(out)
    if isinstance(node, ast.Name) and node.id in module_consts:
        return _positions_from(module_consts[node.id], {})
    return None


def _module_consts(tree: ast.AST) -> Dict[str, ast.AST]:
    out: Dict[str, ast.AST] = {}
    for node in tree.body if hasattr(tree, "body") else []:
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name):
                out[t.id] = node.value
    return out


def _donate_kw(call: ast.Call) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            return kw.value
    return None


# --------------------------------------------------------------------------
# DML012 use-after-donation
# --------------------------------------------------------------------------


class UseAfterDonationRule(ProjectRule):
    name = "use-after-donation"
    rule_id = "DML012"
    severity = "error"
    description = (
        "A name passed at a donate_argnums position of a jitted callable "
        "is READ after the call: donation hands the buffer to XLA for "
        "in-place reuse, so the old value is deleted (RuntimeError on a "
        "real backend) or — with zero-copy aliasing on CPU — silently "
        "overwritten by the next step.  The static twin of the runtime "
        "donation audit (ISSUE 7): the audit proves donation HAPPENED, "
        "this rule proves nobody still depends on the donated value.  "
        "Donation summaries propagate through the call graph, so a "
        "helper that forwards its parameter into a donated position "
        "donates its caller's buffer too (the PR 4 corruption crossed "
        "exactly such a boundary)."
    )
    _HINT = (
        "rebind the result over the donated name "
        "(`params, opt = step(params, opt)`) or snapshot with "
        "np.array(x, copy=True) BEFORE the donating call"
    )

    def check_project(self, project) -> Iterator[Finding]:
        self._mod_bind_cache: Dict[int, Dict[str, tuple]] = {}
        donating_attrs = self._attr_map(project)
        summaries = self._summaries(project, donating_attrs)
        for fn in project.functions.values():
            yield from self._check_fn(
                project, fn, donating_attrs, summaries
            )

    # -- donating-callable discovery ----------------------------------------

    def _jit_donation(self, call: ast.Call, consts) -> Optional[tuple]:
        """Donated positions of a ``jax.jit(..., donate_argnums=...)``
        call expression, else None."""
        callee = _call_name(call) or ""
        if callee not in _JIT_NAMES:
            return None
        kw = _donate_kw(call)
        if kw is None:
            return None
        return _positions_from(kw, consts)

    def _attr_map(self, project) -> Dict[str, tuple]:
        """attr name -> donated positions, for donating programs stored
        as instance attributes (``self.train_epoch = jax.jit(...)``) or
        passed as constructor fields (``Bundle(train_epoch=prog)``).
        Ambiguous attrs (two bindings that disagree) are dropped —
        resolution must never guess."""
        out: Dict[str, tuple] = {}
        dead: Set[str] = set()

        def record(attr: str, pos: tuple) -> None:
            if attr in dead:
                return
            if attr in out and out[attr] != pos:
                del out[attr]
                dead.add(attr)
                return
            out[attr] = pos

        for mod in project.modules.values():
            consts = _module_consts(mod.ctx.tree)
            named: Dict[str, tuple] = {}
            for node in ast.walk(mod.ctx.tree):
                if not isinstance(node, ast.Assign):
                    continue
                if not isinstance(node.value, ast.Call):
                    continue
                pos = self._jit_donation(node.value, consts)
                if pos is None:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Attribute):
                        record(t.attr, pos)
                    elif isinstance(t, ast.Name):
                        named[t.id] = pos
            # constructor fields: Bundle(train_epoch=<donating name>)
            for node in ast.walk(mod.ctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                for kw in node.keywords:
                    if (
                        kw.arg
                        and isinstance(kw.value, ast.Name)
                        and kw.value.id in named
                    ):
                        record(kw.arg, named[kw.value.id])
        return out

    def _summaries(self, project, donating_attrs) -> Dict[str, Set[int]]:
        """qualname -> parameter indices donated THROUGH the function:
        a param forwarded (as a bare name) into a donated position of a
        donating callable inside the body.  Fixpoint over the call graph
        so chains of helpers propagate."""
        summaries: Dict[str, Set[int]] = {}
        for _ in range(10):  # tiny graphs: converges in 2-3 rounds
            changed = False
            for fn in project.functions.values():
                mine = summaries.setdefault(fn.qualname, set())
                for call, positions, _desc in self._donating_calls(
                    project, fn, donating_attrs, summaries
                ):
                    for pos in positions:
                        if pos >= len(call.args):
                            continue
                        arg = call.args[pos]
                        if (
                            isinstance(arg, ast.Name)
                            and arg.id in fn.params
                        ):
                            idx = fn.params.index(arg.id)
                            if idx not in mine:
                                mine.add(idx)
                                changed = True
            if not changed:
                break
        return summaries

    def _donating_calls(self, project, fn, donating_attrs, summaries):
        """(call node, donated positions, callee description) for every
        donating call inside ``fn``'s body."""
        mod = project.modules.get(fn.module)
        consts = _module_consts(mod.ctx.tree) if mod else {}
        # names bound to donating jits or donating attrs, in this
        # function or at module level
        local: Dict[str, tuple] = {}

        def scan_bindings(stmts) -> None:
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    continue  # a nested def's bindings are its own scope
                if isinstance(stmt, ast.Assign):
                    targets = [
                        t.id for t in stmt.targets
                        if isinstance(t, ast.Name)
                    ]
                    if targets:
                        pos: Optional[tuple] = None
                        if isinstance(stmt.value, ast.Call):
                            pos = self._jit_donation(stmt.value, consts)
                        elif isinstance(stmt.value, ast.Attribute):
                            # f = bundle.train_epoch — donating-attr alias
                            pos = donating_attrs.get(stmt.value.attr)
                        if pos is not None:
                            for t in targets:
                                local[t] = pos
                for _, value in ast.iter_fields(stmt):
                    if isinstance(value, list) and value:
                        if isinstance(value[0], ast.stmt):
                            scan_bindings(value)
                        elif isinstance(value[0], ast.excepthandler):
                            for h in value:
                                scan_bindings(h.body)

        if mod:
            cache = getattr(self, "_mod_bind_cache", None)
            if cache is None:
                cache = self._mod_bind_cache = {}
            cached = cache.get(id(mod))
            if cached is None:
                scan_bindings(mod.ctx.tree.body)
                cache[id(mod)] = dict(local)
            else:
                local.update(cached)
        scan_bindings(fn.node.body)
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Name) and func.id in local:
                yield node, local[func.id], func.id
            elif isinstance(func, ast.Attribute):
                if func.attr in donating_attrs:
                    yield node, donating_attrs[func.attr], (
                        _call_name(node) or func.attr
                    )
                    continue
                raw = _dotted(func)
                if raw:
                    target = project.resolve_name(mod, raw, fn.cls) \
                        if mod else None
                    donated = summaries.get(target or "", set())
                    if donated:
                        # self.helper(a) / obj.helper(a): arg i is
                        # param i+1 (the bound receiver fills param 0)
                        offset = 1 if target and project.functions[
                            target
                        ].is_method else 0
                        positions = tuple(
                            p - offset for p in sorted(donated)
                            if p - offset >= 0
                        )
                        if positions:
                            yield node, positions, raw
            elif isinstance(func, ast.Name):
                raw = func.id
                target = project.resolve_name(mod, raw, fn.cls) \
                    if mod else None
                donated = summaries.get(target or "", set())
                if donated:
                    yield node, tuple(sorted(donated)), raw

    # -- the check -----------------------------------------------------------

    def _check_fn(self, project, fn, donating_attrs, summaries
                  ) -> Iterator[Finding]:
        events = list(
            self._donating_calls(project, fn, donating_attrs, summaries)
        )
        if not events:
            return
        cfg = dataflow_lib.build_cfg(fn.node)
        # innermost enclosing CFG statement of each call node
        owner: Dict[int, int] = {}
        for n in cfg.nodes:
            for expr in dataflow_lib._own_expressions(n.stmt):
                for sub in ast.walk(expr):
                    owner.setdefault(id(sub), n.index)
        reported: Set[tuple] = set()
        for call, positions, desc in events:
            stmt_idx = owner.get(id(call))
            if stmt_idx is None:
                continue  # call sits in a nested def: out of this CFG
            for pos in positions:
                if pos >= len(call.args):
                    continue
                arg = call.args[pos]
                if not isinstance(arg, ast.Name):
                    continue
                name = arg.id
                if dataflow_lib.bailout_reason(fn.node, name):
                    continue  # dynamic scope games: refuse to guess
                for read in dataflow_lib.reads_after(
                    cfg, stmt_idx, name
                ):
                    key = (name, read.lineno, read.col_offset)
                    if key in reported:
                        continue
                    reported.add(key)
                    yield self.finding(
                        fn.ctx, read,
                        f"`{name}` is read here but its buffer was "
                        f"donated to `{desc}` at line {call.lineno} "
                        f"(donate_argnums position {pos}) — the donated "
                        f"buffer is deleted or reused in place by the "
                        f"next dispatch",
                        self._HINT,
                    )


# --------------------------------------------------------------------------
# DML013 transitive-chaos-nondeterminism
# --------------------------------------------------------------------------


class TransitiveChaosRule(ProjectRule):
    name = "transitive-chaos-nondeterminism"
    rule_id = "DML013"
    severity = "error"
    description = (
        "The interprocedural closure of DML003: a fault-injection "
        "decision must be a pure function of (seed, op, key, call-count) "
        "ALL the way down — a FaultPlan decision method that calls a "
        "helper in another module which consults wall time, PIDs, or "
        "`random` is exactly as flaky as doing it inline, and the "
        "per-file rule cannot see across the call.  Sites inside files "
        "DML003 already covers are skipped (one owner per site); this "
        "rule reports the sites the call graph reaches OUTSIDE them, "
        "with the chain that reaches each one."
    )
    _HINT = (
        "derive the decision from the seeded hash of stable keys "
        "(_hash_fraction), or hoist the nondeterministic read out of the "
        "decision path and pass its value in as an argument"
    )

    def check_project(self, project) -> Iterator[Finding]:
        chaos_rule = ChaosDeterminismRule()
        scoped = {
            id(ctx) for ctx in project.contexts if chaos_rule.applies(ctx)
        }
        roots: List[str] = []
        for fn in project.functions.values():
            if id(fn.ctx) in scoped:
                roots.append(fn.qualname)
        for cinfo in project.classes.values():
            bases = {b.rsplit(".", 1)[-1] for b in cinfo.bases}
            if cinfo.name == "FaultPlan" or "FaultPlan" in bases:
                roots.extend(m.qualname for m in cinfo.methods.values())
        reach = project.reachable(roots)
        for qual, path in sorted(reach.items()):
            fn = project.functions[qual]
            if id(fn.ctx) in scoped:
                continue  # DML003 owns sites in chaos-scoped files
            yield from self._check_fn(fn, path)

    def _check_fn(self, fn, path) -> Iterator[Finding]:
        chain = " -> ".join(path)
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            callee = _call_name(node)
            if callee is None:
                continue
            why = _nondet_reason(callee)
            if why is None:
                continue
            yield self.finding(
                fn.ctx, node,
                f"nondeterministic `{callee}()` ({why}) is reachable "
                f"from a fault-decision path: {chain}",
                self._HINT,
            )


# --------------------------------------------------------------------------
# DML014 unguarded-shared-state
# --------------------------------------------------------------------------


_LOCK_CTORS = {"named_lock", "NamedLock"}
_RAW_LOCK_CTORS = {"Lock", "RLock", "Semaphore", "BoundedSemaphore"}
_EXEMPT_METHODS = {"__init__", "__post_init__", "__del__", "__new__"}


class _Access:
    __slots__ = ("attr", "method", "node", "held", "write", "nested")

    def __init__(self, attr, method, node, held, write, nested):
        self.attr = attr
        self.method = method
        self.node = node
        self.held = held
        self.write = write
        self.nested = nested


class UnguardedSharedStateRule(ProjectRule):
    name = "unguarded-shared-state"
    rule_id = "DML014"
    severity = "error"
    description = (
        "A static Eraser-style lockset check seeded from the named_lock "
        "role instrumentation: an instance attribute WRITTEN inside a "
        "`with self._lock:` block in one method is shared mutable state "
        "by declaration — reading or writing it in another method while "
        "holding none of its writer locks is the data race the lock was "
        "bought to prevent.  Private helpers whose every intra-class "
        "call site holds the lock inherit it (the `_drain_locked` "
        "idiom, resolved through the call graph); `__init__` — and any "
        "method that CREATES the guarding lock itself (a second-phase "
        "constructor like a connection handshake) — is exempt: "
        "construction happens-before publication."
    )
    _HINT = (
        "take the guarding lock around the access (or, if the access is "
        "deliberately lock-free — an atomic flag read, a snapshot of an "
        "immutable value — say so: "
        "# dmlint: disable=unguarded-shared-state <reason>)"
    )

    def check_project(self, project) -> Iterator[Finding]:
        for cinfo in sorted(
            project.classes.values(), key=lambda c: c.qualname
        ):
            yield from self._check_class(cinfo)

    # -- lock attr discovery -------------------------------------------------

    def _lock_attrs(self, cinfo) -> Dict[str, str]:
        """attr -> role ('' when unnamed).  Conditions wrapping a lock
        attr alias to it; bare Conditions are locks of their own."""
        locks: Dict[str, str] = {}
        alias: Dict[str, str] = {}
        created_in: Dict[str, Set[str]] = {}
        for m in cinfo.methods.values():
            for node in ast.walk(m.node):
                if not isinstance(node, ast.Assign):
                    continue
                if not isinstance(node.value, ast.Call):
                    continue
                callee = (_call_name(node.value) or "").rsplit(".", 1)[-1]
                attr_targets = [
                    t.attr for t in node.targets
                    if isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                ]
                if not attr_targets:
                    continue
                if callee in _LOCK_CTORS:
                    role = ""
                    if node.value.args and isinstance(
                        node.value.args[0], ast.Constant
                    ):
                        role = str(node.value.args[0].value)
                    for a in attr_targets:
                        locks[a] = role
                        created_in.setdefault(m.name, set()).add(a)
                elif callee in _RAW_LOCK_CTORS:
                    for a in attr_targets:
                        locks[a] = ""
                        created_in.setdefault(m.name, set()).add(a)
                elif callee == "Condition":
                    arg = node.value.args[0] if node.value.args else None
                    if (
                        isinstance(arg, ast.Attribute)
                        and isinstance(arg.value, ast.Name)
                        and arg.value.id == "self"
                    ):
                        for a in attr_targets:
                            alias[a] = arg.attr
                    else:
                        role = ""
                        if isinstance(arg, ast.Call):
                            inner = (
                                _call_name(arg) or ""
                            ).rsplit(".", 1)[-1]
                            if inner in _LOCK_CTORS and arg.args and \
                                    isinstance(arg.args[0], ast.Constant):
                                role = str(arg.args[0].value)
                        for a in attr_targets:
                            locks[a] = role
        for cond, lock in alias.items():
            locks[cond] = locks.get(lock, "")
            alias[cond] = lock if lock in locks else cond
        self._alias = alias
        self._created_in = created_in
        return locks

    # -- per-method walk -----------------------------------------------------

    def _check_class(self, cinfo) -> Iterator[Finding]:
        locks = self._lock_attrs(cinfo)
        if not locks:
            return
        alias = self._alias
        method_names = set(cinfo.methods)
        accesses: List[_Access] = []
        # (callee method, effective held, caller method, nested) sites
        self_calls: List[tuple] = []

        def canon(attr: str) -> str:
            return alias.get(attr, attr)

        def lock_of_with(item: ast.withitem) -> Optional[str]:
            expr = item.context_expr
            if isinstance(expr, ast.Call):  # self._cond.acquire() etc: no
                return None
            if (
                isinstance(expr, ast.Attribute)
                and isinstance(expr.value, ast.Name)
                and expr.value.id == "self"
                and expr.attr in locks
            ):
                return canon(expr.attr)
            return None

        def scan_expr(expr, method, held, nested):
            # container mutation counts as a write to the attr: the
            # object behind self.X is what the lock protects, and
            # `self.X[k] = v` under the lock is the guard declaration
            # just as much as `self.X = ...`
            sub_writes: Set[int] = set()
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Subscript) and isinstance(
                    sub.ctx, (ast.Store, ast.Del)
                ):
                    tgt = sub.value
                    if (
                        isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"
                    ):
                        sub_writes.add(id(tgt))
            for sub in ast.walk(expr):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)):
                    continue  # handled by walk_stmts for defs
                if not isinstance(sub, ast.Attribute):
                    continue
                if not (
                    isinstance(sub.value, ast.Name)
                    and sub.value.id == "self"
                ):
                    continue
                if sub.attr in locks or sub.attr in alias:
                    continue
                write = isinstance(sub.ctx, (ast.Store, ast.Del)) \
                    or id(sub) in sub_writes
                accesses.append(_Access(
                    sub.attr, method, sub, held, write, nested
                ))
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Call) and isinstance(
                    sub.func, ast.Attribute
                ):
                    f = sub.func
                    if (
                        isinstance(f.value, ast.Name)
                        and f.value.id == "self"
                        and f.attr in method_names
                    ):
                        self_calls.append((f.attr, held, method, nested))

        def walk_stmts(stmts, method, held, nested):
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    # a nested def runs LATER (callback/thread target):
                    # whatever lock is held now is not held then
                    walk_stmts(stmt.body, method, frozenset(), True)
                    continue
                if isinstance(stmt, (ast.With, ast.AsyncWith)):
                    inner = set(held)
                    for item in stmt.items:
                        scan_expr(item.context_expr, method, held, nested)
                        if item.optional_vars is not None:
                            scan_expr(item.optional_vars, method, held,
                                      nested)
                        got = lock_of_with(item)
                        if got:
                            inner.add(got)
                    walk_stmts(stmt.body, method, frozenset(inner),
                               nested)
                    continue
                # headers of other compounds evaluate at current held
                for expr in dataflow_lib._own_expressions(stmt):
                    scan_expr(expr, method, held, nested)
                for field_name, value in ast.iter_fields(stmt):
                    if isinstance(value, list) and value:
                        if isinstance(value[0], ast.stmt):
                            walk_stmts(value, method, held, nested)
                        elif isinstance(value[0], ast.excepthandler):
                            for h in value:
                                walk_stmts(h.body, method, held, nested)

        for name, m in cinfo.methods.items():
            walk_stmts(m.node.body, name, frozenset(), False)

        # a method can't access state it doesn't touch; accessing a
        # missing method via self_calls is fine (sites list only).
        sites_of: Dict[str, List[tuple]] = {}
        for callee, held, caller, nested in self_calls:
            sites_of.setdefault(callee, []).append(
                (held, caller, nested)
            )

        # lock inheritance fixpoint: a PRIVATE method whose every
        # intra-class call site holds lock set S inherits S.
        inherited: Dict[str, frozenset] = {
            name: frozenset() for name in method_names
        }
        for _ in range(len(method_names) + 1):
            changed = False
            for name in method_names:
                if not name.startswith("_") or name.startswith("__"):
                    continue
                sites = sites_of.get(name)
                if not sites:
                    continue
                common: Optional[Set[str]] = None
                for held, caller, nested in sites:
                    eff = set(held)
                    if not nested:
                        eff |= inherited.get(caller, frozenset())
                    common = eff if common is None else (common & eff)
                new = frozenset(common or ())
                if new != inherited[name]:
                    inherited[name] = new
                    changed = True
            if not changed:
                break

        # guard sets: locks held at locked WRITES, per attr
        guards: Dict[str, Set[str]] = {}
        for acc in accesses:
            eff = set(acc.held)
            if not acc.nested:
                eff |= inherited.get(acc.method, frozenset())
            if acc.write and eff:
                guards.setdefault(acc.attr, set()).update(eff)

        reported: Set[tuple] = set()
        for acc in accesses:
            guard = guards.get(acc.attr)
            if not guard:
                continue
            if acc.method in _EXEMPT_METHODS:
                continue
            if guard & self._created_in.get(acc.method, set()):
                # this method CREATES the guarding lock: it is that
                # lock's construction phase (handshake/open idiom) —
                # nothing else can hold a lock that does not exist yet
                continue
            eff = set(acc.held)
            if not acc.nested:
                eff |= inherited.get(acc.method, frozenset())
            if eff & guard:
                continue
            key = (acc.attr, acc.node.lineno)
            if key in reported:
                continue
            reported.add(key)
            roles = sorted(
                r for r in (locks.get(g, "") for g in guard) if r
            ) or sorted(guard)
            verb = "written" if acc.write else "read"
            yield self.finding(
                cinfo.ctx, acc.node,
                f"`self.{acc.attr}` is guarded by "
                f"{', '.join(repr(r) for r in roles)} elsewhere in "
                f"`{cinfo.name}` but {verb} here in `{acc.method}` "
                f"without holding it — a concurrent locked writer can "
                f"interleave with this access",
                self._HINT,
            )


# --------------------------------------------------------------------------
# DML017 lifetime-quantile
# --------------------------------------------------------------------------

# Calls that compute a percentile/quantile over their first data argument.
_QUANTILE_CALLS = {
    "percentile", "quantile", "quantiles",
    "nanpercentile", "nanquantile",
}

# Methods that BOUND a list in place (ring/window semantics).
_BOUNDING_METHODS = {"popleft", "clear"}


class LifetimeQuantileRule(Rule):
    name = "lifetime-quantile"
    rule_id = "DML017"
    severity = "error"
    description = (
        "a percentile/quantile computed over an UNBOUNDED accumulated "
        "list in a telemetry module: the PR 8 postmortem as a rule — "
        "serve latency quantiles originally accumulated every request's "
        "latency for the process lifetime, so a long soak both leaked "
        "memory without limit and reported a p99 frozen by hours-old "
        "traffic (the autoscaler keys scale-up off that value).  A "
        "lifetime quantile is wrong twice: unbounded growth AND a stale "
        "signal.  Only LIFETIME accumulators are flagged (self "
        "attributes and module-level lists); a function-local list dies "
        "with its call and is fine.  Enforced in obs-instrumented "
        "modules (OBS_INSTRUMENTED_PATTERNS / `# dmlint-scope: "
        "obs-metrics`)."
    )
    _HINT = (
        "window it: collections.deque(maxlen=N) (or an explicit ring) "
        "and compute the quantile over the window — serve/metrics.py's "
        "bounded latency ring is the house idiom"
    )

    def applies(self, ctx) -> bool:
        if "obs-metrics" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in OBS_INSTRUMENTED_PATTERNS)

    # -- accumulator discovery -----------------------------------------------

    @staticmethod
    def _is_list_literal(node: ast.AST) -> bool:
        return isinstance(node, ast.List) or (
            isinstance(node, ast.Call)
            and (_call_name(node) or "") == "list"
            and not node.args
        )

    @staticmethod
    def _self_attr(node: ast.AST) -> Optional[str]:
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
        ):
            return node.attr
        return None

    def _scan_scope(self, nodes) -> Dict[str, Dict[str, bool]]:
        """Per-accumulator evidence over one scope's nodes: ``{name:
        {"list_init", "grows", "bounded"}}``.  ``name`` is ``.attr`` for
        self attributes, the bare identifier for module globals."""
        acc: Dict[str, Dict[str, bool]] = {}

        def rec(name: str) -> Dict[str, bool]:
            return acc.setdefault(
                name, {"list_init": False, "grows": False,
                       "bounded": False}
            )

        for node in nodes:
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    name = self._target_name(tgt)
                    if name is None:
                        continue
                    if self._is_list_literal(node.value):
                        rec(name)["list_init"] = True
                    elif isinstance(tgt, (ast.Attribute, ast.Name)):
                        # Any other reassignment (a slice-trim
                        # ``x = x[-n:]``, a deque, a fresh snapshot)
                        # bounds or replaces the accumulator.
                        rec(name)["bounded"] = True
            elif isinstance(node, ast.AugAssign):
                name = self._target_name(node.target)
                if name is not None:
                    rec(name)["grows"] = True
            elif isinstance(node, ast.Delete):
                for tgt in node.targets:
                    base = (
                        tgt.value if isinstance(tgt, ast.Subscript) else tgt
                    )
                    name = self._target_name(base)
                    if name is not None:
                        rec(name)["bounded"] = True
            elif isinstance(node, ast.Call):
                if not isinstance(node.func, ast.Attribute):
                    continue
                name = self._target_name(node.func.value)
                if name is None:
                    continue
                meth = node.func.attr
                if meth in ("append", "extend", "insert"):
                    rec(name)["grows"] = True
                elif meth in _BOUNDING_METHODS or (
                    meth == "pop" and node.args
                ):
                    # ``pop(0)`` / ``popleft`` / ``clear`` = ring or
                    # reset semantics; bare ``pop()`` consumes the end
                    # of a stack, which also bounds it.
                    rec(name)["bounded"] = True
                elif meth == "pop":
                    rec(name)["bounded"] = True
        return acc

    def _target_name(self, node: ast.AST) -> Optional[str]:
        attr = self._self_attr(node)
        if attr is not None:
            return f".{attr}"
        if isinstance(node, ast.Name):
            return node.id
        return None

    # -- quantile-site discovery ---------------------------------------------

    def _quantile_data_name(self, call: ast.Call) -> Optional[str]:
        callee = (_call_name(call) or "").rsplit(".", 1)[-1]
        if callee not in _QUANTILE_CALLS or not call.args:
            return None
        data = call.args[0]
        # Unwrap ``sorted(x)`` / ``list(x)`` — the copy is taken at call
        # time, so the quantile is still over the accumulator's lifetime
        # contents.
        while (
            isinstance(data, ast.Call)
            and (_call_name(data) or "") in ("sorted", "list")
            and data.args
        ):
            data = data.args[0]
        return self._target_name(data)

    def check(self, ctx) -> Iterator[Finding]:
        # Only LIFETIME accumulators: self attributes (class scope) and
        # names LIST-INITIALIZED at module top level (module scope).  A
        # function-local list dies with its call and is never flagged.
        for scope_nodes, label, allowed in self._scopes(ctx.tree):
            acc = self._scan_scope(scope_nodes)
            for node in scope_nodes:
                if not isinstance(node, ast.Call):
                    continue
                name = self._quantile_data_name(node)
                if name is None or not allowed(name):
                    continue
                info = acc.get(name)
                if not info or not info["list_init"] or not info["grows"]:
                    continue
                if info["bounded"]:
                    continue
                display = (
                    f"self{name}" if name.startswith(".") else name
                )
                yield self.finding(
                    ctx, node,
                    f"quantile over `{display}`, a lifetime-accumulated "
                    f"list that only ever grows — unbounded memory AND a "
                    f"quantile dominated by stale traffic"
                    + (f" (in {label})" if label else ""),
                    self._HINT,
                )

    def _scopes(self, tree: ast.AST):
        """(nodes, label, allowed-name predicate) per judgment scope:
        every class (``self.X`` attrs are instance-lifetime) and the
        module body outside classes (module-top-level lists are
        process-lifetime)."""
        class_nodes: Set[int] = set()
        for cls in ast.walk(tree):
            if isinstance(cls, ast.ClassDef):
                nodes = list(ast.walk(cls))
                class_nodes.update(id(n) for n in nodes)
                yield nodes, cls.name, lambda n: n.startswith(".")
        module_lists = {
            tgt.id
            for node in getattr(tree, "body", [])
            if isinstance(node, ast.Assign)
            and self._is_list_literal(node.value)
            for tgt in node.targets
            if isinstance(tgt, ast.Name)
        }
        yield (
            [n for n in ast.walk(tree) if id(n) not in class_nodes],
            "",
            lambda n: n in module_lists,
        )


# --------------------------------------------------------------------------
# DML018 implicit-upcast-in-quantized-path
# --------------------------------------------------------------------------


# Files on the quantized serving path (quant/'s own modules and the engine
# that compiles its programs); `# dmlint-scope: quant-path` opts others in.
QUANT_PATH_PATTERNS = (
    "quant/",
    "serve/engine.py",
)

_F32_DTYPE_NAMES = {
    "float32",
    "jnp.float32",
    "np.float32",
    "numpy.float32",
    "jax.numpy.float32",
}

# jnp/lax namespaces whose dtype= kwarg runs on device; plain np is
# host-side bookkeeping and exempt.
_JAX_NS_HEADS = {"jnp", "jax", "lax"}


class ImplicitUpcastInQuantizedPathRule(Rule):
    name = "implicit-upcast-in-quantized-path"
    rule_id = "DML018"
    severity = "error"
    description = (
        "an explicit float32 promotion (astype/asarray/convert_element_"
        "type) on the quantized serving path OUTSIDE the designated "
        "dequant helpers: the int8/bf16 program's whole point is that "
        "weights and activations stay narrow until the one sanctioned "
        "f32 cast on the way out (quant.dequantize_output) — a stray "
        "upcast mid-graph silently re-inflates the memory traffic the "
        "quantization paid for, and XLA will happily keep the rest of "
        "the graph in f32 from that op on.  Enforced in quant/ and "
        "serve/engine.py (QUANT_PATH_PATTERNS / `# dmlint-scope: "
        "quant-path`); functions named `dequant*` are the exemption."
    )
    _HINT = (
        "move the cast into a dequant*-named helper (quant/core.py's "
        "dequantize_* family) if it is genuinely the dequantization "
        "boundary — otherwise keep the op in the compute dtype "
        "(bf16) and let dequantize_output do the one f32 cast"
    )

    def applies(self, ctx) -> bool:
        if "quant-path" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in QUANT_PATH_PATTERNS)

    @staticmethod
    def _is_f32(node: Optional[ast.AST]) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Constant):
            return node.value == "float32"
        return _dotted(node) in _F32_DTYPE_NAMES

    @staticmethod
    def _kwarg(node: ast.Call, *names: str) -> Optional[ast.AST]:
        for kw in node.keywords:
            if kw.arg in names:
                return kw.value
        return None

    def check(self, ctx) -> Iterator[Finding]:
        exempt: Set[int] = set()
        for fn in ast.walk(ctx.tree):
            if isinstance(
                fn, (ast.FunctionDef, ast.AsyncFunctionDef)
            ) and fn.name.lstrip("_").startswith("dequant"):
                exempt.update(id(n) for n in ast.walk(fn))
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or id(node) in exempt:
                continue
            # .astype(float32): receiver-agnostic — in scoped files every
            # tensor on this path is meant to be narrow.
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "astype"
            ):
                dty = node.args[0] if node.args else self._kwarg(
                    node, "dtype"
                )
                if self._is_f32(dty):
                    yield self.finding(
                        ctx, node,
                        "float32 astype on the quantized path outside a "
                        "dequant helper",
                        self._HINT,
                    )
                continue
            callee = _call_name(node) or ""
            head = callee.split(".", 1)[0]
            tail = callee.rsplit(".", 1)[-1]
            if tail in ("asarray", "array", "full_like", "zeros_like",
                        "ones_like") and head in _JAX_NS_HEADS:
                if self._is_f32(self._kwarg(node, "dtype")):
                    yield self.finding(
                        ctx, node,
                        f"{callee}(dtype=float32) materializes f32 on the "
                        f"quantized path outside a dequant helper",
                        self._HINT,
                    )
            elif tail == "convert_element_type":
                dty = (
                    node.args[1] if len(node.args) > 1
                    else self._kwarg(node, "new_dtype", "dtype")
                )
                if self._is_f32(dty):
                    yield self.finding(
                        ctx, node,
                        "lax.convert_element_type(..., float32) on the "
                        "quantized path outside a dequant helper",
                        self._HINT,
                    )
            elif callee in ("jnp.float32", "jax.numpy.float32") \
                    and node.args:
                yield self.finding(
                    ctx, node,
                    "jnp.float32(...) promotion on the quantized path "
                    "outside a dequant helper",
                    self._HINT,
                )


# --------------------------------------------------------------------------
# DML019 unguarded-promotion
# --------------------------------------------------------------------------


# Modules that orchestrate live-model promotion (the self-healing loop and
# the runnable examples); `# dmlint-scope: promotion-guard` opts others in.
PROMOTION_PATH_PATTERNS = (
    "loop/",
    "examples/",
)

# A promotion call is sanctioned only inside a function whose NAME says it
# owns the guard: the probation watcher, a rollback path, or an explicit
# guard helper.  serve/swap.py itself is out of scope (it IS the
# mechanism); this rule is about orchestration code reaching past the
# guard.
_GUARD_FN_RE = re.compile(r"(probation|guard|rollback)")

_PROMOTION_CALLS = {"hot_swap", "warm_swap_bundle"}


class UnguardedPromotionRule(Rule):
    name = "unguarded-promotion"
    rule_id = "DML019"
    severity = "error"
    description = (
        "a live-bundle promotion (hot_swap / warm_swap_bundle) issued "
        "from loop-orchestration or example code OUTSIDE a probation/"
        "guard/rollback context: the self-healing loop's whole contract "
        "is that a candidate reaches traffic only through the guarded "
        "path — gate first, probation watch after, retained prior ready "
        "to roll back to.  A bare hot_swap from a controller or example "
        "promotes an unvetted model with nothing watching it and (if "
        "history is bypassed) nothing to roll back to.  Enforced in "
        "loop/ and examples/ (PROMOTION_PATH_PATTERNS / `# dmlint-scope: "
        "promotion-guard`); functions named *probation*/*guard*/"
        "*rollback* are the sanctioned promotion sites."
    )
    _HINT = (
        "route the swap through SelfHealingController."
        "promote_with_probation (gate + probation + auto-rollback), or "
        "move the call into a *probation*/*guard*/*rollback*-named "
        "function that owns the watch window"
    )

    def applies(self, ctx) -> bool:
        if "promotion-guard" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in PROMOTION_PATH_PATTERNS)

    def check(self, ctx) -> Iterator[Finding]:
        guarded: Set[int] = set()
        for fn in ast.walk(ctx.tree):
            if isinstance(
                fn, (ast.FunctionDef, ast.AsyncFunctionDef)
            ) and _GUARD_FN_RE.search(fn.name):
                guarded.update(id(n) for n in ast.walk(fn))
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or id(node) in guarded:
                continue
            callee = _call_name(node) or ""
            tail = callee.rsplit(".", 1)[-1]
            if tail in _PROMOTION_CALLS:
                yield self.finding(
                    ctx, node,
                    f"{tail}() outside a probation/guard/rollback "
                    f"context promotes an unwatched bundle",
                    self._HINT,
                )


# --------------------------------------------------------------------------
# DML020 non-atomic-state-write
# --------------------------------------------------------------------------


# Control-plane state writers: the tune driver/journal/store, the
# self-healing loop's state docs, and checkpoint manifests.  Other modules
# opt in with `# dmlint-scope: state-write`.
STATE_WRITE_PATH_PATTERNS = (
    "tune/",
    "loop/",
    "ckpt/",
)

# json.dump needs a text handle, so only text write modes can feed it.
_TEXT_WRITE_MODES = {"w", "wt", "tw", "w+", "w+t"}

# Callee tails that mark a scope as using the write-temp-then-rename
# discipline (or a helper that wraps it).
_ATOMIC_TAILS = {"rename", "renames", "mkstemp", "NamedTemporaryFile"}


def _open_write_mode(node: ast.Call) -> bool:
    """True when *node* is an ``open(path, "w")``-style call."""
    callee = _call_name(node) or ""
    if callee.rsplit(".", 1)[-1] != "open":
        return False
    mode = None
    if len(node.args) >= 2:
        mode = node.args[1]
    for kw in node.keywords:
        if kw.arg == "mode":
            mode = kw.value
    return (
        isinstance(mode, ast.Constant)
        and isinstance(mode.value, str)
        and mode.value in _TEXT_WRITE_MODES
    )


def _is_atomic_rename(node: ast.Call) -> bool:
    callee = _call_name(node) or ""
    tail = callee.rsplit(".", 1)[-1]
    if callee in ("os.replace", "os.rename"):
        return True
    if tail in _ATOMIC_TAILS or "atomic" in tail.lower():
        return True
    # Path.replace(target) takes one argument; str.replace(old, new)
    # takes two — arity separates the rename from the string method.
    if tail == "replace" and len(node.args) + len(node.keywords) == 1:
        return True
    return False


class NonAtomicStateWriteRule(Rule):
    name = "non-atomic-state-write"
    rule_id = "DML020"
    severity = "error"
    description = (
        "control-plane state written with a bare `open(path, 'w')` + "
        "`json.dump`: a crash (or chaos SIGKILL) between truncate and "
        "flush leaves a torn/empty JSON file, and resume/restore then "
        "fails on the very state it needs.  Every durable state snapshot "
        "on the tune/loop/ckpt paths must write to a temp name in the "
        "same directory and `os.replace` it over the target — readers "
        "then see either the old state or the new one, never a torn "
        "write.  Append-only journals (`open(..., 'a')` + line-framed "
        "records) are exempt: torn trailing lines are dropped on replay."
    )
    _HINT = (
        "write to `path + '.tmp'` then `os.replace(tmp, path)` (see "
        "tune/storage.py / ExperimentStore.write_state), or suppress "
        "with '# dmlint: disable=non-atomic-state-write <reason>' when "
        "the file is genuinely advisory"
    )

    def applies(self, ctx) -> bool:
        if "state-write" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in STATE_WRITE_PATH_PATTERNS)

    def check(self, ctx) -> Iterator[Finding]:
        parents: Dict[int, ast.AST] = {}
        for parent in ast.walk(ctx.tree):
            for child in ast.iter_child_nodes(parent):
                parents[id(child)] = parent

        def _enclosing_fns(node: ast.AST) -> List[ast.AST]:
            chain = []
            cur = parents.get(id(node))
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    chain.append(cur)
                cur = parents.get(id(cur))
            return chain

        # A scope is "atomic" if anywhere in it a rename/temp-file call
        # appears — the json.dump then targets the temp name, not the
        # live state file.
        atomic_scopes: Set[int] = set()
        scopes: List[ast.AST] = [ctx.tree] + [
            n for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for scope in scopes:
            for node in ast.walk(scope):
                if isinstance(node, ast.Call) and _is_atomic_rename(node):
                    atomic_scopes.add(id(scope))
                    break

        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _call_name(node) or ""
            if callee not in ("json.dump", "ujson.dump"):
                continue
            chain = _enclosing_fns(node)
            if any(id(fn) in atomic_scopes for fn in chain):
                continue
            if not chain and id(ctx.tree) in atomic_scopes:
                continue
            # Require an open-for-write in the innermost scope so dumps
            # to sockets/stdout or append streams stay out of scope.
            innermost: ast.AST = chain[0] if chain else ctx.tree
            if not any(
                isinstance(n, ast.Call) and _open_write_mode(n)
                for n in ast.walk(innermost)
            ):
                continue
            yield self.finding(
                ctx, node,
                "json.dump onto an open(..., 'w') handle with no "
                "os.replace in scope — a crash mid-write tears the "
                "state file",
                self._HINT,
            )


# --------------------------------------------------------------------------
# DML021 local-device-serving-path
# --------------------------------------------------------------------------

# Device-enumeration callee tails that size the PROCESS-LOCAL world.  A
# serving module that consults any of these computes a different mesh,
# bucket grid, or program key on every member of a gang that spans
# processes — the exact divergence the gang serving path exists to
# prevent (every member must trace the identical program or the
# collective wedges).
_LOCAL_SIZING_TAILS = {
    "local_device_count", "device_count", "local_devices",
}


class LocalDeviceServingPathRule(Rule):
    name = "local-device-serving-path"
    rule_id = "DML021"
    severity = "error"
    description = (
        "serve-request-path code sizing meshes or buckets from process-"
        "local device enumeration: jax.local_device_count()/"
        "jax.device_count()/jax.local_devices(), len(jax.devices()), or "
        "jax.devices() fed into a mesh/array constructor.  On one process "
        "every such count agrees; the moment a serving gang spans two, "
        "each member derives a DIFFERENT topology, traces a different "
        "program, and the first collective wedges the whole gang.  "
        "Serving topology is decided once at bootstrap "
        "(multihost.runtime.serving_mesh) and handed down; request-path "
        "code must only consume the mesh it was given.  A bare "
        "`jax.devices()[0]` default-device fallback is fine — it picks a "
        "device, it does not size anything."
    )
    _HINT = (
        "take the mesh from the caller (runtime.serving_mesh() at "
        "bootstrap) and size from mesh.devices / "
        "parallel.partition.mesh_axis_sizes(mesh), or from the bundle "
        "manifest's recorded topology — never from per-process device "
        "enumeration on the request path"
    )

    def applies(self, ctx) -> bool:
        if "serve-request-path" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        return any(pat in rel for pat in SERVE_REQUEST_PATH_PATTERNS)

    @staticmethod
    def _is_devices_call(node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Call)
            and (_call_name(node) or "").rsplit(".", 1)[-1] == "devices"
        )

    def check(self, ctx) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _call_name(node) or ""
            tail = callee.rsplit(".", 1)[-1]
            if tail in _LOCAL_SIZING_TAILS:
                yield self.finding(
                    ctx, node,
                    f"`{callee}()` on the serve request path — a per-"
                    f"process count that diverges across gang members",
                    self._HINT,
                )
                continue
            # jax.devices() used as an argument of another call is a
            # sizing use (len(jax.devices()), Mesh(np.array(jax.devices()),
            # ...)); a subscripted jax.devices()[0] fallback is not.
            for arg in list(node.args) + [
                kw.value for kw in node.keywords
            ]:
                if self._is_devices_call(arg):
                    yield self.finding(
                        ctx, arg,
                        "jax.devices() fed into a constructor on the "
                        "serve request path — request-path code must "
                        "consume the mesh it was handed, not enumerate "
                        "devices itself",
                        self._HINT,
                    )


# --------------------------------------------------------------------------
# DML022 raw-hashed-write-outside-store
# --------------------------------------------------------------------------

# Modules whose artifact bytes belong in the content store (``store/``):
# checkpoint chunk writers, compile-artifact shipping, dataset caches, and
# the export bundler.  Other modules opt in with `# dmlint-scope: cas-path`.
CAS_PATH_PATTERNS = (
    "ckpt/",
    "compilecache/",
    "data/",
)

# Names whose presence in a scope marks it as going through the store
# layer (so its sha256 is the STORE's addressing, not a parallel scheme).
_STORE_LAYER_NAMES = {
    "put_blob", "get_blob", "get_store", "ContentStore", "put_manifest",
    "read_manifest", "ref_copy_subtree", "set_ref", "read_ref",
    "local_blob_path", "has_blob",
}

# Binary write modes: a sha256-named payload landing via one of these
# bypasses the store's first-publish-wins/fsync/GC-pin contract.
_BINARY_WRITE_MODES = {"wb", "bw", "wb+", "w+b", "bw+", "xb", "bx"}


def _open_binary_write(node: ast.Call) -> bool:
    callee = _call_name(node) or ""
    if callee.rsplit(".", 1)[-1] != "open":
        return False
    mode = None
    if len(node.args) >= 2:
        mode = node.args[1]
    for kw in node.keywords:
        if kw.arg == "mode":
            mode = kw.value
    return (
        isinstance(mode, ast.Constant)
        and isinstance(mode.value, str)
        and mode.value in _BINARY_WRITE_MODES
    )


class RawHashedWriteOutsideStoreRule(Rule):
    name = "raw-hashed-write-outside-store"
    rule_id = "DML022"
    severity = "error"
    description = (
        "a CAS-path module hashing bytes with sha256 and writing them to "
        "a file itself — a hand-rolled parallel content-addressing scheme "
        "next to the one the repo already has (``store/``).  Bytes "
        "published this way are invisible to dedup accounting, unpinned "
        "against the GC-vs-writer race, not fsync'd under the first-"
        "publish-wins contract, and the reachability GC can neither "
        "retain nor reclaim them.  Checkpoint chunks, compile artifacts, "
        "dataset-cache products, and export payloads all publish through "
        "``store.ContentStore.put_blob`` + a manifest + a ref."
    )
    _HINT = (
        "publish through the store layer: `store.get_store(root)` then "
        "`put_blob(data)` (pin digests while the ref is pending), "
        "`put_manifest({..., 'store_chunks': [...]})`, `set_ref(...)` — "
        "or suppress with '# dmlint: disable=raw-hashed-write-outside-"
        "store <reason>' when the sha256 is a checksum over an object "
        "the store intentionally does not own"
    )

    def applies(self, ctx) -> bool:
        if "cas-path" in ctx.scopes:
            return True
        rel = ctx.display_path.replace("\\", "/")
        if rel.endswith("serve/export.py"):
            return True
        return any(pat in rel for pat in CAS_PATH_PATTERNS)

    def check(self, ctx) -> Iterator[Finding]:
        parents: Dict[int, ast.AST] = {}
        for parent in ast.walk(ctx.tree):
            for child in ast.iter_child_nodes(parent):
                parents[id(child)] = parent

        def _innermost_scope(node: ast.AST) -> ast.AST:
            cur = parents.get(id(node))
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    return cur
                cur = parents.get(id(cur))
            return ctx.tree

        hashed: Set[int] = set()       # scopes that sha256 something
        store_layer: Set[int] = set()  # scopes that touch the store API
        writes: List[ast.AST] = []     # raw binary-write call sites
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Name) and node.id in _STORE_LAYER_NAMES:
                store_layer.add(id(_innermost_scope(node)))
            elif (
                isinstance(node, ast.Attribute)
                and node.attr in _STORE_LAYER_NAMES
            ):
                store_layer.add(id(_innermost_scope(node)))
            if not isinstance(node, ast.Call):
                continue
            callee = _call_name(node) or ""
            tail = callee.rsplit(".", 1)[-1]
            if tail == "sha256":
                hashed.add(id(_innermost_scope(node)))
            elif tail == "write_bytes" or _open_binary_write(node):
                writes.append(node)

        for node in writes:
            scope = _innermost_scope(node)
            if id(scope) not in hashed or id(scope) in store_layer:
                continue
            yield self.finding(
                ctx, node,
                "sha256-addressed bytes written with a raw file write — "
                "a parallel content-addressing scheme the store's dedup, "
                "pins, and reachability GC cannot see",
                self._HINT,
            )


ALL_RULES: List[Rule] = [
    DonationAliasRule(),
    ChaosDeterminismRule(),
    WallclockDeadlineRule(),
    PickleCheckpointRule(),
    ImportTraceRule(),
    ThreadSwallowRule(),
    UndonatedHotJitRule(),
    UnboundedQueueRule(),
    HostSyncInScanRule(),
    BlockingTransferInLoopRule(),
    BareCounterIncrementRule(),
    LocalGlobalDeviceConfusionRule(),
    LifetimeQuantileRule(),
    UseAfterDonationRule(),
    TransitiveChaosRule(),
    UnguardedSharedStateRule(),
    ImplicitUpcastInQuantizedPathRule(),
    UnguardedPromotionRule(),
    NonAtomicStateWriteRule(),
    LocalDeviceServingPathRule(),
    RawHashedWriteOutsideStoreRule(),
]


def get_rule(name: str) -> Rule:
    for rule in ALL_RULES:
        if rule.name == name or rule.rule_id == name:
            return rule
    raise KeyError(f"no dmlint rule named {name!r}")
