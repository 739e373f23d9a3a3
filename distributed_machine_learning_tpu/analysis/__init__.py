"""Project-native static analysis (dmlint) + runtime lock-order checking.

Two halves, one goal — every hard bug this codebase has shipped was an
invariant violation that could have been caught mechanically (ISSUE 6):

* ``dmlint`` (:mod:`engine`, :mod:`rules`, :mod:`findings`): an AST rules
  engine encoding the repo's JAX/concurrency invariants — donation
  aliasing, chaos determinism, wall-clock deadlines, pickle-free
  checkpoints, import-time tracing, swallowed thread
  exceptions.  Since v2 (ISSUE 11) the engine is whole-project: every
  file parses once into a shared context, and cross-file rules reason
  over a symbol table + call graph (:mod:`callgraph`) and an
  intraprocedural CFG/reaching-definitions pass (:mod:`dataflow`) —
  use-after-donation, the transitive closure of chaos determinism, and
  a static Eraser-style lockset check seeded from the ``named_lock``
  roles.  Run it with ``dml-tpu lint`` (exits non-zero on any
  unsuppressed finding; ``--changed`` for pre-commit, ``--format=sarif``
  for CI annotators) or via :func:`lint_paths`.
* lock-order recording (:mod:`locks`): ``named_lock()``-created locks
  record per-thread acquisition edges; a cycle in the role graph is a
  deadlock precondition detectable from single-threaded tests.

This package imports NO jax (and must stay that way): the linter runs in
environments where initializing a backend is wrong or impossible, and
``locks`` is imported by low-level modules everywhere.  The ONE scoped
exception is the program-level tier (:mod:`jaxlint`, dmlint v3 /
ISSUE 12): it audits jaxprs and lowered modules, so *running* it needs
jax — but every jax import in it is function-local, it is loaded lazily
(:func:`run_jax_checks` below), and even then it only ever calls
``eval_shape`` / ``make_jaxpr`` / ``lower()`` — nothing allocated,
nothing compiled (enforced by a tier-1 inertness test).  Run it with
``dml-tpu lint --jax`` or ``dml-tpu audit-sharding``.

Catalog, severities, and the suppression/baseline workflow:
docs/static-analysis.md.
"""

from distributed_machine_learning_tpu.analysis.engine import (  # noqa: F401
    DEFAULT_BASELINE,
    LintResult,
    clear_context_cache,
    iter_python_files,
    lint_paths,
    parse_count,
    render,
    render_sarif,
)
from distributed_machine_learning_tpu.analysis.findings import (  # noqa: F401
    Finding,
    save_baseline,
)
from distributed_machine_learning_tpu.analysis.locks import (  # noqa: F401
    LockOrderRecorder,
    LockOrderViolation,
    NamedLock,
    get_recorder,
    named_lock,
)
from distributed_machine_learning_tpu.analysis.rules import (  # noqa: F401
    ALL_RULES,
    CHECKPOINT_PATH_PATTERNS,
    get_rule,
)


def run_jax_checks(*args, **kwargs):
    """Lazy surface over :func:`jaxlint.run_jax_checks` — importing this
    package must never pull jax; only running the jax tier does."""
    from distributed_machine_learning_tpu.analysis.jaxlint import (
        run_jax_checks as _run,
    )

    return _run(*args, **kwargs)


def jax_check_catalog():
    """The jax-tier check list (JaxCheck instances), lazily imported."""
    from distributed_machine_learning_tpu.analysis.jaxlint import JAX_CHECKS

    return list(JAX_CHECKS)


def get_jax_check(name: str):
    from distributed_machine_learning_tpu.analysis.jaxlint import (
        get_jax_check as _get,
    )

    return _get(name)
