"""Canonical program keys: config -> shape-class fingerprint -> stable id.

An XLA program is determined by everything that shapes the traced
computation: model family and architecture knobs, batch/sequence shapes,
dtypes, optimizer FAMILY (the chain's structure), and the donation
signature.  It is NOT determined by the hyperparameters that ride in state
— ``learning_rate`` and ``weight_decay`` live in the injected optimizer
hyperparams (``ops/optimizers.py``) and ``seed`` enters as a traced PRNG
key argument — so two trials differing only in those trace to IDENTICAL
HLO.  The key must say so: that identity is what lets the second trial, the
second worker, and the restarted replica skip compilation entirely.

The fingerprint must also be **stable across processes and hosts** (the
cluster origin exchanges artifacts by key; the bench compares keys across
child processes), so it is a sha256 over a canonical JSON rendering, never
``hash()`` (salted per process) or ``repr`` of dicts (order-dependent
pre-3.7 idioms).
"""

from __future__ import annotations

import hashlib
import json
from typing import Any, Dict, Optional, Sequence, Tuple

# Hyperparameters that never shape the traced program: they are carried in
# optimizer state / PRNG arguments (the vectorized runner's VECTOR_KEYS is
# this same set — tune/vectorized.py asserts they agree).
NON_STRUCTURAL_KEYS = frozenset({"learning_rate", "weight_decay", "seed"})

# Driver-level knobs that select HOW a program is built/cached but never
# appear in the traced computation itself.
_DRIVER_KEYS = frozenset({"checkpoint_freq"})


def _canonical(value: Any) -> Any:
    """JSON-stable rendering: tuples -> lists, sets sorted, floats via repr
    (json floats are already deterministic in CPython, but -0.0 vs 0.0 and
    int-valued floats must not alias ints)."""
    if isinstance(value, (list, tuple)):
        return [_canonical(v) for v in value]
    if isinstance(value, (set, frozenset)):
        return sorted(_canonical(v) for v in value)
    if isinstance(value, dict):
        return {str(k): _canonical(v) for k, v in sorted(value.items())}
    if isinstance(value, float):
        return f"f:{value!r}"
    if isinstance(value, bool):
        return f"b:{value}"
    return value


def shape_class_fingerprint(config: Dict[str, Any]) -> Tuple:
    """The structural slice of a trial config, as a sorted item tuple.

    Everything except :data:`NON_STRUCTURAL_KEYS` and pure driver knobs is
    structural — d_model, heads, layers, batch_size, optimizer family,
    schedule family, interval/steps counts, dtypes all change the traced
    program.  EXCEPTION: with ``inject_hyperparams=False`` the optimizer
    bakes lr/wd into the HLO as constants, so they become structural again
    (the key must split what the compiler splits)."""
    injected = bool(config.get("inject_hyperparams", True))
    skip = set(_DRIVER_KEYS)
    skip.update(
        k for k in NON_STRUCTURAL_KEYS
        if injected or k == "seed"  # seed is a traced argument either way
    )
    items = []
    for k in sorted(config):
        if k in skip:
            continue
        items.append((k, _canonical(config[k])))
    return tuple(items)


def program_key(
    config: Dict[str, Any],
    *,
    batch_shape: Optional[Sequence[Sequence[int]]] = None,
    dtype: Optional[str] = None,
    donation: Sequence[int] = (),
    extra: Optional[Dict[str, Any]] = None,
) -> str:
    """Stable id for one (shape class, batch shape, dtype, donation) program.

    ``batch_shape``: the data shapes the program closes over / is called
    with (e.g. staged train/val split shapes, or a serve bucket's padded
    input shape).  ``donation``: the ``donate_argnums`` signature — a
    donated and an undonated build of the same computation are different
    executables.  ``extra``: any additional identity the caller knows
    (population row count, scan trip count, mesh topology).
    """
    payload = {
        "v": 1,  # key-format version: bump if the canonicalization changes
        "fingerprint": _canonical(list(shape_class_fingerprint(config))),
        "batch_shape": _canonical(
            [list(s) for s in batch_shape] if batch_shape else []
        ),
        "dtype": dtype or "",
        "donation": sorted(int(d) for d in donation),
        "extra": _canonical(extra or {}),
    }
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return "pk_" + hashlib.sha256(blob.encode()).hexdigest()[:32]


def pbt_program_key(
    config: Dict[str, Any],
    *,
    interval: int,
    generations: int,
    rows: int,
    objective: Any = None,
    mutation_spec: Any = None,
    batch_shape: Optional[Sequence[Sequence[int]]] = None,
    dtype: Optional[str] = None,
    extra: Optional[Dict[str, Any]] = None,
) -> str:
    """:func:`program_key` for the compiled PBT generation scan.

    The generation scan is keyed by everything that shapes ITS trace on
    top of the base shape class: the **perturbation interval** (inner
    epoch-scan trip count), the **generation count** (outer scan trip
    count), the **population row count**, the **objective** scalarization,
    and the **mutation spec** constants (domain bounds, factors, resample
    probability, quantile — all baked into the exploit/explore step).
    The PBT ``seed`` must NOT split the key: it enters as per-row PRNG key
    arguments, exactly like trial seeds in the base key — and
    ``learning_rate``/``weight_decay`` stay non-structural (injected
    optimizer state the scan mutates in-device).
    """
    spec = dict(mutation_spec or {})
    merged = {
        "pbt_scan": {
            "interval": int(interval),
            "generations": int(generations),
            "rows": int(rows),
            "objective": _canonical(objective or "quality"),
            "mutations": _canonical(spec),
        }
    }
    if extra:
        merged.update(extra)
    return program_key(
        config,
        batch_shape=batch_shape,
        dtype=dtype,
        donation=(0, 1, 2),
        extra=merged,
    )


def chunked_program_key(
    config: Dict[str, Any],
    *,
    chunk_rows: int,
    batch_shape: Optional[Sequence[Sequence[int]]] = None,
    dtype: Optional[str] = None,
    donation: Sequence[int] = (),
    extra: Optional[Dict[str, Any]] = None,
) -> str:
    """:func:`program_key` for one streaming CHUNK program
    (``data/pipeline.py``: the out-of-core prefetch ring).

    The chunk's **row count** (batches per staged slab — the chunk scan's
    trip count, baked into the trace) folds into the key on top of the
    base shape class; the **number of chunks per epoch does NOT** — the
    host loops over chunks, so a 10-chunk and a 1000-chunk epoch of the
    same slab shape run the identical executable.  An epoch whose batch
    count does not divide the chunk size gets exactly one extra key (the
    tail chunk's smaller row count).  Dataset length and epoch batch
    count therefore never split streaming keys — only the slab geometry
    does.
    """
    merged = {"stream_chunk_rows": int(chunk_rows)}
    if extra:
        merged.update(extra)
    return program_key(
        config,
        batch_shape=batch_shape,
        dtype=dtype,
        donation=donation,
        extra=merged,
    )


def gang_program_key(
    config: Dict[str, Any],
    *,
    process_count: int,
    local_device_counts: Sequence[int],
    batch_shape: Optional[Sequence[Sequence[int]]] = None,
    dtype: Optional[str] = None,
    donation: Sequence[int] = (),
    extra: Optional[Dict[str, Any]] = None,
) -> str:
    """:func:`program_key` for a program lowered over a PROCESS-SPANNING
    mesh (``multihost/`` gang trials).

    The **process topology** — process count × per-process local device
    layout — folds into the key because the compiler splits on it: the
    same mesh shape decomposed differently across processes lowers
    different cross-process collectives (2 processes × 2 devices and
    4 × 1 are different programs).  Reshaping the gang therefore splits
    the key; a SECOND gang of the same topology computes the identical
    key, which is what lets it fetch the first gang's artifacts from the
    cluster origin and compile nothing.  Canonical (counts only — no
    device ids, hostnames, or ports), so the key is stable across hosts.
    """
    merged = {
        "process_topology": {
            "process_count": int(process_count),
            "local_device_counts": [int(c) for c in local_device_counts],
        }
    }
    if extra:
        merged.update(extra)
    return program_key(
        config,
        batch_shape=batch_shape,
        dtype=dtype,
        donation=donation,
        extra=merged,
    )


def sharded_program_key(
    config: Dict[str, Any],
    *,
    mesh_shape: Dict[str, int],
    rules_fingerprint: str,
    batch_shape: Optional[Sequence[Sequence[int]]] = None,
    dtype: Optional[str] = None,
    donation: Sequence[int] = (),
    extra: Optional[Dict[str, Any]] = None,
) -> str:
    """:func:`program_key` for a program compiled under a named mesh.

    Two additional identities fold into the key because the compiler
    splits on both: the **mesh shape** (``{"dp": 2, "tp": 4}`` and
    ``{"dp": 4, "tp": 2}`` lower to different collectives even over the
    same 8 devices) and the **partition-rule fingerprint**
    (``parallel.partition.rules_fingerprint`` — a rule-table edit changes
    every layout the traced program bakes in).  With these in the key,
    sharded programs AOT-cache and cross-worker-dedup exactly like
    unsharded ones: same mesh shape + same rule table on another worker
    ⇒ artifact fetch, anything else ⇒ honest recompile.
    """
    merged = {
        "mesh_shape": {str(k): int(v) for k, v in (mesh_shape or {}).items()},
        "rules_fp": str(rules_fingerprint),
    }
    if extra:
        merged.update(extra)
    return program_key(
        config,
        batch_shape=batch_shape,
        dtype=dtype,
        donation=donation,
        extra=merged,
    )
