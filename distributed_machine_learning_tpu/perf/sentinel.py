"""Cross-round bench regression sentinel: honest comparisons only.

``BENCH_r*.json`` / ``MULTICHIP_r*.json`` round artifacts lie by
juxtaposition when rounds ran on different backends: a chip capture
followed by CPU captures reads as a collapse when nothing
chip-comparable was measured.  The sentinel parses the round artifacts, buckets them into **comparability classes** (backend +
compute dtype + metric), and only issues regression/improvement
verdicts WITHIN a class and outside a noise band:

* Rounds on the repo's **reference backend** (the backend of the most
  recent non-CPU capture — the chip era) form the comparable chains the
  CI gate judges.
* Rounds on a *different* backend than the reference are flagged
  ``cpu_fallback`` / non-comparable: they get an informational
  same-backend delta against the previous same-class round, never a
  regression verdict against the chip chain.
* Unparseable rounds (wedged captures, ``parsed: null``) are listed,
  not guessed at.

``dml-tpu perf compare --artifacts BENCH_r*.json`` renders the report
and exits nonzero exactly when an in-class regression beyond the noise
band exists.

Stdlib-only; runs on hosts with no jax at all.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Optional

ROUND_RE = re.compile(r"(BENCH|MULTICHIP)_r(\d+)\.json$")

DEFAULT_NOISE_BAND = 0.15


def load_round(path: str) -> Optional[Dict[str, Any]]:
    """One artifact file -> a round record, or None for non-round paths.

    Bench rounds: ``{"kind": "bench", "round": n, "parsed": {...}|None}``.
    Multichip rounds carry health only (``ok``/``rc``/``n_devices``)."""
    m = ROUND_RE.search(os.path.basename(path))
    if not m:
        return None
    kind = m.group(1).lower()
    rec: Dict[str, Any] = {
        "path": path,
        "kind": kind,
        "round": int(m.group(2)),
    }
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as exc:
        rec["error"] = str(exc)
        return rec
    if kind == "bench":
        parsed = data.get("parsed")
        rec["parsed"] = parsed if isinstance(parsed, dict) else None
    else:
        rec.update({
            "ok": bool(data.get("ok")),
            "rc": data.get("rc"),
            "n_devices": data.get("n_devices"),
            "skipped": bool(data.get("skipped")),
        })
    return rec


def load_rounds(paths: List[str]) -> List[Dict[str, Any]]:
    out = []
    for p in paths:
        rec = load_round(p)
        if rec is not None:
            out.append(rec)
    out.sort(key=lambda r: (r["kind"], r["round"]))
    return out


def comparability_class(parsed: Dict[str, Any]) -> str:
    """``<backend>+<compute_dtype>`` for one parsed bench line.  Rounds
    predating the ``compute_dtype`` field report ``?`` — the chain
    matcher treats ``?`` as compatible with any dtype on the same
    backend (r02's chip capture must anchor the chip chain, not be
    orphaned by a missing field)."""
    backend = str(parsed.get("backend") or "?")
    dtype = str(parsed.get("compute_dtype") or "?")
    return f"{backend}+{dtype}"


def _same_class(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    if (a.get("backend") or "?") != (b.get("backend") or "?"):
        return False
    da = str(a.get("compute_dtype") or "?")
    db = str(b.get("compute_dtype") or "?")
    return "?" in (da, db) or da == db


def reference_backend(rounds: List[Dict[str, Any]]) -> Optional[str]:
    """The backend perf claims are judged on: the most recent parseable
    non-CPU capture's backend.  None when nothing establishes a reference
    (all-CPU repo: CPU is then judged as the reference by the caller)."""
    ref = None
    for rec in rounds:
        parsed = rec.get("parsed")
        if not parsed:
            continue
        if (parsed.get("backend") or "cpu") != "cpu":
            ref = parsed["backend"]
    return ref


def evaluate_rounds(
    rounds: List[Dict[str, Any]],
    noise_band: float = DEFAULT_NOISE_BAND,
) -> Dict[str, Any]:
    """The sentinel verdict over a set of round records."""
    bench = [r for r in rounds if r["kind"] == "bench"]
    multichip = [r for r in rounds if r["kind"] == "multichip"]
    ref = reference_backend(bench)

    annotated: List[Dict[str, Any]] = []
    unparsed: List[int] = []
    for rec in bench:
        parsed = rec.get("parsed")
        if not parsed or parsed.get("value") is None:
            unparsed.append(rec["round"])
            continue
        backend = str(parsed.get("backend") or "?")
        fallback = ref is not None and backend != ref
        annotated.append({
            "round": rec["round"],
            "value": float(parsed["value"]),
            "unit": parsed.get("unit"),
            "metric": parsed.get("metric"),
            "backend": backend,
            "compute_dtype": parsed.get("compute_dtype"),
            "class": comparability_class(parsed),
            "cpu_fallback": fallback,
            "comparability": (
                f"{backend}-fallback vs {ref} (non-comparable)"
                if fallback else f"comparable ({backend} era)"
            ),
            "parsed": parsed,
        })

    # Reference chain: successive reference-backend rounds, same class.
    chain = [a for a in annotated if not a["cpu_fallback"]]
    verdicts: List[Dict[str, Any]] = []
    regressions: List[Dict[str, Any]] = []
    for prev, cur in zip(chain, chain[1:]):
        if not _same_class(prev["parsed"], cur["parsed"]):
            verdicts.append({
                "from_round": prev["round"], "to_round": cur["round"],
                "verdict": "non-comparable",
                "reason": f"{prev['class']} -> {cur['class']}",
            })
            continue
        ratio = cur["value"] / prev["value"] if prev["value"] else None
        if ratio is None:
            verdict = "non-comparable"
        elif ratio < 1.0 - noise_band:
            verdict = "regression"
        elif ratio > 1.0 + noise_band:
            verdict = "improvement"
        else:
            verdict = "flat"
        v = {
            "from_round": prev["round"], "to_round": cur["round"],
            "class": cur["class"],
            "ratio": round(ratio, 4) if ratio is not None else None,
            "noise_band": noise_band,
            "verdict": verdict,
        }
        verdicts.append(v)
        if verdict == "regression":
            regressions.append(v)

    # Fallback rounds: informational same-backend deltas only — never a
    # verdict against the reference chain (the r02->r03 "0.8x" trap).
    fallback_rounds: List[Dict[str, Any]] = []
    prev_fb: Optional[Dict[str, Any]] = None
    for a in annotated:
        if not a["cpu_fallback"]:
            continue
        entry = {
            "round": a["round"],
            "value": a["value"],
            "backend": a["backend"],
            "class": a["class"],
            "comparability": a["comparability"],
        }
        if prev_fb is not None and _same_class(
            prev_fb["parsed"], a["parsed"]
        ) and prev_fb["value"]:
            entry["vs_prev_same_backend"] = round(
                a["value"] / prev_fb["value"], 4
            )
        fallback_rounds.append(entry)
        prev_fb = a

    # Maximal runs of mutually comparable reference-backend rounds.
    chains: List[Dict[str, Any]] = []
    run: List[Dict[str, Any]] = []
    for a in chain:
        if run and not _same_class(run[-1]["parsed"], a["parsed"]):
            chains.append(run)
            run = []
        run.append(a)
    if run:
        chains.append(run)
    chains = [
        {
            "class": c[0]["class"],
            "backend": c[0]["backend"],
            "rounds": [a["round"] for a in c],
            "values": [a["value"] for a in c],
        }
        for c in chains
    ]
    return {
        "reference_backend": ref,
        "noise_band": noise_band,
        "comparable_chains": chains,
        "verdicts": verdicts,
        "regressions": regressions,
        "fallback_rounds": fallback_rounds,
        "unparsed_rounds": unparsed,
        "multichip": [
            {k: r.get(k) for k in ("round", "ok", "rc", "skipped")}
            for r in multichip
        ],
        "ok": not regressions,
    }


def render_report(report: Dict[str, Any]) -> str:
    """Human-readable sentinel verdict."""
    lines = [
        f"perf sentinel: reference backend = "
        f"{report['reference_backend'] or 'none established'}, "
        f"noise band = +/-{report['noise_band'] * 100:.0f}%"
    ]
    for c in report["comparable_chains"]:
        pts = ", ".join(
            f"r{r:02d}={v:g}" for r, v in zip(c["rounds"], c["values"])
        )
        lines.append(f"  chain [{c['class']}]: {pts}")
    if not report["comparable_chains"]:
        lines.append("  no comparable chain (no reference-backend rounds)")
    for v in report["verdicts"]:
        ratio = f" {v['ratio']:.2f}x" if v.get("ratio") is not None else ""
        lines.append(
            f"  r{v['from_round']:02d} -> r{v['to_round']:02d}:"
            f"{ratio} {v['verdict'].upper()}"
        )
    for fb in report["fallback_rounds"]:
        same = fb.get("vs_prev_same_backend")
        extra = f", {same:.2f}x vs prev same-backend" if same else ""
        lines.append(
            f"  r{fb['round']:02d}: {fb['comparability']}"
            f" (value {fb['value']:g}{extra})"
        )
    if report["unparsed_rounds"]:
        lines.append(
            "  unparsed rounds: "
            + ", ".join(f"r{r:02d}" for r in report["unparsed_rounds"])
        )
    if report["multichip"]:
        health = ", ".join(
            "r{:02d}={}".format(
                m["round"], "ok" if m["ok"] else f"rc={m['rc']}"
            )
            for m in report["multichip"]
        )
        lines.append(f"  multichip health: {health}")
    lines.append(
        "  verdict: "
        + ("OK — no in-class regression" if report["ok"] else
           f"{len(report['regressions'])} in-class regression(s) beyond "
           f"the noise band")
    )
    return "\n".join(lines)
