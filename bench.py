"""Benchmark: HPO trial throughput of the TPU-native framework.

Workload (mirrors BASELINE.json's quality/throughput framing): a fixed-shape
transformer regression trial (glucose-like windowed series, batch 32) run as
an HPO sweep over lr/weight-decay.  Fixed architecture keeps every trial on
one XLA executable, so the sweep amortizes a single compile — the
compile-cache story that makes HPO viable on TPU (SURVEY.md §7 hard parts).

Baseline: the same trial implemented in torch (the reference's stack is
torch + Ray on CUDA; this image has torch-CPU), timed per-step and
extrapolated to a full trial.  ``vs_baseline`` = our trials/hour divided by
torch's trials/hour on this host.

Process architecture: a chip belongs to one process at a time, so the
parent (``python bench.py``) never imports jax.  It runs the suite child
ONCE and relays the child's one JSON line.  The child names the device it
ran on (``platform``, ``device_kind``, device count) and exits non-zero
when the platform is not ``tpu`` — there is no CPU arm: a measurement
path that finds no chip fails.  The other ``child_*`` sections are
self-contained measurement bodies (``python bench.py --child <kind>``)
kept for the benchmark harness to mine; their CPU tests call them
directly.
"""

from __future__ import annotations

import functools
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

# Full (TPU) workload — the reference's production run: 50 trials x 20
# epochs, batch 32 (`ray-tune-hpo-regression.py:472,322,456`).
# warm_repeats: the FIFO sweep re-runs N times warm (same process, compile
# cached) and the headline is the MEDIAN warm wall with recorded spread —
# a single draw hid 12-71s variance in round 3 (VERDICT r3 weak #5, which
# asks for >=5 repeated cells per measured configuration).
FULL = dict(num_trials=50, num_epochs=20, data_steps=100_000, warm_repeats=5)
# Scaled CPU-fallback workload (1-core host; keep it minute-scale). Warm
# repeats so the headline excludes one-time compile (the r3 CPU fallback
# "lost" to torch 0.39x mostly on jit compile baked into a single cold
# wall) AND is a median with spread — the cross-call program cache makes
# each repeat cost only the execute wall (~18s here).
SMALL = dict(num_trials=8, num_epochs=3, data_steps=30_000, warm_repeats=5)

# MXU-bound flagship measurement (VERDICT r3 next #2): the
# end-to-end shape — d_model 512, seq 2048, bf16, explicit flash attention
# (head_dim 64 = the kernel's measured-win regime).
BENCH_RESULTS_DIR = os.path.join(tempfile.gettempdir(), "bench_results")
# Metric each variant optimizes — used by partial recovery to report the
# best value among trials that DID finish before a child died.
VARIANT_METRICS = {
    "pbt_cnn": "validation_mse",
    "bohb_transformer": "validation_mse",
    "sharded_resnet": "validation_loss",
}

FLAGSHIP = dict(d_model=512, num_heads=8, num_layers=4, dim_feedforward=2048,
                seq=2048, batch=8, features=16)

BATCH = 32
D_MODEL = 64
LAYERS = 2
HEADS = 4
FEATURES = 16
SEQ = 96  # glucose windows are interval=96
DFF = D_MODEL * 2
TORCH_STEPS_MEASURED = 30

def _unlink_quiet(path) -> None:
    try:
        os.unlink(path)
    except OSError:
        pass


def _atomic_json_dump(path: str, obj, **dump_kw) -> None:
    """Write JSON via tmp + rename: a SIGTERM mid-write (bench children run
    under kill timeouts) must never leave a truncated file."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, **dump_kw)
    os.replace(tmp, path)


SUITE_TIMEOUT_S = 3000
HEARTBEAT_STALE_S = 300


def _run_child_monitored(args, env, timeout_s: float, heartbeat_path,
                         stale_s):
    """Run a child; returns (rc, out, err); rc=124 on any kill.

    On timeout — or, when ``heartbeat_path`` is given, as soon as the
    child's progress heartbeat goes stale for ``stale_s`` (a hung device
    call burns minutes, not the whole timeout) — terminate with SIGTERM,
    then SIGKILL: a child that needs the chip must be gone before the
    next one starts.

    stdout/stderr go through temp files (a polling loop can't use
    ``communicate`` without risking pipe-buffer deadlock)."""
    if heartbeat_path:
        _unlink_quiet(heartbeat_path)
    with tempfile.TemporaryFile(mode="w+") as fout, \
            tempfile.TemporaryFile(mode="w+") as ferr:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)] + args,
            env=env, cwd=_REPO_ROOT, stdout=fout, stderr=ferr, text=True,
        )
        start = time.time()
        timed_out = False
        while proc.poll() is None:
            now = time.time()
            beat, have_beat = start, False
            if heartbeat_path:
                try:
                    beat = os.path.getmtime(heartbeat_path)
                    have_beat = True
                except OSError:
                    pass
            # Before the child's FIRST beat exists, allow a longer grace
            # (advisor r4): a legitimately slow backend start or one cold
            # compile must not be killed as stalled at the ordinary
            # between-beats threshold.
            threshold = stale_s if (not stale_s or have_beat) \
                else 2 * stale_s
            if now - start > timeout_s or (
                    stale_s and now - max(start, beat) > threshold):
                timed_out = True
                break
            time.sleep(1.0)

        def read_both():
            fout.seek(0)
            ferr.seek(0)
            return fout.read(), ferr.read()

        if not timed_out:
            out, err = read_both()
            result = (proc.returncode, out, err)
        else:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            out, err = read_both()
            result = (124, out, err)
    # Forensics: the parent normally surfaces only the stderr tail.
    # Opt-in full retention.
    log_dir = os.environ.get("DML_BENCH_CHILD_LOG_DIR")
    if log_dir:
        try:
            os.makedirs(log_dir, exist_ok=True)
            tag = "_".join(a.lstrip("-") for a in args)[:80]
            # pid disambiguates same-second same-args children (a fast
            # rc=1 pair would otherwise truncate each other's evidence).
            stamp = f"{int(time.time())}_{tag}_pid{proc.pid}_rc{result[0]}"
            with open(os.path.join(log_dir, stamp + ".out"), "w") as f:
                f.write(result[1])
            with open(os.path.join(log_dir, stamp + ".err"), "w") as f:
                f.write(result[2])
        except OSError as exc:
            # Best-effort, but never silently: an unwritable dir on an
            # instrumented forensic session must not eat the evidence
            # run without a trace.
            print(f"[bench] child log retention failed: {exc!r}",
                  file=sys.stderr, flush=True)
    return result


def _median(walls):
    ordered = sorted(walls)
    return ordered[len(ordered) // 2]


def _round_opt(v, nd: int = 2):
    return round(v, nd) if isinstance(v, (int, float)) else v


def _parse_result(out: str):
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


# ---------------------------------------------------------------------------
# Analytic FLOPs (for MFU)


def transformer_fwd_flops(batch: int, seq: int) -> float:
    """Analytic forward FLOPs of the bench transformer — delegates to the
    framework's estimator (ops.flops) so there is ONE formula to maintain."""
    from distributed_machine_learning_tpu.ops.flops import forward_flops

    return forward_flops(
        {"model": "transformer", "d_model": D_MODEL, "num_layers": LAYERS,
         "dim_feedforward": DFF},
        batch, seq, FEATURES,
    )


def sweep_total_flops(num_trials: int, num_epochs: int, steps_per_epoch: int,
                      n_val: int) -> float:
    """Train (fwd+bwd ~= 3x fwd) + one eval pass per epoch, per trial."""
    train = 3.0 * transformer_fwd_flops(BATCH, SEQ) * steps_per_epoch
    evalp = transformer_fwd_flops(max(n_val, 1), SEQ)
    return num_trials * num_epochs * (train + evalp)


# ---------------------------------------------------------------------------
# Child: our framework (runs under either env; jax imported lazily)


def _touch_heartbeat() -> None:
    """Progress heartbeat for the monitored parent: every phase-boundary
    note refreshes the file's mtime, so a child whose device call hangs
    (mtime goes stale) is distinguishable from one that is slow but moving
    — the 915s silent-stall burn of 2026-07-31 bounded to minutes.
    Shared protocol with the vectorized runner's dispatch-boundary beats:
    utils/heartbeat.py.

    The import MUST stay lazy: the package ``__init__`` imports jax, and
    the bench parent must never import jax (a parent that has touched jax
    holds the chip its child needs — module docstring).
    Only children call this."""
    from distributed_machine_learning_tpu.utils.heartbeat import (
        touch_heartbeat,
    )

    touch_heartbeat()


def _make_note(t0: float):
    """Phase narration to stderr (the stall forensics channel) + heartbeat."""
    def note(msg: str) -> None:
        _touch_heartbeat()
        print(f"[child {time.time() - t0:6.1f}s] {msg}",
              file=sys.stderr, flush=True)
    return note


def _make_checkpoint(partial_path):
    """Atomic best-effort partial-result writer (parent falls back to this
    file when a child dies rc!=0). Doubles as a heartbeat."""
    def checkpoint_partial(snapshot: dict) -> None:
        _touch_heartbeat()
        if partial_path:
            _atomic_json_dump(partial_path, snapshot)
    return checkpoint_partial


def _bench_space(scale: dict, compute_dtype: str) -> dict:
    """THE bench search space — one builder for the headline sweeps AND
    the quality-at-budget sweeps, so their static signatures (and thus
    traced programs) stay identical by construction: a hand-copied
    variant drifted once (review r5 — a missing compute_dtype key broke
    the program-cache match even at the same resolved dtype).

    Optional dropout-PRNG override (DML_BENCH_RNG_IMPL=threefry|rbg):
    default is "auto" (ops/rng.py) — hardware RNG on TPU, measured ~1.5x
    sweep throughput vs threefry on-chip; the override exists to measure
    the other stream implementation for comparison."""
    from distributed_machine_learning_tpu import tune

    space = {
        "model": "transformer",
        "d_model": D_MODEL,
        "num_heads": HEADS,
        "num_layers": LAYERS,
        "dim_feedforward": DFF,
        "dropout": 0.1,
        "learning_rate": tune.loguniform(1e-4, 1e-2),
        "weight_decay": tune.loguniform(1e-6, 1e-3),
        "seed": tune.randint(0, 1_000_000),
        "num_epochs": scale["num_epochs"],
        "batch_size": BATCH,
        "max_seq_length": 128,
        "loss_function": "mse",
        "compute_dtype": compute_dtype,
    }
    rng_impl = os.environ.get("DML_BENCH_RNG_IMPL")
    if rng_impl:
        space["rng_impl"] = rng_impl
    return space


def child_ours(scale: dict, compute_dtype: str = "float32") -> None:
    t_child0 = time.time()
    note = _make_note(t_child0)
    checkpoint_partial = _make_checkpoint(
        os.environ.get("DML_BENCH_PARTIAL_PATH")
    )

    # Time budget (seconds, from the parent = child timeout minus margin):
    # optional phases (warm repeats, ASHA) are skipped when the projected
    # cost would overrun it, so the child exits cleanly with what it has
    # instead of being SIGTERMed mid-phase.
    budget_s = float(os.environ.get("DML_BENCH_CHILD_BUDGET_S", "0") or 0)

    def remaining_s() -> float:
        return (budget_s - (time.time() - t_child0)) if budget_s else 1e9

    result = _sweep_result(
        scale, compute_dtype, note, checkpoint_partial, remaining_s
    )
    print(json.dumps(result))


def _sweep_result(scale: dict, compute_dtype: str, note, checkpoint_partial,
                  remaining_s) -> dict:
    """The measured HPO sweep (FIFO cold + warm repeats + ASHA) on whatever
    backend this process sees.  Runs inside ``child_ours`` or as one phase
    of the suite child (``child_suite``)."""
    # Runner-internal phase narration (trace/compile/execute boundaries) on
    # stderr, so a stall names the phase it hit.
    os.environ.setdefault("DML_TUNE_PROGRESS", "1")

    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.data import glucose_like_data

    note(f"generating data (steps={scale['data_steps']})")
    train, val = glucose_like_data(
        num_steps=scale["data_steps"], num_features=FEATURES
    )
    note(f"data ready: train {train.x.shape}, val {val.x.shape}")
    space = _bench_space(scale, compute_dtype)

    def sweep(tag, scheduler=None, epochs_per_dispatch=1):
        note(f"sweep '{tag}' start (epochs_per_dispatch={epochs_per_dispatch})")
        t0 = time.time()
        analysis = tune.run_vectorized(
            space,
            train_data=train,
            val_data=val,
            metric="validation_mape",
            mode="min",
            num_samples=scale["num_trials"],
            max_batch_trials=scale["num_trials"],
            scheduler=scheduler,
            storage_path=BENCH_RESULTS_DIR,
            name=f"bench_{tag}_{int(t0)}",
            seed=42,
            verbose=0,
            epochs_per_dispatch=epochs_per_dispatch,
        )
        wall = time.time() - t0
        note(f"sweep '{tag}' done in {wall:.1f}s")
        with open(os.path.join(analysis.root, "experiment_state.json")) as f:
            state = json.load(f)
        return analysis, wall, state

    # FIFO dispatches the whole per-trial budget as ONE scanned program:
    # measured on the chip (2026-07-30), one 20-epoch program beats
    # quarter-sweep chunks cold (33.6s vs 42.2s total — one compile instead
    # of chunk+remainder programs) and matches them warm (an earlier
    # round's figures, not measured on today's code).
    # DML_BENCH_EPD overrides the dispatch size (smaller programs, partial
    # progress) without editing the file.
    epd = int(os.environ.get("DML_BENCH_EPD") or scale["num_epochs"])
    analysis, wall, fifo_state = sweep("fifo", epochs_per_dispatch=epd)
    done = analysis.num_terminated()
    steps_per_epoch = len(train.x) // BATCH
    flops = sweep_total_flops(
        done, scale["num_epochs"], steps_per_epoch, len(val.x)
    )
    import jax

    from distributed_machine_learning_tpu.ops.flops import device_peak_flops

    partial = {
        "trials_per_hour": done * 3600.0 / wall,
        "wall_s": wall, "cold_wall_s": wall,
        "trials_per_hour_cold": done * 3600.0 / wall,
        "compile_s": fifo_state.get("compile_time_total_s"),
        "device_utilization": fifo_state.get("device_utilization"),
        "done": done, "flops": flops, "compute_dtype": compute_dtype,
        "best_mape": float(analysis.best_result.get("validation_mape", -1)),
        # platform/peak travel WITH the partial: a recovered bf16 result
        # must not have its MFU computed against the f32 fallback peak.
        "platform": jax.devices()[0].platform,
        "peak_flops": device_peak_flops(
            jax.devices()[0], compute_dtype=compute_dtype
        ),
        "partial": True,
    }
    if epd != scale["num_epochs"]:
        # Non-default dispatch sizing must be visible on EVERY snapshot a
        # chunked child leaves behind, not only the final result.
        partial["epochs_per_dispatch"] = epd
    checkpoint_partial(partial)
    # Warm repeats: same sweep re-run in this process (compile cache hot).
    # Headline = median warm wall; cold wall + spread recorded alongside.
    cold_state = fifo_state
    warm_walls = []
    for i in range(int(scale.get("warm_repeats", 0))):
        if remaining_s() < 1.5 * wall:
            note(f"skipping warm repeats {i}.. (remaining {remaining_s():.0f}s"
                 f" < 1.5x cold wall {wall:.0f}s)")
            partial["warm_skipped_after"] = i
            break
        _, w_wall, fifo_state = sweep(
            f"fifo_warm{i}", epochs_per_dispatch=epd
        )
        warm_walls.append(w_wall)
        med = _median(warm_walls)
        partial.update({
            "wall_s": med, "trials_per_hour": done * 3600.0 / med,
            "warm_walls_s": [round(w, 2) for w in warm_walls],
            "device_utilization": fifo_state.get("device_utilization"),
        })
        checkpoint_partial(partial)
    headline_wall = _median(warm_walls) if warm_walls else wall
    # Compile-artifact accounting (compilecache counter family) of the COLD
    # sweep — the run that actually paid compiles; plus the headline split
    # into per-trial compile vs execute seconds, so "startup cost" and
    # "steady-state cost" stop hiding inside one wall number.
    comp = cold_state.get("compile") or {}
    done_safe = max(done, 1)
    compile_cache_block = {
        "hits": int(comp.get("program_hits", 0)
                    + comp.get("persistent_cache_hits", 0)),
        "misses": int(comp.get("program_misses", 0)),
        "aot_exports": int(comp.get("aot_exports", 0)),
        "fetch_fallbacks": int(comp.get("fetch_fallbacks", 0)),
        "uncached_backend_compiles": int(
            comp.get("backend_compiles_uncached", 0)
        ),
    }
    result = {
        "trials_per_hour": done * 3600.0 / headline_wall,
        "wall_s": headline_wall,
        "cold_wall_s": wall,
        "trials_per_hour_cold": done * 3600.0 / wall,
        "warm_walls_s": [round(w, 2) for w in warm_walls],
        "wall_spread_s": (
            [round(min(warm_walls), 2), round(max(warm_walls), 2)]
            if warm_walls else None
        ),
        "compile_s": cold_state.get("compile_time_total_s"),
        # Per-trial breakout of the COLD sweep: what one trial pays in
        # compile vs execute (short ASHA rungs are all startup).
        "compile_s_per_trial": round(
            (cold_state.get("compile_time_total_s") or 0.0) / done_safe, 4
        ),
        "exec_s_per_trial": round(
            (cold_state.get("device_exec_s") or 0.0) / done_safe, 4
        ),
        "compile_cache": compile_cache_block,
        # Duty cycle of the headline (warm when repeats ran) sweep: measured
        # device-execute seconds over wall (vectorized.py) — the honest
        # utilization figure BASELINE.md's >=90% target is judged against.
        "device_utilization": fifo_state.get("device_utilization"),
        "device_exec_s": fifo_state.get("device_exec_s"),
        "done": done,
        "flops": flops,
        "best_mape": float(analysis.best_result.get("validation_mape", -1)),
        # Identity fields live on result from construction so every later
        # checkpoint_partial carries them (MFU denominator honesty).
        "platform": partial["platform"],
        "compute_dtype": compute_dtype,
        "peak_flops": partial["peak_flops"],
    }
    if "warm_skipped_after" in partial:
        result["warm_skipped_after"] = partial["warm_skipped_after"]
    if epd != scale["num_epochs"]:
        result["epochs_per_dispatch"] = epd

    checkpoint_partial(dict(result, partial=True))

    # Same budget under ASHA: early stopping + population compaction should
    # finish the sweep in less wall-clock (fewer total epochs executed).
    try:
        if remaining_s() < 1.5 * wall:
            raise RuntimeError(
                f"skipped: deadline (remaining {remaining_s():.0f}s "
                f"< 1.5x cold wall {wall:.0f}s)"
            )
        grace = max(1, scale["num_epochs"] // 4)
        asha = tune.ASHAScheduler(
            max_t=scale["num_epochs"],
            grace_period=grace,
            reduction_factor=2,
        )
        # "auto": the cost model picks rung-sized chunks (stops save
        # compute) or one speculative whole-budget dispatch (reuses the
        # warm FIFO program; stops land post-hoc at the same rungs) from
        # the FIFO phase's measured dispatch history — at latency-bound
        # bench shapes chunking measured 0.88x FIFO, so speculation
        # should win here (vectorized._resolve_auto_dispatch).
        asha_analysis, asha_wall, asha_state = sweep(
            "asha", asha, epochs_per_dispatch="auto"
        )
        result.update({
            "asha_wall_s": asha_wall,
            "asha_compile_s": asha_state.get("compile_time_total_s"),
            "asha_trials_per_hour":
                asha_analysis.num_terminated() * 3600.0 / asha_wall,
            "asha_epochs_run": sum(
                len(t.results) for t in asha_analysis.trials
            ),
            "fifo_epochs_run": sum(len(t.results) for t in analysis.trials),
            "asha_row_epochs": asha_state.get("row_epochs_computed"),
            "fifo_row_epochs": fifo_state.get("row_epochs_computed"),
            "asha_best_mape": float(
                asha_analysis.best_result.get("validation_mape", -1)
            ),
        })
    except Exception:  # noqa: BLE001 - FIFO number still stands
        import traceback

        result["asha_error"] = traceback.format_exc()[-1500:]

    return result


# ---------------------------------------------------------------------------
# Child: torch baseline (per-step timing, extrapolated to a full trial)


def _torch_baseline_model(in_features: int, max_len: int = 512):
    """The reference's TransformerModel, faithfully: input projection,
    sin/cos positional encoding + dropout, N encoder layers, last-token
    pooling, and the fc1..fc5 ReLU regression head
    (`ray-tune-hpo-regression.py:183-240`) — the same work the JAX side
    trains, so vs_baseline compares models, not a lighter proxy.  Shared
    by the per-step baseline (child_torch) and the equal-budget quality
    baseline (child_torch_quality)."""
    import numpy as np
    import torch
    import torch.nn as nn

    class Baseline(nn.Module):
        def __init__(self):
            super().__init__()
            self.proj = nn.Linear(in_features, D_MODEL)
            pos = torch.zeros(max_len, D_MODEL)
            position = torch.arange(max_len, dtype=torch.float32)[:, None]
            div = torch.exp(
                torch.arange(0, D_MODEL, 2, dtype=torch.float32)
                * (-np.log(10000.0) / D_MODEL)
            )
            pos[:, 0::2] = torch.sin(position * div)
            pos[:, 1::2] = torch.cos(position * div)
            self.register_buffer("pe", pos)
            self.pe_dropout = nn.Dropout(0.1)
            enc = nn.TransformerEncoderLayer(
                d_model=D_MODEL, nhead=HEADS, dim_feedforward=DFF,
                dropout=0.1, batch_first=True)
            self.encoder = nn.TransformerEncoder(enc, num_layers=LAYERS)
            # The reference's 5-layer ReLU head (fc1..fc5, `:217-221`).
            self.head = nn.Sequential(
                nn.Linear(D_MODEL, 128), nn.ReLU(),
                nn.Linear(128, 64), nn.ReLU(),
                nn.Linear(64, 32), nn.ReLU(),
                nn.Linear(32, 16), nn.ReLU(),
                nn.Linear(16, 1),
            )

        def forward(self, x):
            h = self.proj(x)
            h = self.pe_dropout(h + self.pe[: h.shape[1]][None])
            h = self.encoder(h)
            return self.head(h[:, -1, :])

    return Baseline()


def child_torch(scale: dict) -> None:
    import numpy as np  # noqa: F401
    import torch
    import torch.nn as nn

    from distributed_machine_learning_tpu.data import glucose_like_data

    torch.manual_seed(0)
    train, val = glucose_like_data(
        num_steps=scale["data_steps"], num_features=FEATURES
    )

    x = torch.from_numpy(train.x)
    y = torch.from_numpy(train.y)
    xv = torch.from_numpy(val.x)
    n = len(x)
    steps_per_epoch = n // BATCH

    model = _torch_baseline_model(train.x.shape[-1])
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    loss_fn = nn.MSELoss()
    perm = torch.randperm(n)

    def one_step(i):
        sel = perm[(i * BATCH) % (n - BATCH): (i * BATCH) % (n - BATCH) + BATCH]
        opt.zero_grad()
        loss = loss_fn(model(x[sel]), y[sel])
        loss.backward()
        opt.step()

    for i in range(3):  # warmup
        one_step(i)
    t0 = time.time()
    for i in range(TORCH_STEPS_MEASURED):
        one_step(i + 3)
    step_s = (time.time() - t0) / TORCH_STEPS_MEASURED
    t0 = time.time()
    with torch.no_grad():
        model.eval()
        _ = model(xv)
    eval_s = time.time() - t0

    per_trial_s = (
        scale["num_epochs"] * (steps_per_epoch * step_s + eval_s)
    )
    print(json.dumps({
        "trials_per_hour": 3600.0 / per_trial_s,
        "per_trial_s": per_trial_s,
        "step_s": step_s,
        "steps_measured": TORCH_STEPS_MEASURED,
        "extrapolated": True,
        # 1-min loadavg on this 1-core host: >~1.5 means another process
        # contended the measurement and the baseline reads slow.
        "loadavg_1m": round(os.getloadavg()[0], 2),
    }))


# ---------------------------------------------------------------------------
# Quality at equal wall-clock budget (BASELINE.md row 4; VERDICT r4 next
# #4): both stacks search the SAME space (lr/wd/seed over the bench
# transformer) on the SAME data for the SAME seconds; the artifact reports
# each side's best validation_mape (the reference's target metric,
# `ray-tune-hpo-regression.py:473`) and how many trials the budget bought.

QUALITY_BUDGET_S = 120.0  # override: DML_BENCH_QUALITY_BUDGET_S (0 = skip)


def _quality_budget_s() -> float:
    raw = os.environ.get("DML_BENCH_QUALITY_BUDGET_S")
    return float(raw) if raw not in (None, "") else QUALITY_BUDGET_S


def _quality_result(scale: dict, budget_s: float, note) -> dict:
    """Our stack's best-val-at-budget: repeated ASHA sweeps until the NEXT
    sweep's projected cost would overrun the budget.  Runs on whatever
    backend this process sees.

    Every sweep uses the HEADLINE sweep's exact program shapes — same
    architecture keys, population size (num_trials), and rung-sized
    dispatch — so inside the suite child the cross-call program cache
    serves the already-traced/compiled programs (zero fresh compiles),
    and across processes the persistent XLA cache does; the
    budget buys trials, not compiles.  Each sweep draws a fresh seed, so
    quality-at-budget is best-of-N independent ASHA sweeps (at whole-
    population chunks the TPE prior is equivalent to random within a
    sweep; the volume advantage vs the torch baseline is the point)."""
    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.data import glucose_like_data

    train, val = glucose_like_data(
        num_steps=scale["data_steps"], num_features=FEATURES
    )
    import jax

    grace = max(1, scale["num_epochs"] // 4)
    pop = scale["num_trials"]
    # Same builder as the headline sweeps: identical static signature =
    # identical traced programs (the cache-reuse invariant).  float32 is
    # the suite's first-run dtype, so quality rides its warm programs.
    space = _bench_space(scale, "float32")
    t0 = time.time()
    best, total_trials, sweeps, last_wall = None, 0, 0, 0.0
    while True:
        elapsed = time.time() - t0
        if elapsed + max(last_wall, 5.0) > budget_s:
            break
        analysis = tune.run_vectorized(
            space, train_data=train, val_data=val,
            metric="validation_mape", mode="min",
            num_samples=pop, max_batch_trials=pop,
            scheduler=tune.ASHAScheduler(
                max_t=scale["num_epochs"], grace_period=grace,
                reduction_factor=2,
            ),
            storage_path=BENCH_RESULTS_DIR,
            name=f"quality_{sweeps}_{int(t0)}",
            seed=1000 + sweeps, verbose=0, epochs_per_dispatch=grace,
        )
        last_wall = (time.time() - t0) - elapsed
        b = float(analysis.best_result.get("validation_mape", float("inf")))
        best = b if best is None else min(best, b)
        total_trials += analysis.num_terminated()
        sweeps += 1
        _touch_heartbeat()
        note(f"quality sweep {sweeps}: best {best:.2f} "
             f"({total_trials} trials, {time.time() - t0:.0f}s)")
    return {
        "budget_s": budget_s,
        "wall_s": round(time.time() - t0, 1),
        "best_validation_mape": best,
        "trials": total_trials,
        "sweeps": sweeps,
        "platform": jax.devices()[0].platform,
    }


def child_quality(scale: dict) -> None:
    t0 = time.time()
    note = _make_note(t0)
    result = _quality_result(scale, _quality_budget_s(), note)
    print(json.dumps(result))


def _pbt_quality_result(scale: dict, budget_s: float, note) -> dict:
    """The in-device PBT arm of quality-at-budget (ISSUE 9).

    Same space, data, budget, and program shapes as the ASHA arm — but the
    whole population trains as ONE generation-scan program: exploit
    ranking, the state gather, and the lr/wd explore are compiled in, so a
    sweep of G generations costs ceil(num_epochs/chunk) host dispatches
    instead of num_epochs/interval.  Repeated sweeps (fresh seeds) until
    the next one would overrun the budget; the artifact carries best MAPE,
    trials, the summed pbt counter block, and the measured host-dispatch
    count — the directly comparable answer to the ASHA arm's
    best-of-N-independent-sweeps number.
    """
    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.data import glucose_like_data

    train, val = glucose_like_data(
        num_steps=scale["data_steps"], num_features=FEATURES
    )
    import jax

    pop = scale["num_trials"]
    epochs = scale["num_epochs"]
    # One generation per ASHA grace period: the same decision cadence the
    # ASHA arm prunes at, so the two arms spend comparable compute per
    # decision.
    interval = max(1, epochs // 4)
    space = _bench_space(scale, "float32")
    t0 = time.time()
    best, total_trials, sweeps, last_wall = None, 0, 0, 0.0
    counters = {"generations": 0, "exploits": 0, "explores": 0,
                "host_dispatches": 0}
    while True:
        elapsed = time.time() - t0
        if elapsed + max(last_wall, 5.0) > budget_s:
            break
        pbt = tune.PopulationBasedTraining(
            perturbation_interval=interval,
            hyperparam_mutations={
                # The space's own lr/wd domains (search_space builders in
                # _bench_space): explore stays inside what ASHA samples.
                "learning_rate": tune.loguniform(1e-4, 1e-2),
                "weight_decay": tune.loguniform(1e-6, 1e-3),
            },
            quantile_fraction=0.25,
            seed=3000 + sweeps,
        )
        analysis = tune.run_vectorized(
            space, train_data=train, val_data=val,
            metric="validation_mape", mode="min",
            num_samples=pop, max_batch_trials=pop,
            scheduler=pbt,
            storage_path=BENCH_RESULTS_DIR,
            name=f"pbt_quality_{sweeps}_{int(t0)}",
            seed=2000 + sweeps, verbose=0,
        )
        last_wall = (time.time() - t0) - elapsed
        with open(os.path.join(analysis.root,
                               "experiment_state.json")) as f:
            state = json.load(f)
        for k in ("generations", "exploits", "explores", "host_dispatches"):
            counters[k] += int((state.get("pbt") or {}).get(k, 0))
        counters["mode"] = (state.get("pbt") or {}).get("mode")
        b = float(analysis.best_result.get("validation_mape", float("inf")))
        best = b if best is None else min(best, b)
        total_trials += analysis.num_terminated()
        sweeps += 1
        _touch_heartbeat()
        note(f"pbt quality sweep {sweeps}: best {best:.2f} "
             f"({total_trials} trials, "
             f"{counters['host_dispatches']} host dispatches, "
             f"{time.time() - t0:.0f}s)")
    return {
        "budget_s": budget_s,
        "wall_s": round(time.time() - t0, 1),
        "best_validation_mape": best,
        "trials": total_trials,
        "sweeps": sweeps,
        "host_dispatches": counters["host_dispatches"],
        "pbt": counters,
        "platform": jax.devices()[0].platform,
    }


def child_pbt_quality(scale: dict) -> None:
    t0 = time.time()
    note = _make_note(t0)
    result = _pbt_quality_result(scale, _quality_budget_s(), note)
    print(json.dumps(result))


def child_torch_quality(scale: dict) -> None:
    """The reference stack's best-val-at-budget: random search with
    synchronous successive halving (brackets of 8, bottom half culled each
    rung — the generous stand-in for Ray's ASHA+BayesOpt on the torch
    side) over the same space/data/epochs, until the budget is spent."""
    import numpy as np
    import torch

    from distributed_machine_learning_tpu.data import glucose_like_data

    budget_s = _quality_budget_s()
    train, val = glucose_like_data(
        num_steps=scale["data_steps"], num_features=FEATURES
    )
    x = torch.from_numpy(train.x)
    y = torch.from_numpy(train.y)
    xv = torch.from_numpy(val.x)
    yv = torch.from_numpy(val.y)
    n = len(x)
    steps_per_epoch = n // BATCH
    max_t = scale["num_epochs"]
    grace = max(1, max_t // 4)
    rng = np.random.RandomState(42)
    loss_fn = torch.nn.MSELoss()

    def val_mape(model) -> float:
        model.eval()
        with torch.no_grad():
            p = model(xv)
        model.train()
        return float(
            (torch.abs(yv - p) / (torch.abs(yv) + 1e-8)).mean() * 100.0
        )

    def train_epochs(model, opt, e: int, deadline: float) -> bool:
        """Run e epochs; False if the deadline cut them short."""
        for _ in range(e):
            perm = torch.randperm(n)
            for i in range(steps_per_epoch):
                sel = perm[i * BATCH:(i + 1) * BATCH]
                opt.zero_grad()
                loss = loss_fn(model(x[sel]), y[sel])
                loss.backward()
                opt.step()
                if time.time() > deadline:
                    return False
        return True

    t0 = time.time()
    deadline = t0 + budget_s
    best, total_trials, brackets = None, 0, 0
    while time.time() < deadline:
        # One synchronous successive-halving bracket: 8 candidates at
        # grace epochs, top half advances with doubled epochs, until max_t.
        cands = []
        for _ in range(8):
            torch.manual_seed(int(rng.randint(0, 1 << 31)))
            model = _torch_baseline_model(train.x.shape[-1])
            lr = float(10 ** rng.uniform(-4, -2))
            wd = float(10 ** rng.uniform(-6, -3))
            opt = torch.optim.Adam(model.parameters(), lr=lr,
                                   weight_decay=wd)
            cands.append([model, opt, None])
        total_trials += len(cands)
        brackets += 1
        epochs_done, rung_e = 0, grace
        cut = False
        while cands and epochs_done < max_t and not cut:
            rung_e = min(rung_e, max_t - epochs_done)
            for c in cands:
                if not train_epochs(c[0], c[1], rung_e, deadline):
                    cut = True
                c[2] = val_mape(c[0])
                b = c[2]
                best = b if best is None else min(best, b)
                if cut:
                    break
            epochs_done += rung_e
            rung_e *= 2
            if len(cands) > 1:
                # A deadline cut can leave later candidates unevaluated
                # (None): sort them last, they're culled first.
                cands.sort(key=lambda c: c[2] if c[2] is not None
                           else float("inf"))
                cands = cands[:max(1, len(cands) // 2)]
    print(json.dumps({
        "budget_s": budget_s,
        "wall_s": round(time.time() - t0, 1),
        "best_validation_mape": best,
        "trials": total_trials,
        "brackets": brackets,
        "loadavg_1m": round(os.getloadavg()[0], 2),
        "sha": {"bracket": 8, "grace": grace, "max_t": max_t,
                "reduction": 2},
    }))


# ---------------------------------------------------------------------------
# Children: BASELINE.json configs 3-5 as measurable variants
# (`python bench.py --variant pbt_cnn|bohb_transformer|sharded_resnet`).
# Not part of the headline run — manual on-chip measurements
# (VERDICT r3 next #7).

VARIANT_SCALES = {
    # BASELINE config 3: "PBT on 1D-CNN tabular regressor, 128 trials".
    "pbt_cnn": {
        "full": dict(trials=128, epochs=12, interval=3, data_steps=60_000),
        "small": dict(trials=8, epochs=6, interval=2, data_steps=20_000),
    },
    # BASELINE config 4: "BOHB on Transformer-tiny (early-stop + XLA
    # compile cache reuse)".
    "bohb_transformer": {
        "full": dict(trials=64, max_t=9, data_steps=40_000),
        "small": dict(trials=8, max_t=4, data_steps=20_000),
    },
    # BASELINE config 5: "ResNet-18 regression head over 4 cores/trial,
    # 32 trials" (devices clamp to what the host has: 1 on a one-chip
    # machine, 4 on a CPU test mesh or a four-chip host).
    "sharded_resnet": {
        "full": dict(trials=32, epochs=4, devices=4),
        "small": dict(trials=2, epochs=2, devices=4),
    },
}


def _stderr_reporter():
    """Live trial table on stderr for variant children: a stalled child's
    captured log then shows exactly how far it got (the 2026-07-31 bohb
    stall was invisible — 2s CPU, zero output, nothing to diagnose).
    Every trial result also refreshes the bench heartbeat, so thread-
    executor variants (bohb, sharded_resnet — whose dispatches don't pass
    through the vectorized runner's beats) register progress with the
    monitored parent."""
    from distributed_machine_learning_tpu import tune

    class _HeartbeatReporter(tune.ProgressReporter):
        def on_trial_result(self, trial, result):
            _touch_heartbeat()
            return super().on_trial_result(trial, result)

    return _HeartbeatReporter(interval_s=30.0, file=sys.stderr)


def child_variant(name: str, scale_name: str) -> None:
    import jax
    import numpy as np

    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.data import (
        Dataset,
        glucose_like_data,
    )

    scale = VARIANT_SCALES[name][scale_name]
    t0 = time.time()
    # Parent-chosen experiment name (partial recovery: the parent scans the
    # experiment dir if this child dies mid-sweep).
    exp_name = os.environ.get("DML_BENCH_EXP_NAME") or None
    extra = {}
    if name == "pbt_cnn":
        train, val = glucose_like_data(
            num_steps=scale["data_steps"], num_features=FEATURES
        )
        space = {
            "model": "cnn1d",
            "channels": (32, 64),
            "kernel_size": 5,
            "learning_rate": tune.loguniform(1e-4, 3e-2),
            "weight_decay": tune.loguniform(1e-6, 1e-3),
            "seed": tune.randint(0, 1_000_000),
            "num_epochs": scale["epochs"],
            "batch_size": BATCH,
            "loss_function": "mse",
            "lr_schedule": "constant",
        }
        pbt = tune.PopulationBasedTraining(
            perturbation_interval=scale["interval"],
            hyperparam_mutations={
                "learning_rate": tune.loguniform(1e-4, 3e-2),
            },
            quantile_fraction=0.25,
            seed=7,
        )
        analysis = tune.run_vectorized(
            space, train_data=train, val_data=val,
            metric="validation_mse", mode="min",
            num_samples=scale["trials"], max_batch_trials=scale["trials"],
            scheduler=pbt, storage_path=BENCH_RESULTS_DIR,
            name=exp_name or f"variant_pbt_{int(t0)}", seed=11, verbose=0,
            callbacks=[_stderr_reporter()],
        )
        extra["best_validation_mse"] = float(
            analysis.best_result.get("validation_mse", -1)
        )
    elif name == "bohb_transformer":
        train, val = glucose_like_data(
            num_steps=scale["data_steps"], num_features=FEATURES
        )
        space = {
            "model": "simple_transformer",
            "d_model": 32,
            "num_heads": 2,
            "num_layers": 2,
            "dim_feedforward": 64,
            "dropout": 0.1,
            "learning_rate": tune.loguniform(1e-4, 1e-2),
            "weight_decay": tune.loguniform(1e-6, 1e-3),
            "seed": tune.randint(0, 1_000_000),
            "num_epochs": scale["max_t"],
            "batch_size": BATCH,
            "loss_function": "mse",
        }
        if jax.devices()[0].platform != "cpu":
            # Serialize the cohort's first backend compile through the
            # persistent cache (VERDICT r4 next #3): the architecture is
            # FIXED here and lr/wd are INJECTED optimizer state
            # (trainable.py), so every cohort trial traces to identical
            # HLO — but N worker threads starting together would still
            # fire concurrent first compiles of that one program.  One
            # sequential 1-epoch standalone trial compiles it; the cohort
            # then starts on cache hits.  total_steps is pinned to the
            # cohort's value (it is baked into the schedule as an HLO
            # constant; num_epochs=1 alone would compile a DIFFERENT
            # program).  Timestamped so a stall during THIS phase reads
            # as compile (vs cohort execution) in the child log; a
            # background beater keeps the parent's heartbeat alive for a
            # bounded window (a slow-but-live compile can
            # legitimately exceed the 300s staleness kill), and any
            # warmup failure falls through to the cohort, which tolerates
            # trial-level errors on its own.
            print(f"[child {time.time() - t0:7.1f}s] compile warmup start",
                  file=sys.stderr, flush=True)
            import threading

            _touch_heartbeat()
            stop_beat = threading.Event()

            def _beat_during_warmup():
                deadline = time.time() + 600  # bounded: a true hang
                while not stop_beat.wait(60):  # still dies at 600+300s
                    if time.time() > deadline:
                        return
                    _touch_heartbeat()

            beater = threading.Thread(target=_beat_during_warmup,
                                      daemon=True)
            beater.start()
            try:
                n_tr = len(train.x)
                bs = min(BATCH, n_tr)
                warm_cfg = dict(
                    {k: v for k, v in space.items()
                     if not hasattr(v, "sample")},
                    learning_rate=1e-3, weight_decay=1e-5, seed=0,
                    num_epochs=1,
                    total_steps=scale["max_t"] * max(n_tr // bs, 1),
                )
                with tune.standalone():
                    tune.train_regressor(
                        warm_cfg, train_data=train, val_data=val
                    )
                print(f"[child {time.time() - t0:7.1f}s] compile warmup "
                      f"done", file=sys.stderr, flush=True)
            except Exception as exc:  # noqa: BLE001 - warmup is optional
                print(f"[child {time.time() - t0:7.1f}s] compile warmup "
                      f"FAILED (cohort continues): {exc!r}",
                      file=sys.stderr, flush=True)
            finally:
                stop_beat.set()
                _touch_heartbeat()
        analysis = tune.run(
            tune.with_parameters(
                tune.train_regressor, train_data=train, val_data=val
            ),
            space,
            metric="validation_mse", mode="min",
            num_samples=scale["trials"],
            scheduler=tune.HyperBandScheduler(
                max_t=scale["max_t"], grace_period=1, reduction_factor=3
            ),
            search_alg=tune.TPESearch(),
            storage_path=BENCH_RESULTS_DIR,
            name=exp_name or f"variant_bohb_{int(t0)}",
            verbose=0,
            callbacks=[_stderr_reporter()],
        )
        # The compile-cache-reuse story: one fixed architecture => later
        # trials hit the jit cache instead of recompiling.
        hits = [t.last_result.get("compile_cache_hits", 0)
                for t in analysis.trials if t.last_result]
        extra["compile_cache_hits_total"] = int(sum(hits))
        extra["best_validation_mse"] = float(
            analysis.best_result.get("validation_mse", -1)
        )
    elif name == "sharded_resnet":
        n_dev = min(scale["devices"], len(jax.devices()))
        rng = np.random.default_rng(0)
        x = rng.normal(size=(1024, 16, 16, 3)).astype(np.float32)
        y = x.mean(axis=(1, 2, 3), keepdims=False)[:, None].astype(np.float32)
        train, val = Dataset(x[:768], y[:768]), Dataset(x[768:], y[768:])
        analysis = tune.run(
            tune.with_parameters(
                tune.train_sharded_regressor, train_data=train, val_data=val
            ),
            {
                "model": "resnet18",
                "learning_rate": tune.loguniform(1e-4, 1e-2),
                "seed": tune.randint(0, 1_000_000),
                "num_epochs": scale["epochs"],
                "batch_size": 64,
                "lr_schedule": "constant",
            },
            metric="validation_loss", mode="min",
            num_samples=scale["trials"],
            resources_per_trial={"devices": n_dev},
            storage_path=BENCH_RESULTS_DIR,
            name=exp_name or f"variant_resnet_{int(t0)}",
            verbose=0,
            callbacks=[_stderr_reporter()],
        )
        extra["devices_per_trial"] = n_dev
        extra["best_validation_loss"] = float(
            analysis.best_result.get("validation_loss", -1)
        )
    else:
        raise SystemExit(f"unknown variant {name!r}")
    wall = time.time() - t0
    done = analysis.num_terminated()
    print(json.dumps({
        "variant": name,
        "scale": scale_name,
        "trials_per_hour": round(done * 3600.0 / wall, 2),
        "wall_s": round(wall, 1),
        "done": done,
        "workload": scale,
        "platform": jax.devices()[0].platform,
        **extra,
    }))


def _variant_partial(name: str, exp_name: str, t_start: float):
    """Recover a partial result from a dead variant child's experiment dir.

    The runner rewrites experiment_state.json on every trial completion
    (tune/experiment.py write_state), so a child that stalled or crashed
    mid-sweep leaves an authoritative count of trials that finished and
    when.  Returns None when nothing terminated (nothing to claim)."""
    state_path = os.path.join(BENCH_RESULTS_DIR, exp_name,
                              "experiment_state.json")
    try:
        with open(state_path) as f:
            state = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    finished = [t for t in state.get("trials", [])
                if t.get("status") == "TERMINATED"]
    done = len(finished)
    wall = float(state.get("timestamp", t_start)) - t_start
    if done <= 0 or wall <= 0:
        return None
    metric = VARIANT_METRICS.get(name)
    best = min(
        (t["last_result"][metric] for t in finished
         if isinstance(t.get("last_result"), dict)
         and isinstance(t["last_result"].get(metric), (int, float))),
        default=None,
    )
    return {
        "variant": name,
        "scale": "full",
        "partial": True,
        "trials_per_hour": round(done * 3600.0 / wall, 2),
        "wall_s": round(wall, 1),
        "done": done,
        "workload": VARIANT_SCALES[name]["full"],
        "platform": "tpu",  # partials only come from the TPU child
        **({f"best_{metric}": best} if best is not None else {}),
    }


def run_variant(name: str) -> int:
    """Parent mode for --variant: run the variant child once at full
    scale, print ONE JSON line (the child's, or the trials that finished
    before it died, flagged partial).  Returns the exit code."""
    if name not in VARIANT_SCALES:
        raise SystemExit(
            f"unknown variant {name!r}; expected one of "
            f"{sorted(VARIANT_SCALES)}"
        )
    log = lambda m: print(f"[bench] {m}", file=sys.stderr, flush=True)
    exp_name = f"variant_{name}_{int(time.time())}"
    t_child = time.time()
    hb_path = os.path.join(
        tempfile.gettempdir(), f"bench_variant_hb_{os.getpid()}"
    )
    # Heartbeat-monitored: vectorized variants beat per dispatch,
    # thread-executor variants per trial result, so a hung child dies at
    # HEARTBEAT_STALE_S staleness, not the full timeout.
    rc, out, err = _run_child_monitored(
        ["--child", "variant", name, "full"],
        dict(os.environ, DML_BENCH_EXP_NAME=exp_name,
             DML_BENCH_HEARTBEAT_PATH=hb_path),
        1800, hb_path, HEARTBEAT_STALE_S,
    )
    _unlink_quiet(hb_path)
    res = _parse_result(out) if rc == 0 else None
    if res is not None:
        print(json.dumps(res), flush=True)
        return 0
    log(f"variant child failed rc={rc}; tail: {err[-400:]}")
    partial = _variant_partial(name, exp_name, t_child)
    if partial is not None:
        # Trials that DID terminate before the child died are real
        # evidence; report them (flagged) instead of forfeiting.
        log(f"recovered partial: {partial['done']} trials terminated")
        print(json.dumps(partial), flush=True)
    return rc or 1


# ---------------------------------------------------------------------------
# Child: MXU-bound flagship (single-chip step time + MFU)


def child_flagship() -> None:
    """Standalone flagship child: prints each incremental snapshot so a
    later-phase hang still leaves the MHA result on stdout (the parent
    takes the last parseable JSON line)."""
    _flagship_result(lambda snap: print(json.dumps(snap), flush=True))


def child_sharded_flagship() -> None:
    _sharded_flagship_result(lambda snap: print(json.dumps(snap), flush=True))


# ---------------------------------------------------------------------------
# Child: flagship step over a mesh SPANNING >1 process (ISSUE 14)


def child_multihost(process_id: int, num_processes: int, port: str) -> None:
    """One process of a 2+-process flagship step measurement: joins
    jax.distributed (the parent split device visibility per process via
    TPU_VISIBLE_CHIPS / the CPU device-count flag), builds a dp-across-
    processes × tp-inside mesh through multihost_mesh, and times the full
    sharded train step — cross-process gradient all-reduce included.
    Only process 0 prints the result JSON."""
    import time as _time

    import jax

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # Cross-process CPU collectives need a backend; gloo ships in jaxlib.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=num_processes,
        process_id=process_id,
    )
    import jax.numpy as jnp
    import numpy as np

    from distributed_machine_learning_tpu.models import build_model
    from distributed_machine_learning_tpu.models.flagship import (
        flagship_sharded_config,
        single_chip_hbm_bytes,
    )
    from distributed_machine_learning_tpu.multihost import runtime as mh
    from distributed_machine_learning_tpu.ops.flops import (
        device_peak_flops,
        train_step_flops,
    )
    from distributed_machine_learning_tpu.ops.losses import get_loss
    from distributed_machine_learning_tpu.ops.optimizers import make_optimizer
    from distributed_machine_learning_tpu.parallel.train_step import (
        make_sharded_train_step,
    )
    from distributed_machine_learning_tpu.tune.trainable_sharded import (
        _partitionable_threefry,
    )
    from jax.sharding import PartitionSpec as P

    devices = jax.devices()
    local0 = jax.local_devices()[0]
    cfg = flagship_sharded_config(single_chip_hbm_bytes(local0))
    F = FLAGSHIP["features"]
    B, S = int(cfg["batch_size"]), int(cfg["max_seq_length"])
    per_host = jax.local_device_count()
    tp = max(t for t in (1, 2, 4, 8)
             if per_host % t == 0 and int(cfg["num_heads"]) % t == 0)
    with _partitionable_threefry():
        mesh = mh.multihost_mesh(tp=tp, devices=devices)
        model = build_model(dict(cfg, mesh=mesh))
        tx = make_optimizer("adam", learning_rate=1e-3)
        init_fn, step_fn = make_sharded_train_step(
            model, tx, get_loss("mse"), mesh, shard_seq=False
        )
        rng = np.random.default_rng(0)
        with mesh:
            params, opt_state = init_fn(
                jax.random.key(0), jnp.zeros((1, S, F), jnp.float32)
            )
            x = mh.stage_global(
                rng.normal(size=(B, S, F)).astype(np.float32),
                (mesh, P("dp")),
            )
            y = mh.stage_global(
                rng.normal(size=(B, 1)).astype(np.float32), (mesh, P("dp"))
            )
            # Warmup (compile) + timed steps.
            params, opt_state, loss = step_fn(
                params, opt_state, x, y, jax.random.key(1)
            )
            jax.block_until_ready(loss)
            steps = 8
            t0 = _time.monotonic()
            for i in range(steps):
                params, opt_state, loss = step_fn(
                    params, opt_state, x, y, jax.random.key(2 + i)
                )
            jax.block_until_ready(loss)
            step_s = (_time.monotonic() - t0) / steps
    if process_id == 0:
        peak = device_peak_flops(local0, compute_dtype="float32")
        flops = train_step_flops(dict(cfg, features=F))
        mesh_peak = (peak or 0) * len(devices)
        print(json.dumps({
            "platform": local0.platform,
            "num_processes": num_processes,
            "num_devices": len(devices),
            "mesh": {k: int(v) for k, v in mesh.shape.items()},
            "step_s": round(step_s, 5),
            "mfu": (round(flops / step_s / mesh_peak, 4)
                    if mesh_peak else None),
            "loss": float(loss),
        }), flush=True)


def _sharded_flagship_result(progress_cb) -> dict:
    """Per-mesh-shape step time + MFU for the SHARDED flagship (ISSUE 7):
    the config whose params + adam moments exceed one chip's HBM
    (``models/flagship.py`` derives it from the measured budget), trained
    as the fused donated epoch program over 2-D (dp, tp) meshes built
    from the model family's partition rules.

    Per mesh shape: ``step_s`` (median of timed cells over the scan),
    ``mfu`` against the WHOLE mesh's peak (n_devices × per-chip peak —
    collective overhead reads as lost MFU, which is the honest number),
    and the ``compile_s``/``exec_s`` split from the compilecache
    tracker's counters.  Only meaningful on the MXU: the parent records
    a skipped-with-reason stub on CPU fallback instead of a
    non-comparable number.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_machine_learning_tpu import compilecache
    from distributed_machine_learning_tpu.models import build_model
    from distributed_machine_learning_tpu.models.flagship import (
        flagship_sharded_config,
        param_opt_bytes,
        single_chip_hbm_bytes,
    )
    from distributed_machine_learning_tpu.models.partition_rules import (
        rules_for,
    )
    from distributed_machine_learning_tpu.ops.flops import (
        device_peak_flops,
        train_step_flops,
    )
    from distributed_machine_learning_tpu.parallel.mesh import make_mesh
    from distributed_machine_learning_tpu.parallel.partition import (
        mesh_axis_sizes,
        rules_fingerprint,
    )
    from distributed_machine_learning_tpu.parallel.sharding import (
        opt_state_shardings,
        param_shardings,
    )
    from distributed_machine_learning_tpu.tune.trainable_sharded import (
        _partitionable_threefry,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    budget = single_chip_hbm_bytes(devices[0])
    cfg = flagship_sharded_config(budget)
    F = FLAGSHIP["features"]
    B, S = int(cfg["batch_size"]), int(cfg["max_seq_length"])
    num_batches = 4  # scan trip count per fused epoch program
    peak = device_peak_flops(devices[0], compute_dtype="float32")
    rules = rules_for(cfg)
    out = {
        "platform": devices[0].platform,
        "num_devices": n,
        "single_chip_hbm_bytes": budget,
        "param_opt_bytes": param_opt_bytes(cfg, features=F),
        "config": {k: v for k, v in cfg.items() if k != "mesh_shape"},
        "rules_fp": rules_fingerprint(rules),
        "meshes": {},
    }
    assert out["param_opt_bytes"] > budget  # the point of the section

    # Candidate 2-D shapes: every tp that divides both the device count
    # and the head count, dp = n // tp (dp and tp both > 1 = genuinely
    # 2-D; at most three shapes so the phase stays minutes, not hours).
    shapes = [
        {"dp": n // tp, "tp": tp}
        for tp in (2, 4, 8)
        if n % tp == 0 and n // tp > 1 and cfg["num_heads"] % tp == 0
    ][:3]

    tracker = compilecache.get_tracker()
    for mesh_shape in shapes:
        tag = "x".join(f"{k}{v}" for k, v in mesh_shape.items())
        _touch_heartbeat()
        try:
            with _partitionable_threefry():
                compile_base = tracker.total_seconds()
                mesh = make_mesh(mesh_shape, devices)
                model = build_model(dict(cfg, mesh=mesh))
                rng = jax.random.key(0)
                x1 = jnp.zeros((1, S, F), jnp.float32)
                shapes_v = jax.eval_shape(
                    lambda r, x: model.init(r, x, deterministic=True),
                    {"params": rng, "dropout": rng}, x1,
                )
                p_sh = param_shardings(shapes_v["params"], mesh, rules)
                params = jax.jit(
                    lambda r, x: model.init(r, x, deterministic=True),
                    out_shardings={"params": p_sh},
                )({"params": rng, "dropout": rng}, x1)["params"]
                tx = optax.adam(1e-3)
                o_sh = opt_state_shardings(
                    jax.eval_shape(tx.init, params), p_sh, mesh
                )
                opt_state = jax.jit(
                    tx.init, in_shardings=(p_sh,), out_shardings=o_sh
                )(params)
                repl = NamedSharding(mesh, P())
                xb_sh = NamedSharding(mesh, P(None, "dp"))

                def epoch(params, opt_state, xb, yb, key):
                    def step(carry, batch):
                        params, opt_state, i = carry
                        x, y = batch

                        def loss_of(p):
                            preds = model.apply(
                                {"params": p}, x,
                                rngs={"dropout": jax.random.fold_in(key, i)},
                                deterministic=False,
                            )
                            return jnp.mean(
                                (preds.astype(jnp.float32) - y) ** 2
                            )

                        loss, grads = jax.value_and_grad(loss_of)(params)
                        updates, opt_state = tx.update(
                            grads, opt_state, params
                        )
                        params = optax.apply_updates(params, updates)
                        return (params, opt_state, i + 1), loss

                    (params, opt_state, _), losses = jax.lax.scan(
                        step, (params, opt_state, jnp.int32(0)), (xb, yb)
                    )
                    return params, opt_state, losses.mean()

                train_epoch = jax.jit(
                    epoch,
                    donate_argnums=(0, 1, 2, 3),
                    in_shardings=(p_sh, o_sh, xb_sh, xb_sh, repl),
                    out_shardings=(p_sh, o_sh, repl),
                )

                rs = np.random.RandomState(0)

                def batches():
                    xb = jax.device_put(
                        rs.randn(num_batches, B, S, F).astype(np.float32),
                        xb_sh,
                    )
                    yb = jax.device_put(
                        rs.randn(num_batches, B, 1).astype(np.float32),
                        xb_sh,
                    )
                    return xb, yb

                t0 = time.time()
                xb, yb = batches()
                params, opt_state, loss = train_epoch(
                    params, opt_state, xb, yb, jax.random.key(1)
                )
                float(loss)
                compile_plus_first = time.time() - t0
                compile_s = tracker.total_seconds() - compile_base

                cells = []
                for _ in range(4):
                    _touch_heartbeat()
                    xb, yb = batches()  # donated each epoch: restage
                    t0 = time.time()
                    params, opt_state, loss = train_epoch(
                        params, opt_state, xb, yb, jax.random.key(2)
                    )
                    float(loss)
                    cells.append((time.time() - t0) / num_batches)
                step_s = _median(cells)
                cells.sort()
                flops = train_step_flops(cfg, B, S, F)
                mesh_peak = (peak or 0) * n
                out["meshes"][tag] = {
                    "mesh_shape": dict(mesh_shape),
                    "step_s": round(step_s, 5),
                    "step_s_spread": [round(cells[0], 5),
                                      round(cells[-1], 5)],
                    "flops_per_step": flops,
                    "mfu": (round(flops / step_s / mesh_peak, 4)
                            if mesh_peak else None),
                    "tflops_per_s": round(flops / step_s / 1e12, 2),
                    # compile_s: backend-compile seconds from the
                    # compilecache tracker (event durations fire on hits
                    # too, so this can exceed the first-call wall on
                    # cache-warm hosts); exec_s: one steady-state epoch's
                    # measured execute wall.
                    "compile_s": round(compile_s, 1),
                    "exec_s": round(step_s * num_batches, 2),
                    "compile_plus_first_epoch_s": round(
                        compile_plus_first, 1
                    ),
                }
                # Free the mesh's buffers before the next shape compiles.
                del params, opt_state, xb, yb
        except Exception as exc:  # noqa: BLE001 - smaller shapes still count
            out["meshes"][tag] = {"error": repr(exc)[-300:]}
        progress_cb(out)
    best = max(
        (m for m in out["meshes"].values() if m.get("mfu")),
        key=lambda m: m["mfu"], default=None,
    )
    if best:
        out["mfu"] = best["mfu"]
        out["step_s"] = best["step_s"]
        out["best_mesh"] = best["mesh_shape"]
    out["compile_cache"] = compilecache.get_counters().snapshot()
    out["complete"] = True
    progress_cb(out)
    return out


def _flagship_result(progress_cb) -> dict:
    """Train-step time + MFU at the MXU-bound shape (FLAGSHIP): d_model 512,
    seq 2048, bf16 compute, explicit Pallas flash attention.  The sweep
    workload (d_model 64, seq 96) is latency-bound by design; this is the
    configuration whose MFU says how well the compute path maps to the MXU
    (VERDICT r3 next #2).  Timing forces a scalar readback per step.

    ``progress_cb(snapshot)`` is invoked after every completed sub-phase
    (MHA, GQA variant, batch x2) with the result-so-far, so the caller can
    print or checkpoint incrementally; the final snapshot is returned.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_machine_learning_tpu.models import build_model
    from distributed_machine_learning_tpu.ops.flops import (
        device_peak_flops,
        train_step_flops,
    )

    B, S, F = FLAGSHIP["batch"], FLAGSHIP["seq"], FLAGSHIP["features"]
    base_cfg = {
        "model": "transformer",
        "d_model": FLAGSHIP["d_model"],
        "num_heads": FLAGSHIP["num_heads"],
        "num_layers": FLAGSHIP["num_layers"],
        "dim_feedforward": FLAGSHIP["dim_feedforward"],
        "dropout": 0.0,
        "attention_type": "flash",
        "compute_dtype": "bfloat16",
        "max_seq_length": FLAGSHIP["seq"],
    }
    peak = device_peak_flops(jax.devices()[0], compute_dtype="bfloat16")

    def measure(cfg: dict, batch: int = B, seq_len: int = S) -> dict:
        model = build_model(dict(cfg, max_seq_length=seq_len))
        rng = jax.random.PRNGKey(0)
        x = jnp.asarray(np.random.RandomState(0).randn(batch, seq_len, F),
                        jnp.float32)
        y = jnp.asarray(np.random.RandomState(1).randn(batch, 1),
                        jnp.float32)
        params = model.init({"params": rng, "dropout": rng}, x,
                            deterministic=True)["params"]
        tx = optax.adam(1e-3)
        opt_state = tx.init(params)

        # donate_argnums: the old params/opt buffers alias the outputs —
        # undonated, every measured step pays an extra params+opt HBM
        # copy and the MFU reads low (dmlint DML008 caught this).
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt_state, x, y, rng):
            def loss_of(p):
                preds = model.apply({"params": p}, x, rngs={"dropout": rng},
                                    deterministic=False)
                return jnp.mean((preds.astype(jnp.float32) - y) ** 2)

            loss, grads = jax.value_and_grad(loss_of)(params)
            updates, opt_state2 = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state2, loss

        t0 = time.time()
        params, opt_state, loss = step(params, opt_state, x, y, rng)
        float(loss)  # readback: compile + first step complete
        compile_s = time.time() - t0

        # >=5 timed cells (VERDICT r3 next #8), each a small fixed step
        # count with a forced readback; report the median + spread.
        steps_per_cell, cells = 5, 6
        cell_s = []
        for _ in range(cells):
            _touch_heartbeat()
            t0 = time.time()
            for _ in range(steps_per_cell):
                params, opt_state, loss = step(params, opt_state, x, y, rng)
            float(loss)
            cell_s.append((time.time() - t0) / steps_per_cell)
        step_s = _median(cell_s)
        cell_s.sort()
        flops = train_step_flops(cfg, batch, seq_len, F)
        return {
            "step_s": round(step_s, 5),
            "step_s_spread": [round(cell_s[0], 5), round(cell_s[-1], 5)],
            "cells": cells,
            "steps_per_cell": steps_per_cell,
            "compile_plus_first_step_s": round(compile_s, 1),
            "flops_per_step": flops,
            "mfu": (round(flops / step_s / peak, 4) if peak else None),
            "tflops_per_s": round(flops / step_s / 1e12, 2),
        }

    out = measure(base_cfg)
    out.update({
        "peak_flops": peak,
        "platform": jax.devices()[0].platform,
        "config": dict(base_cfg, batch=B, seq=S, features=F),
    })
    # Surface the MHA flagship result BEFORE attempting the GQA variant: a
    # GQA-phase hang then costs only the variant, not the round's MFU
    # evidence.
    progress_cb(out)
    # Grouped-query variant at the same shape: the native grouped-kv flash
    # kernel keeps K/V at kv_heads width end to end (VERDICT r3 next #4) —
    # its step-time delta vs full MHA is the driver-artifact evidence of
    # the kv-projection + kv-bandwidth saving. train_step_flops scales the
    # K/V terms by kv_heads/heads, so BOTH MFUs stay honest.
    try:
        gqa = measure(dict(base_cfg, num_kv_heads=2))
        gqa["speedup_vs_mha"] = (
            round(out["step_s"] / gqa["step_s"], 3) if gqa["step_s"] else None
        )
        out["gqa_kv2"] = gqa
    except Exception as exc:  # noqa: BLE001 - MHA number still stands
        out["gqa_kv2"] = {"error": repr(exc)[-300:]}
    progress_cb(out)
    # Batch scaling: the MXU's utilization rises with the M dimension —
    # measured 0.243 MFU at B8 vs 0.284 at B16 on the v5e chip — so climb
    # the doublings (B -> 2B -> 4B) while they keep winning.  Each variant
    # is measured in its own compile, printed incrementally, and PROMOTED
    # to the headline step/MFU when it wins — the artifact self-selects
    # the best honest single-chip number (config recorded either way).
    # The climb stops at the first non-improving doubling (a losing 2B
    # means 4B would pay another compile to lose harder) or on error
    # (e.g. activation HBM exhaustion at the biggest batch).
    # (2, 4, 8): the 2026-08-01 capture promoted x4 (B32, mfu 0.3111) as
    # the last rung tried while still improving — x8 is attempted only
    # when x4 won, so a stalling climb costs nothing extra.
    for mult in (2, 4, 8):
        key = f"batch_x{mult}"
        try:
            bx = FLAGSHIP["batch"] * mult
            var = measure(base_cfg, batch=bx)
            var["batch"] = bx
            out[key] = var
            if var["mfu"] and out["mfu"] and var["mfu"] > out["mfu"]:
                # Promote EVERY per-run field the variant shares with the
                # base record (a hand-picked subset would mix two configs'
                # numbers under one config), then stamp the winning batch.
                out.update({k: v for k, v in var.items() if k in out})
                out["config"] = dict(out["config"], batch=bx)
            else:
                break
        except Exception as exc:  # noqa: BLE001 - base result still stands
            out[key] = {"error": repr(exc)[-300:]}
            break
        progress_cb(out)
    # The GQA comparison must match the PROMOTED config: when a bigger
    # batch won the headline, re-measure grouped-kv at that batch so
    # speedup_vs_mha compares like with like (the base-batch comparison
    # stays in gqa_kv2).
    # Sequence scaling at the winning batch (VERDICT r4 next #2, the
    # seq-4096 knob): doubling S quadruples attention FLOPs per token
    # window while the flash kernel stays O(S) in memory — if the longer
    # program tiles the MXU better, it takes the headline (config
    # recorded either way; an HBM-exhaustion error is recorded and the
    # climb stops).
    win_b = out["config"]["batch"]
    try:
        sx = measure(base_cfg, batch=win_b, seq_len=2 * S)
        sx["seq"] = 2 * S
        out["seq_x2"] = sx
        if sx["mfu"] and out["mfu"] and sx["mfu"] > out["mfu"]:
            out.update({k: v for k, v in sx.items() if k in out})
            out["config"] = dict(out["config"], seq=2 * S)
    except Exception as exc:  # noqa: BLE001 - winner so far still stands
        out["seq_x2"] = {"error": repr(exc)[-300:]}
    progress_cb(out)
    # Flash tile probe at the winning shape (VERDICT r4 next #2 "flash
    # tile re-tune"): the kernel's default tiles were chosen at smaller
    # shapes; block 256 at the flagship shape is one extra compile and is
    # promoted on an MFU win like the other knobs.
    win_s = out["config"].get("seq", S)
    win_cfg = dict(base_cfg)
    try:
        tl = measure(dict(base_cfg, block_size=256),
                     batch=win_b, seq_len=win_s)
        tl["block_size"] = 256
        out["tile_256"] = tl
        if tl["mfu"] and out["mfu"] and tl["mfu"] > out["mfu"]:
            out.update({k: v for k, v in tl.items() if k in out})
            out["config"] = dict(out["config"], block_size=256)
            win_cfg["block_size"] = 256
    except Exception as exc:  # noqa: BLE001 - winner so far still stands
        out["tile_256"] = {"error": repr(exc)[-300:]}
    progress_cb(out)
    # The GQA comparison must match the PROMOTED config: when a bigger
    # batch, longer sequence, or re-tuned tile won the headline,
    # re-measure grouped-kv at the FINAL config so speedup_vs_mha
    # compares like with like (the base-shape comparison stays in
    # gqa_kv2).
    if (win_b != B or win_s != S or "block_size" in win_cfg) \
            and "error" not in out.get("gqa_kv2", {}):
        try:
            gqa_w = measure(dict(win_cfg, num_kv_heads=2),
                            batch=win_b, seq_len=win_s)
            gqa_w["batch"] = win_b
            gqa_w["seq"] = win_s
            gqa_w["speedup_vs_mha"] = (
                round(out["step_s"] / gqa_w["step_s"], 3)
                if gqa_w["step_s"] else None
            )
            out["gqa_kv2_winner"] = gqa_w
        except Exception as exc:  # noqa: BLE001 - base comparison stands
            out["gqa_kv2_winner"] = {"error": repr(exc)[-300:]}
    # Checkpoint + heartbeat before the XL compile: two fresh compiles
    # (gqa winner + XL) in one heartbeat gap could exceed the staleness
    # kill and lose BOTH from the partial snapshot.
    progress_cb(out)
    # XL ceiling probe (never promoted): the parity flagship is pinned to
    # the reference's d_model 512 (ray-tune-hpo-regression.py:456-459),
    # whose contractions under-fill the MXU; one d_model-1024 / 8-layer
    # cell records the MFU the same compute path reaches when the shape
    # feeds the systolic array properly.  Kept out of the headline —
    # it is a different model than the flagship — but carried in the
    # artifact as the framework's measured ceiling.
    if out["platform"] == "tpu":
        try:
            xl_cfg = dict(base_cfg, d_model=1024, num_heads=16,
                          num_layers=8, dim_feedforward=4096)
            xl = measure(xl_cfg, batch=B, seq_len=S)
            xl["config"] = dict(xl_cfg, batch=B, seq=S, features=F)
            out["xl_d1024"] = xl
        except Exception as exc:  # noqa: BLE001 - flagship result stands
            out["xl_d1024"] = {"error": repr(exc)[-300:]}
    else:
        # A d1024/8-layer compile is minutes on the fallback host for a
        # number that only means something on the MXU.
        out["xl_d1024"] = {"skipped": out["platform"]}
    # Every sub-phase ran (possibly recording its error): intermediate
    # snapshots recovered from a killed child lack this marker, and the
    # parent turns its absence into the `partial` honesty flag.
    out["complete"] = True
    progress_cb(out)
    return out


# ---------------------------------------------------------------------------
# Child: the one-process TPU suite (flagship + both-dtype sweeps)


def _device_record() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _require_tpu() -> None:
    """A measurement path that finds no chip fails: no CPU arm."""
    dev = _device_record()
    if dev["platform"] != "tpu":
        print(f"[bench] refusing to measure on {dev}: bench.py needs a TPU",
              file=sys.stderr, flush=True)
        raise SystemExit(3)


def child_suite(scale_name: str) -> None:
    """Run the WHOLE measurement suite — f32 sweep (the headline), then
    flagship, then the bf16 sweep — in ONE process: a chip belongs to one
    process at a time, and one process also shares every compile.

    The printed JSON names the device (``device``: platform, kind, count).
    ``python bench.py --child suite`` refuses any platform but ``tpu``
    before calling this (``_require_tpu``); CPU tests call it directly.

    Each phase checkpoints into DML_BENCH_PARTIAL_PATH, and a fresh suite
    child RESUMES from that file (completed phases are skipped).  Phase
    boundaries touch DML_BENCH_HEARTBEAT_PATH; the parent kills the child
    when the heartbeat goes stale instead of waiting out the full timeout.
    """
    t0 = time.time()
    note = _make_note(t0)
    partial_path = os.environ.get("DML_BENCH_PARTIAL_PATH")
    checkpoint = _make_checkpoint(partial_path)
    budget_s = float(os.environ.get("DML_BENCH_CHILD_BUDGET_S", "0") or 0)

    def remaining_s() -> float:
        return (budget_s - (time.time() - t0)) if budget_s else 1e9

    suite: dict = {}
    if partial_path and os.path.exists(partial_path):
        try:
            with open(partial_path) as f:
                suite = json.load(f)
            note(f"resuming: have {sorted(suite)} "
                 f"+ sweeps {sorted(suite.get('sweeps') or {})}")
        except (OSError, json.JSONDecodeError):
            suite = {}
    suite.setdefault("sweeps", {})

    # A tiny op through the backend first, narrated, so a start-up stall
    # is distinguishable from a compile/execute stall.
    import jax
    import jax.numpy as jnp

    note("starting backend")
    assert float(jnp.ones((8, 8)).sum()) == 64.0
    suite["device"] = _device_record()
    note(f"backend up: {suite['device']}")

    # Phase order is value-at-risk: the f32 sweep carries the round's
    # HEADLINE (trials/hour, the `value` field) and is the scarcest
    # evidence — it gets the chip first.  bf16 closes (its headline-alt
    # role survives via the f32 number).
    scale = FULL if scale_name == "full" else SMALL

    def run_sweep_phase(dtype: str) -> None:
        prev = suite["sweeps"].get(dtype)
        if prev and "error" not in prev:
            # Keep completed AND partial results (a cold number in hand is
            # not worth re-risking a stall for warm repeats); re-run only
            # sweeps that raised.
            note(f"sweep {dtype} already in partial; skipping")
            return
        if remaining_s() < 120:
            note(f"skipping sweep {dtype}: {remaining_s():.0f}s left")
            return

        def sweep_checkpoint(snapshot: dict) -> None:
            suite["sweeps"][dtype] = snapshot
            checkpoint(suite)

        note(f"sweep {dtype} start")
        try:
            suite["sweeps"][dtype] = _sweep_result(
                scale, dtype, note, sweep_checkpoint, remaining_s
            )
            checkpoint(suite)
        except Exception:  # noqa: BLE001 - keep earlier phases
            import traceback

            tb = traceback.format_exc()
            note(f"sweep {dtype} FAILED: {tb.splitlines()[-1]}")
            suite["sweeps"][dtype] = {"error": tb[-800:]}
            checkpoint(suite)
        note(f"sweep {dtype} done")

    run_sweep_phase("float32")

    # Re-run the flagship when the stored snapshot is absent, errored, OR
    # an intermediate (no "complete" marker — a child killed mid-sub-phase
    # left e.g. only the MHA cell); re-measuring is cheap relative to a
    # sweep and recovers the GQA/batch-climb evidence (advisor r4).
    if (not suite.get("flagship") or "error" in suite["flagship"]
            or not suite["flagship"].get("complete")):
        if remaining_s() < 120:
            note(f"skipping flagship: {remaining_s():.0f}s left")
        else:
            note(f"flagship start: {FLAGSHIP}")
            try:
                def on_progress(snap):
                    suite["flagship"] = snap
                    checkpoint(suite)
                _flagship_result(on_progress)
            except Exception:  # noqa: BLE001 - sweeps carry TPU evidence
                import traceback

                suite["flagship"] = {"error": traceback.format_exc()[-800:]}
                checkpoint(suite)
            note("flagship done")
    else:
        note("flagship already in partial; skipping")

    # Sharded-flagship phase (ISSUE 7): per-mesh-shape step/MFU for the
    # over-HBM config.  After the single-chip flagship (its MFU is the
    # headline comparison), before bf16 (scarcer evidence first).
    prev_sf = suite.get("sharded_flagship")
    if prev_sf and "error" not in prev_sf and prev_sf.get("complete"):
        note("sharded_flagship already in partial; skipping")
    elif remaining_s() < 240:
        note(f"skipping sharded_flagship: {remaining_s():.0f}s left")
    else:
        note("sharded_flagship start")
        try:
            def on_sf(snap):
                suite["sharded_flagship"] = snap
                checkpoint(suite)
            _sharded_flagship_result(on_sf)
        except Exception:  # noqa: BLE001 - earlier phases still stand
            import traceback

            suite["sharded_flagship"] = {
                "error": traceback.format_exc()[-800:]
            }
            checkpoint(suite)
        note("sharded_flagship done")

    run_sweep_phase("bfloat16")

    # Quality-at-budget phase (BASELINE.md row 4): our side of the equal-
    # wall-clock comparison runs in this same process.
    qb = _quality_budget_s()
    prev_q = suite.get("quality")
    if prev_q and "error" not in prev_q:
        note("quality already in partial; skipping")
    elif qb <= 0:
        note("quality phase disabled (budget 0)")
    elif remaining_s() < qb + 60:
        note(f"skipping quality phase: {remaining_s():.0f}s left "
             f"< budget {qb:.0f}s + 60s margin")
    else:
        note(f"quality phase start (budget {qb:.0f}s)")
        try:
            suite["quality"] = _quality_result(scale, qb, note)
        except Exception:  # noqa: BLE001 - earlier phases still stand
            import traceback

            suite["quality"] = {"error": traceback.format_exc()[-800:]}
        checkpoint(suite)
        note("quality done")

    print(json.dumps(suite))


# ---------------------------------------------------------------------------
# Child: serving soak (ISSUE 8 serve_soak section)


def child_serve_soak() -> None:
    """Sustained-RPS soak of the serving plane: continuous batching,
    replica autoscaling, admission control, a chaos replica kill and a
    zero-downtime hot swap both landing mid-soak.

    The request stream is a load STEP (base -> burst -> base) so the
    autoscaler has something real to answer; the kill lands in the first
    base phase, the swap during the burst.  Emits ONE JSON line whose
    claims are counter-verified from /metrics: achieved RPS, windowed
    p50/p99 against the stated SLO, shed rate, dropped (non-shed)
    requests (must be 0 — replica deaths redispatch server-side),
    post-swap recompiles (must be 0 — the swap warms through the AOT
    caches off-path), and the replica-count trajectory."""
    import threading
    import urllib.error
    import urllib.request

    import jax
    import numpy as np

    from distributed_machine_learning_tpu import chaos, serve
    from distributed_machine_learning_tpu.models import build_model

    requests_n = int(os.environ.get("DML_SOAK_REQUESTS", "240"))
    base_rps = float(os.environ.get("DML_SOAK_RPS", "40"))
    burst_rps = float(os.environ.get("DML_SOAK_BURST_RPS", "120"))
    slo_ms = float(os.environ.get("DML_SOAK_SLO_P99_MS", "500"))
    rows, feat = 4, 8

    config = {"model": "mlp", "hidden_sizes": [32, 16]}
    model = build_model(config)
    x0 = np.zeros((rows, feat), np.float32)
    variables_a = model.init(jax.random.PRNGKey(0), x0, deterministic=True)
    # The "new model" of the promotion: same architecture cohort (shared
    # bucket programs through the AOT cache), different weights.
    variables_b = jax.tree_util.tree_map(
        lambda a: np.array(a) * 1.001, variables_a
    )
    bundle_a = serve.ServableBundle(
        config=dict(config), variables=variables_a, path="soak://a"
    )
    bundle_b = serve.ServableBundle(
        config=dict(config), variables=variables_b, path="soak://b"
    )

    kill_at = max(requests_n // 4, 2)
    swap_at = max(requests_n // 2, 4)
    plan = chaos.FaultPlan(
        seed=7, replica_kills=((kill_at, -1),), hot_swaps=(swap_at,),
    )
    srv = serve.PredictionServer(
        bundle_a, port=0, num_replicas=2,
        max_batch_size=16, max_bucket=16, batcher="continuous",
        max_queue=256, shed_watermark=192,
        autoscale=serve.AutoscaleConfig(
            min_replicas=1, max_replicas=3, up_queue_depth=6,
            slo_p99_ms=slo_ms, down_idle_s=1.0, cooldown_s=0.5,
            interval_s=0.1,
        ),
        request_timeout_s=30.0, fault_plan=plan,
    )
    srv.warmup(x0)
    swap_done = threading.Event()

    def do_swap():
        serve.hot_swap(srv.replicas, bundle_b, sample=x0)
        swap_done.set()

    srv.replicas.on_swap_signal = do_swap
    host, port = srv.start()
    url = f"http://{host}:{port}/predict"
    payload = json.dumps({"instances": x0.tolist()}).encode()

    counts = {"ok": 0, "shed": 0, "dropped": 0}
    counts_lock = threading.Lock()

    def one_request():
        req = urllib.request.Request(
            url, data=payload,
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                resp.read()
            key = "ok"
        except urllib.error.HTTPError as exc:
            # Honest shed = an admission/breaker answer WITH backpressure
            # (Retry-After); anything else the client never got is a drop.
            shed = exc.code == 429 or (
                exc.code == 503 and exc.headers.get("Retry-After")
            )
            key = "shed" if shed else "dropped"
        except Exception:  # noqa: BLE001 - network-level failure = drop
            key = "dropped"
        with counts_lock:
            counts[key] += 1

    burst_lo, burst_hi = requests_n * 2 // 5, requests_n * 4 // 5
    t0 = time.time()
    threads = []
    for i in range(requests_n):
        th = threading.Thread(target=one_request, daemon=True)
        th.start()
        threads.append(th)
        rps = burst_rps if burst_lo <= i < burst_hi else base_rps
        time.sleep(1.0 / rps)
    for th in threads:
        th.join(timeout=60)
    soak_wall = time.time() - t0
    swap_landed = swap_done.wait(timeout=30)

    # Post-step settle: the trajectory should come back DOWN after the
    # load stops (down_idle_s + cooldown; bounded wait, not a sleep).
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if srv.replicas.scale_stats()["scale_downs"] >= 1:
            break
        time.sleep(0.2)

    m = srv.handle_metrics()
    scale = m["autoscale"]
    faults = plan.snapshot()
    result = {
        "platform": "cpu",
        "requests": requests_n,
        "ok": counts["ok"],
        "shed": counts["shed"],
        "dropped": counts["dropped"],
        "shed_rate": round(counts["shed"] / max(requests_n, 1), 4),
        "achieved_rps": round(counts["ok"] / max(soak_wall, 1e-9), 2),
        "offered_rps": round(requests_n / max(soak_wall, 1e-9), 2),
        "p50_ms": m["latency_ms_p50"],
        "p99_ms": m["latency_ms_p99"],
        "slo_ms": slo_ms,
        "slo_met": bool(m["latency_ms_p99"] <= slo_ms),
        "latency_window": m["latency_window"],
        "replica_kills": faults.get("replica_kills", 0),
        "hot_swap_signals": faults.get("hot_swap_signals", 0),
        "swap_landed": bool(swap_landed),
        "swaps_total": m["swap"]["swaps_total"],
        "post_swap_new_programs": m["compile"]["new_programs_since_warmup"],
        "redispatches": m["admission"]["redispatches"],
        "sheds_total": m["admission"]["sheds_total"],
        # restarts may be 0 when the swap replaced the dead slot before
        # the monitor's next tick — "healed" is the invariant, the healer
        # is a race between two working recovery paths.
        "restarts": m["restarts"],
        "replicas_healthy": m["num_healthy"],
        "breaker_opens": m["breakers"]["opens_total"],
        "scale_ups": scale["scale_ups"],
        "scale_downs": scale["scale_downs"],
        "replicas_final": scale["replicas"],
        "trajectory": [
            (e["t_s"], e["replicas"]) for e in scale["events"]
        ],
        "wall_s": round(soak_wall, 2),
    }
    srv.close()

    # Quantized arm (ISSUE 16): the SAME architecture served as int8
    # beside an f32 control.  Both arms get a clean fixed-replica server
    # (no chaos, no autoscale) so rps-per-replica and p99 compare the
    # PRECISION, not the fault schedule; each arm's ``comparability`` is
    # keyed on precision so trend tooling never diffs across the
    # f32/int8 boundary.
    from distributed_machine_learning_tpu import quant

    def _precision_arm(bundle):
        arm_n = max(requests_n // 4, 24)
        s2 = serve.PredictionServer(
            bundle, port=0, num_replicas=2, max_batch_size=16,
            max_bucket=16, batcher="continuous", max_queue=256,
            request_timeout_s=30.0,
        )
        s2.warmup(x0)
        h2, p2 = s2.start()
        arm_url = f"http://{h2}:{p2}/predict"
        arm_ok = [0]
        arm_lock = threading.Lock()

        def _req():
            req = urllib.request.Request(
                arm_url, data=payload,
                headers={"Content-Type": "application/json"},
            )
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                with arm_lock:
                    arm_ok[0] += 1
            except Exception:  # noqa: BLE001 - arm is a measurement
                pass

        t_arm = time.time()
        ths = []
        for _ in range(arm_n):
            th = threading.Thread(target=_req, daemon=True)
            th.start()
            ths.append(th)
            time.sleep(1.0 / base_rps)
        for th in ths:
            th.join(timeout=60)
        arm_wall = time.time() - t_arm
        m2 = s2.handle_metrics()
        replicas = max(m2["num_healthy"], 1)
        arm = {
            "precision": m2["precision"],
            "requests": arm_n,
            "ok": arm_ok[0],
            "rps_per_replica": round(
                arm_ok[0] / max(arm_wall, 1e-9) / replicas, 2
            ),
            "p99_ms": m2["latency_ms_p99"],
            "new_programs_since_warmup":
                m2["compile"]["new_programs_since_warmup"],
            "comparability": f"cpu-{m2['precision']}",
        }
        if m2.get("quality_delta_mape") is not None:
            arm["quality_delta_mape"] = round(m2["quality_delta_mape"], 6)
        s2.close()
        return arm

    qvariables, _qstats = quant.quantize_variables(variables_b, "int8")
    bundle_q = serve.ServableBundle(
        config=dict(config), variables=qvariables,
        manifest={"precision": "int8"}, path="soak://b-int8",
    )
    result["precision"] = "f32"
    result["comparability"] = "cpu-f32"
    result["precision_arms"] = {
        "f32": _precision_arm(bundle_b),
        "int8": _precision_arm(bundle_q),
    }
    print(json.dumps(result))


# Child: out-of-core streaming vs resident (ISSUE 10 streaming section)


def child_streaming() -> None:
    """Out-of-core input pipeline: the SAME workload trained twice — once
    HBM-resident (under a huge virtual budget) and once through the
    double-buffered prefetch ring (under a budget the dataset provably
    exceeds, so ``"auto"`` engages streaming and resident staging raises).

    Emits ONE JSON line whose claims are counter-verified: per-step time
    in both modes and their ratio (acceptance: streaming step rate >=
    0.9x resident), overlap efficiency with the producer/consumer wait
    counters behind it, and bit-identical final params (the determinism
    contract, re-proven on the bench workload)."""
    import numpy as np

    from distributed_machine_learning_tpu.data import dummy_regression_data
    from distributed_machine_learning_tpu.data import pipeline as hostpipe
    from distributed_machine_learning_tpu.tune import session as tune_session
    from distributed_machine_learning_tpu.tune.trainable import (
        train_regressor,
    )

    t0 = time.time()
    note = _make_note(t0)
    budget = int(os.environ.get("DML_STREAM_BUDGET_BYTES", str(8 << 20)))
    samples = int(os.environ.get("DML_STREAM_SAMPLES", "9000"))
    epochs = int(os.environ.get("DML_STREAM_EPOCHS", "4"))
    seq, feats = 16, 16
    train, val = dummy_regression_data(
        num_samples=samples, seq_len=seq, num_features=feats, seed=7
    )
    dataset_bytes = hostpipe.staged_nbytes(train, val, np.float32)
    config = {
        "model": "transformer", "d_model": 64, "num_heads": 4,
        "num_layers": 2, "dim_feedforward": 128, "dropout": 0.1,
        "max_seq_length": seq, "learning_rate": 1e-3, "batch_size": 64,
        "num_epochs": epochs, "seed": 3, "checkpoint_freq": epochs,
        "lr_schedule": "constant",
    }
    steps_per_epoch = len(train) // config["batch_size"]

    def run_mode(tag):
        records = []
        sess = tune_session.Session(
            trial=tune_session._StandaloneTrial(),
            report_fn=lambda m, c: records.append((m, c)) or "continue",
            checkpoint_loader=lambda: None,
        )
        tune_session.set_session(sess)
        try:
            train_regressor(dict(config), train_data=train, val_data=val)
        finally:
            tune_session.set_session(None)
        note(f"{tag}: {len(records)} epochs")
        # Median WARM epoch (epoch 0 carries the compile).
        walls = sorted(r[0]["epoch_time_s"] for r in records[1:])
        step_s = walls[len(walls) // 2] / max(steps_per_epoch, 1)
        return step_s, records

    # The virtual-budget overrides below are scoped: normally this runs
    # in a throwaway bench child, but test_bench drives the section
    # in-process, and a leaked 256 KiB "HBM" budget rewrites what
    # flagship_sharded_config derives for every later caller (found by
    # the jaxlint flagship-fit audit going red mid-suite).
    _prior_budget = os.environ.get("DML_CPU_DEVICE_BUDGET_BYTES")
    try:
        # Resident arm: budget far above the dataset -> "auto" stays
        # resident.
        os.environ["DML_CPU_DEVICE_BUDGET_BYTES"] = str(1 << 30)
        _touch_heartbeat()
        resident_step_s, resident_records = run_mode("resident")
        assert resident_records[-1][0].get("input_mode") != "streaming"

        # Streaming arm: the dataset exceeds the virtual budget ->
        # resident staging provably fails, "auto" engages the ring.
        os.environ["DML_CPU_DEVICE_BUDGET_BYTES"] = str(budget)
        resident_over_budget = False
        try:
            hostpipe.check_resident_budget(dataset_bytes)
        except hostpipe.ResidentOverBudgetError:
            resident_over_budget = True
        counters = hostpipe.get_host_input_counters()
        base = counters.snapshot()
        _touch_heartbeat()
        streaming_step_s, streaming_records = run_mode("streaming")
        hi = counters.delta_since(base)
        eff = hostpipe.overlap_efficiency(hi)
    finally:
        if _prior_budget is None:
            os.environ.pop("DML_CPU_DEVICE_BUDGET_BYTES", None)
        else:
            os.environ["DML_CPU_DEVICE_BUDGET_BYTES"] = _prior_budget

    import jax

    bit_identical = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree.leaves(resident_records[-1][1]["params"]),
            jax.tree.leaves(streaming_records[-1][1]["params"]),
        )
    )
    ratio = resident_step_s / max(streaming_step_s, 1e-9)
    result = {
        "platform": jax.devices()[0].platform,
        "dataset_mb": round(dataset_bytes / 2**20, 2),
        "budget_mb": round(budget / 2**20, 2),
        "resident_over_budget": resident_over_budget,
        "streamed": streaming_records[-1][0].get("input_mode")
        == "streaming",
        "epochs": epochs,
        "steps_per_epoch": steps_per_epoch,
        "resident_step_s": round(resident_step_s, 5),
        "streaming_step_s": round(streaming_step_s, 5),
        # Acceptance: streaming >= 0.9x resident step RATE (ratio of step
        # times, resident over streaming).
        "step_rate_vs_resident": round(ratio, 3),
        "pass_0p9": bool(ratio >= 0.9),
        "overlap_efficiency": eff,
        "chunks_staged": hi.get("chunks_staged"),
        "bytes_staged": hi.get("bytes_staged"),
        "prefetch_hits": hi.get("prefetch_hits"),
        "consumer_waits": hi.get("consumer_waits"),
        "consumer_wait_s": hi.get("consumer_wait_s"),
        "producer_waits": hi.get("producer_waits"),
        "producer_wait_s": hi.get("producer_wait_s"),
        "params_bit_identical": bool(bit_identical),
        "wall_s": round(time.time() - t0, 1),
    }
    print(json.dumps(result))


# Child: self-healing loop time-to-recover (ISSUE 17 online_loop section)


def child_online_loop() -> None:
    """The self-healing loop end to end, timed: serve an incumbent, shift
    the world mid-stream, and measure how long the loop takes to notice
    (detect_s: first drifted request -> debounced trigger) and to heal
    (heal_s: trigger -> journaled retrain episode lands ``promoted``).

    Emits ONE JSON line whose claims are counter-verified from /metrics
    and the loop snapshot: served MAPE before/during/after, requests
    dropped (must be 0 — detection and promotion both ride the live
    serving path), and serving-path compiles after warmup (must be 0 —
    the retrained candidate shares the incumbent's program class and the
    swap warms through the AOT caches off-path)."""
    import urllib.request

    import numpy as np

    from distributed_machine_learning_tpu import chaos, loop, serve
    from distributed_machine_learning_tpu.models import build_model
    from distributed_machine_learning_tpu.serve import export as serve_export
    from distributed_machine_learning_tpu.tune._regression_program import (
        detect_call_convention,
    )

    t0 = time.time()
    seq, feat = 4, 3
    w = np.array([0.7, -0.4, 1.1], np.float32)
    drift_spec = {"at_request": 0, "feature_shift": 2.5,
                  "label_shift": 0.5, "seed": 11}
    config = {"model": "mlp", "hidden_sizes": [8], "seed": 3}

    def make_xy(n, seed, drifted=False):
        r = np.random.default_rng(seed)
        x = r.standard_normal((n, seq, feat)).astype(np.float32)
        y = (x[:, -2:, :] @ w).mean(axis=1, keepdims=True)
        if drifted:
            x, y = chaos.apply_drift(drift_spec, x, y)
        return x.astype(np.float32), y.astype(np.float32)

    def data_fn(kind):
        seeds = {"train": 100, "holdout": 200, "probation": 300}
        return make_xy(48, seeds[kind], drifted=True)

    x, y = make_xy(64, 1)
    probe, _ = detect_call_convention(build_model(config), x[:1])
    variables, _ = loop.fine_tune(
        config, {"params": probe["params"]}, x, y,
        epochs=6, learning_rate=0.05, seed=0,
    )
    root = tempfile.mkdtemp(prefix="bench_loop_")
    inc_dir = os.path.join(root, "incumbent")
    serve_export.write_bundle(inc_dir, {
        "bundle_version": serve_export.BUNDLE_VERSION,
        "config": config, "precision": "f32",
    }, variables)
    srv = serve.PredictionServer(
        serve.load_bundle(inc_dir), port=0, num_replicas=2, max_bucket=16,
    )
    srv.warmup(x[:1])
    host, port = srv.start()
    base = f"http://{host}:{port}"
    drift = loop.DriftMonitor(window=16, z_threshold=4.0, sustain=3)
    srv.metrics.attach_drift(drift)
    ctl = loop.SelfHealingController(
        srv, loop.LoopJournal(os.path.join(root, "loop.json")),
        drift, data_fn, root,
        loop.LoopConfig(retrain_epochs=4, probation_batches=4),
    )

    sent = 0

    def feed(n, seed0, drifted=False):
        nonlocal sent
        apes = []
        for i in range(n):
            xb, yb = make_xy(4, seed0 + i, drifted)
            req = urllib.request.Request(
                f"{base}/predict",
                data=json.dumps({"instances": xb.tolist()}).encode(),
                headers={"Content-Type": "application/json"},
            )
            preds = np.asarray(json.loads(
                urllib.request.urlopen(req).read())["predictions"],
                np.float32)
            sent += 1
            apes.append(float(np.mean(
                np.abs(yb - preds.reshape(yb.shape))
                / (np.abs(yb) + 1e-8)
            )))
        return float(np.mean(apes))

    clean_mape = feed(24, seed0=1000)
    t_drift = time.time()
    drifted_mape = feed(30, seed0=2000, drifted=True)
    trig = drift.snapshot()
    t_trigger = time.time()
    outcome = ctl.poll() or {"state": "never_triggered"}
    t_healed = time.time()
    healed_mape = feed(24, seed0=3000, drifted=True)

    m = srv.handle_metrics()
    snap = ctl.snapshot()
    result = {
        "platform": "cpu",
        "state": outcome.get("state"),
        "detect_s": round(t_trigger - t_drift, 2),
        "heal_s": round(t_healed - t_trigger, 2),
        "recovery_s": round(t_healed - t_drift, 2),
        "clean_mape": round(clean_mape, 4),
        "drifted_mape": round(drifted_mape, 4),
        "healed_mape": round(healed_mape, 4),
        "recovered": bool(healed_mape < drifted_mape),
        "drift_triggers": trig["triggers"],
        "episodes": snap["episodes"],
        "promotions": snap["promotions"],
        "requests": sent,
        "requests_total": m["requests_total"],
        "dropped": sent - m["requests_total"],
        "swaps_total": m["swap"]["swaps_total"],
        "post_swap_new_programs":
            m["compile"]["new_programs_since_warmup"],
        "probation_mape": round(outcome.get("probation_mape", -1.0), 4),
        "incumbent_mape": round(outcome.get("incumbent_mape", -1.0), 4),
        "wall_s": round(time.time() - t0, 1),
    }
    ctl.close()
    drift.close()
    srv.close()
    print(json.dumps(result))


# ---------------------------------------------------------------------------
# Child: head-crash auto-resume timings (ISSUE 18 head_recovery section)


def child_head_recovery() -> None:
    """The durable control plane's recovery cost, timed: a small sweep's
    head is killed mid-journal-append (``chaos.kill_head_at`` —
    ``os._exit(86)`` with the decision durable and its effect not yet
    applied), and ``resume="auto"`` finishes the experiment.

    Emits ONE JSON line with the three recovery phases the runbook's
    counter table points at: ``detect_s`` (spot the uncommitted
    journal), ``replay_s`` (head_start -> replay record: journal parse +
    searcher/scheduler state restore), ``requeue_s`` (replay -> first
    re-dispatch of an in-flight trial).  ``best_matches_control``
    counter-verifies the headline claim: the resumed sweep and an
    uninterrupted control land the identical best trial."""
    from distributed_machine_learning_tpu.tune import crashsim

    root = tempfile.mkdtemp(prefix="bench_head_recovery_")
    spec = dict(num_samples=4, epochs=4, seed=7)
    ctrl = crashsim.control_run(root, "ctrl", **spec)
    out = crashsim.killed_then_resumed(root, "crash", kill_at=6, **spec)
    res = out["result"]
    print(json.dumps({
        "detect_s": out["detect_s"],
        "replay_s": out["replay_s"],
        "requeue_s": out["requeue_s"],
        "resume_total_s": out["resume_total_s"],
        "decisions_journaled": out["journal"]["decisions"],
        "head_incarnations": out["journal"]["head_starts"],
        "best_matches_control":
            bool(res["best_trial"] == ctrl["best_trial"]
                 and res["best_score"] == ctrl["best_score"]),
        "committed": bool(out["journal"]["committed"]),
    }))


# Child: content-store dedup + ref-copy export (ISSUE 20 store section)


def child_store() -> None:
    """The content-addressed store's headline numbers, measured: chunk-
    level dedup on the two write patterns that motivated it (a keep-K
    generation chain where little changes between saves, and a PBT
    population whose exploits copy donor rows), and the ref-copy export
    against the legacy full-rewrite it replaces.

    Emits ONE JSON line: ``bytes_logical``/``bytes_physical`` and their
    ``dedup_ratio`` (< 0.5 is the acceptance bar), ``dedup_hits``,
    save walls for the CAS vs the pre-CAS (``DML_STORE_CKPT=0``) chunk
    writer on the same chain, ref-copy vs full-rewrite export walls, and
    ``export_param_blob_writes`` — which must be 0: exporting a committed
    generation moves metadata, not parameter bytes."""
    import numpy as np

    from distributed_machine_learning_tpu import store
    from distributed_machine_learning_tpu.ckpt import format as fmt

    # Small pieces so the modest bench arrays split into many blobs and
    # the row-aligned dedup has boundaries to land on.
    os.environ["DML_STORE_CHUNK_BYTES"] = "4096"
    os.environ.pop("DML_STORE_CKPT", None)
    root = tempfile.mkdtemp(prefix="bench_store_")
    rng = np.random.default_rng(0)

    def chain_trees(n=4):
        w = rng.standard_normal((1024, 64)).astype(np.float32)
        b = rng.standard_normal(64).astype(np.float32)
        out = []
        for gen in range(n):
            w = w.copy()
            w[gen % w.shape[0]] += 1.0  # one-row update per generation
            out.append({"params": {"w": w, "b": b}})
        return out

    # -- keep-K generation chain, CAS on --------------------------------
    trees = chain_trees()
    before = store.get_metrics().snapshot()
    t0 = time.time()
    for i, tree in enumerate(trees):
        fmt.save_sharded(os.path.join(root, "cas", f"gen_{i + 1:06d}"),
                         tree)
    cas_save_s = time.time() - t0
    chain = store.get_metrics().delta_since(before)

    # -- PBT population: 3 exploits copying donor rows ------------------
    pop = rng.standard_normal((8, 32, 512)).astype(np.float32)
    before = store.get_metrics().snapshot()
    for step, (dst, src) in enumerate([(3, 0), (5, 1), (7, 0)]):
        pop = pop.copy()
        pop[dst] = pop[src]  # exploit: donor member's rows, bit for bit
        fmt.save_sharded(
            os.path.join(root, "pbt", f"gen_{step + 1:06d}"),
            {"pop": pop},
        )
    pbt = store.get_metrics().delta_since(before)

    # -- export: ref-copy vs the legacy full rewrite --------------------
    last = os.path.join(root, "cas", f"gen_{len(trees):06d}")
    before = store.get_metrics().snapshot()
    t0 = time.time()
    copied = fmt.ref_copy_subtree(last, os.path.join(root, "export.cas"))
    refcopy_s = time.time() - t0
    dexp = store.get_metrics().delta_since(before)
    # puts minus the ref-copy's own manifest blob = param-chunk writes.
    param_blob_writes = int(dexp["puts"]) - 1

    os.environ["DML_STORE_CKPT"] = "0"
    try:
        t0 = time.time()
        loaded = fmt.load_sharded(last)
        fmt.save_sharded(os.path.join(root, "export_legacy"), loaded)
        legacy_export_s = time.time() - t0
        t0 = time.time()
        for i, tree in enumerate(trees):
            fmt.save_sharded(
                os.path.join(root, "legacy", f"gen_{i + 1:06d}"), tree
            )
        legacy_save_s = time.time() - t0
    finally:
        os.environ.pop("DML_STORE_CKPT", None)

    # Chain + PBT + export together: the dedup the store actually banked.
    logical = int(chain["bytes_logical"] + pbt["bytes_logical"])
    physical = int(chain["bytes_physical"] + pbt["bytes_physical"])
    print(json.dumps({
        "bytes_logical": logical,
        "bytes_physical": physical,
        "dedup_ratio": round(physical / logical, 4) if logical else 1.0,
        "dedup_hits": int(chain["dedup_hits"] + pbt["dedup_hits"]),
        "pbt_dedup_hits": int(pbt["dedup_hits"]),
        "pass_half": bool(logical and physical < 0.5 * logical),
        "cas_save_s": round(cas_save_s, 3),
        "legacy_save_s": round(legacy_save_s, 3),
        "export_refcopy_s": round(refcopy_s, 4),
        "export_legacy_s": round(legacy_export_s, 4),
        "export_param_blob_writes": param_blob_writes,
        "export_chunks": copied["chunks"] if copied else None,
    }))


# ---------------------------------------------------------------------------
# Parent


def main() -> int:
    """Run the suite child once and relay its JSON line.  Never imports
    jax: the child needs the chip.  Non-zero (and no line) when the child
    failed — including when it found no TPU."""
    log = lambda m: print(f"[bench] {m}", file=sys.stderr, flush=True)
    hb_path = os.path.join(
        tempfile.gettempdir(), f"bench_suite_hb_{os.getpid()}"
    )
    t0 = time.time()
    rc, out, err = _run_child_monitored(
        ["--child", "suite", "full"],
        dict(os.environ, DML_BENCH_HEARTBEAT_PATH=hb_path),
        SUITE_TIMEOUT_S, hb_path, HEARTBEAT_STALE_S,
    )
    _unlink_quiet(hb_path)
    suite = _parse_result(out) if rc == 0 else None
    if suite is None:
        log(f"suite child failed rc={rc}; tail: {err[-800:]}")
        return rc or 1
    suite["total_s"] = round(time.time() - t0, 1)
    print(json.dumps(suite), flush=True)
    return 0


if __name__ == "__main__":
    argv = sys.argv[1:]
    if argv and argv[0] == "--child":
        kind = argv[1]
        if kind == "serve_soak":
            child_serve_soak()
        elif kind == "streaming":
            child_streaming()
        elif kind == "online_loop":
            child_online_loop()
        elif kind == "head_recovery":
            child_head_recovery()
        elif kind == "store":
            child_store()
        elif kind == "flagship":
            child_flagship()
        elif kind == "sharded_flagship":
            child_sharded_flagship()
        elif kind == "multihost":
            child_multihost(int(argv[2]), int(argv[3]), argv[4])
        elif kind == "suite":
            _require_tpu()
            child_suite(argv[2] if len(argv) > 2 else "full")
        elif kind == "ours":
            child_ours(
                FULL if argv[2] == "full" else SMALL,
                argv[3] if len(argv) > 3 else "float32",
            )
        elif kind == "torch":
            child_torch(FULL if argv[2] == "full" else SMALL)
        elif kind == "quality":
            child_quality(FULL if argv[2] == "full" else SMALL)
        elif kind == "pbt_quality":
            child_pbt_quality(FULL if argv[2] == "full" else SMALL)
        elif kind == "torch_quality":
            child_torch_quality(FULL if argv[2] == "full" else SMALL)
        elif kind == "variant":
            child_variant(argv[2], argv[3])
        elif kind == "_test_stall":
            # Test-only: beat once, then hang — a real-process probe of the
            # monitored parent's staleness kill (tests/test_bench.py).
            hb = os.environ.get("DML_BENCH_HEARTBEAT_PATH")
            if hb:
                with open(hb, "w") as f:
                    f.write(repr(time.time()))
            time.sleep(600)
        else:
            raise SystemExit(f"unknown child kind {kind!r}")
    elif argv and argv[0] == "--variant":
        if len(argv) < 2:
            raise SystemExit(
                f"--variant needs a name: {sorted(VARIANT_SCALES)}"
            )
        sys.exit(run_variant(argv[1]))
    else:
        sys.exit(main())
