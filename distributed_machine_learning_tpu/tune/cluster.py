"""Multi-host trial execution: driver <-> per-host worker supervisors.

The TPU-native replacement for the reference's delegated Ray Core layer
(SURVEY.md §2b D4, §5 "distributed communication backend"): Ray's gRPC control
plane + object store scheduled trial actors across a cluster
(`ray-tune-hpo-regression.py:469-478` never sees it). Here the control plane
is explicit and minimal:

* ``serve_worker`` — one supervisor process per TPU host. It owns that host's
  ``jax.devices()``, runs trials in device-pinned threads (same execution model
  as the single-host executor), streams per-epoch metrics to the driver, and
  applies the driver's continue/stop decisions. Trial *data* never moves over
  this plane — datasets load host-locally and checkpoints go to shared storage
  (GCS on a real pod) — only configs, metrics, and decisions do, which is why
  plain length-prefixed frames over TCP (DCN between hosts) are enough.
* ``run_distributed`` — the driver loop. Scheduler (ASHA/PBT/...), searcher,
  and experiment store are the same single-threaded components as
  ``tune.run``; only the executor is remote. Worker death (preemption) is
  detected as a connection drop: the worker's running trials are requeued to
  surviving workers, restoring from their latest shared-storage checkpoint,
  within the per-trial ``max_failures`` budget (SURVEY.md §5: promoted to
  first-class because TPU pods are preemptible).

Trainables cross hosts **by name** (``"module:function"``) or by pickle-by-
reference — the worker imports the module host-side. This mirrors how real
pods run (same container image everywhere) and keeps arbitrary bytes off the
control plane.

Wire format: 8-byte big-endian length + [32-byte HMAC-SHA256 when a shared
secret is configured] + pickle. Single driver per worker.

Security model: the control plane carries pickled frames, so anyone who can
complete a frame exchange can execute code on the worker. Defenses, in order:
(1) the supervisor binds loopback by default — exposing it on a routable
interface is an explicit operator choice; (2) setting ``DML_CLUSTER_SECRET``
(env var, same value on driver and workers — how real pods share it: baked
into the job spec) MACs every frame, and frames failing verification are
dropped *before* unpickling, closing the connection; (3) the expected
deployment is a private pod network (DCN between TPU hosts), which is the
trusted-network assumption this plane inherits from the reference's Ray
cluster (`ray-tune-hpo-regression.py` never configures Ray auth either).
"""

from __future__ import annotations

import hashlib
import hmac as hmac_lib
import importlib
import os
import pickle
import queue
import socket
import struct
import subprocess
import sys
import threading
import time
import traceback
import uuid
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

from distributed_machine_learning_tpu.analysis.locks import named_lock
from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib
from distributed_machine_learning_tpu.tune.experiment import (
    ExperimentAnalysis,
    ExperimentStore,
)
from distributed_machine_learning_tpu.tune._driver import (
    TrialLifecycle,
    scheduler_debug_block,
)
from distributed_machine_learning_tpu.tune import journal as journal_lib
from distributed_machine_learning_tpu.tune.schedulers.base import (
    FIFOScheduler,
    TrialScheduler,
)
from distributed_machine_learning_tpu.tune.search.base import (
    RandomSearch,
    Searcher,
    maybe_warm_start,
)
from distributed_machine_learning_tpu.tune.search_space import SearchSpace
from distributed_machine_learning_tpu.tune.session import (
    PauseTrial,
    Session,
    StopTrial,
    set_session,
)
from distributed_machine_learning_tpu.tune.trial import Trial, TrialStatus

_LEN = struct.Struct(">Q")
_MAC_SIZE = 32  # HMAC-SHA256


def _cluster_secret() -> Optional[bytes]:
    s = os.environ.get("DML_CLUSTER_SECRET")
    return s.encode() if s else None


def _is_loopback(host: str) -> bool:
    """Whether ``host`` stays on this machine — the one predicate behind
    every no-secret pickle-trust warning, so the sites can't drift."""
    return host in ("127.0.0.1", "localhost", "::1")


# --------------------------------------------------------------------------
# framing
# --------------------------------------------------------------------------


def _send(
    sock: socket.socket,
    lock: threading.Lock,
    msg: Dict[str, Any],
    secret: Optional[bytes] = None,
):
    payload = pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)
    if secret:
        mac = hmac_lib.new(secret, payload, hashlib.sha256).digest()
        payload = mac + payload
    with lock:
        sock.sendall(_LEN.pack(len(payload)) + payload)


def _recv(
    sock: socket.socket, secret: Optional[bytes] = None
) -> Optional[Dict[str, Any]]:
    header = _recv_exact(sock, _LEN.size)
    if header is None:
        return None
    (n,) = _LEN.unpack(header)
    payload = _recv_exact(sock, n)
    if payload is None:
        return None
    if secret:
        # Verify BEFORE unpickling — an unauthenticated frame must never
        # reach pickle.loads (that is the code-execution boundary).
        if len(payload) < _MAC_SIZE:
            return None
        mac, payload = payload[:_MAC_SIZE], payload[_MAC_SIZE:]
        expect = hmac_lib.new(secret, payload, hashlib.sha256).digest()
        if not hmac_lib.compare_digest(mac, expect):
            print("[cluster] dropping frame with bad MAC; closing connection",
                  flush=True)
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            return None
    return pickle.loads(payload)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = b""
    while len(buf) < n:
        try:
            chunk = sock.recv(n - len(buf))
        except OSError:
            return None
        if not chunk:
            return None
        buf += chunk
    return buf


def resolve_trainable(spec: Union[str, Callable]) -> Callable:
    """Resolve ``"module:function"`` (or ``module.function``) to a callable."""
    if callable(spec):
        return spec
    if ":" in spec:
        mod_name, attr = spec.split(":", 1)
    else:
        mod_name, _, attr = spec.rpartition(".")
    if not mod_name:
        raise ValueError(f"Cannot resolve trainable spec {spec!r}")
    obj = importlib.import_module(mod_name)
    for part in attr.split("."):
        obj = getattr(obj, part)
    if not callable(obj):
        raise TypeError(f"{spec!r} resolved to non-callable {obj!r}")
    return obj


# --------------------------------------------------------------------------
# worker supervisor (one per TPU host)
# --------------------------------------------------------------------------


class _WorkerState:
    def __init__(self, sock: socket.socket, secret: Optional[bytes] = None):
        self.sock = sock
        self.secret = secret
        self.send_lock = named_lock("cluster.worker.send")
        # (trial_id, incarnation) -> decision queue; incarnation-keyed so a
        # fenced incarnation and its redispatched replacement on this same
        # worker never swallow each other's decisions.
        self.decisions: Dict[Tuple[str, int], "queue.Queue[str]"] = {}
        self.dec_lock = named_lock("cluster.worker.decisions")
        # program key -> reply queue for in-flight compile-artifact fetches
        # (the trial thread blocks on it; the recv loop answers).
        self.artifact_replies: Dict[str, "queue.Queue"] = {}
        self.art_lock = named_lock("cluster.worker.artifacts")
        # (trial_id, incarnation) -> live gang-member child handle
        # (multihost/spawn.py) — the gang_abort/teardown kill target.
        self.gang_children: Dict[Tuple[str, int], Any] = {}
        self.gang_lock = named_lock("cluster.worker.gangs")


# Program keys this worker PROCESS has already fetched-or-compiled: the
# first trial of a shape class talks to the origin; its siblings on this
# host ride the local jit/persistent caches without another round trip.
_SEEN_PROGRAM_KEYS: set = set()
_SEEN_KEYS_LOCK = named_lock("cluster.seen_keys")

_ARTIFACT_FETCH_TIMEOUT_S = float(
    os.environ.get("DML_ARTIFACT_FETCH_TIMEOUT_S", "10.0")
)


def _fetch_artifacts(state: _WorkerState, key: str) -> bool:
    """Ask the head for compile artifacts under ``key`` and install them
    into this process's compile-cache directory.  EVERY failure — injected
    fault, timeout, dead driver, bad payload — degrades to a local compile
    (counted ``fetch_fallbacks``); a fetch can slow a trial start, never
    fail a trial."""
    from distributed_machine_learning_tpu import chaos
    from distributed_machine_learning_tpu import compilecache as cc

    counters = cc.get_counters()
    q: "queue.Queue" = queue.Queue()
    try:
        plan = chaos.active_plan()
        if plan is not None:
            plan.on_artifact_fetch(key)
        with state.art_lock:
            state.artifact_replies[key] = q
        _send(state.sock, state.send_lock,
              {"type": "artifact_get", "key": key}, state.secret)
        files = q.get(timeout=_ARTIFACT_FETCH_TIMEOUT_S)
    except Exception as exc:  # noqa: BLE001 - fall back to local compile
        counters.add("fetch_fallbacks")
        print(f"[worker] artifact fetch for {key} failed ({exc!r}); "
              f"compiling locally", flush=True)
        return False
    finally:
        with state.art_lock:
            state.artifact_replies.pop(key, None)
    cache = cc.cache_dir()
    if files and cache:
        cc.install_artifacts(cache, files)
        counters.add("fetch_hits")
        return True
    counters.add("fetch_misses")
    return False


def _publish_artifacts(state: _WorkerState, key: str,
                       pre_files: set) -> None:
    """Diff the compile-cache directory against its pre-trial snapshot and
    publish what THIS compile produced to the head's artifact registry."""
    from distributed_machine_learning_tpu import compilecache as cc

    cache = cc.cache_dir()
    if not cache:
        return
    new = cc.snapshot_cache_dir(cache) - pre_files
    if not new:
        return
    files = cc.pack_artifacts(cache, new)
    if not files:
        return
    try:
        _send(state.sock, state.send_lock,
              {"type": "artifact_put", "key": key, "files": files},
              state.secret)
        cc.get_counters().add("publishes")
    except OSError:
        pass  # driver gone; nothing to publish to


def _worker_run_trial(state: _WorkerState, msg: Dict[str, Any], devices: List):
    from distributed_machine_learning_tpu import obs

    trial_id = msg["trial_id"]
    # Join the head's trace: the dispatch frame carries the trace id, the
    # head-side dispatch span to parent under, the shared trace dir, and
    # the dump destination.  Idempotent re-configuration per trial — a
    # supervisor serves many trials (and many experiments) in one process.
    obs.configure_from_frame(msg.get("obs"), label=f"worker{os.getpid()}")
    # Decision routing is keyed by (trial_id, incarnation): after a fence +
    # requeue the driver may redispatch the SAME trial to this same worker
    # while the fenced incarnation still drains — their decisions must
    # never cross.
    incarnation = int(msg.get("incarnation", 0))
    dec_key = (trial_id, incarnation)
    dq: "queue.Queue[str]" = queue.Queue()
    with state.dec_lock:
        state.decisions[dec_key] = dq

    trial = Trial(trial_id=trial_id, config=dict(msg["config"]))
    trial.restore_path = msg.get("restore_path")
    ckpt_dir = msg.get("checkpoint_dir")
    ckpt_format = msg.get("checkpoint_format", "msgpack")
    iteration = [int(msg.get("start_iteration", 0))]

    # Compile-artifact origin (compile-once tentpole): the FIRST trial of a
    # program key on this host asks the head for the key's artifacts before
    # compiling locally; if it does compile, the first report boundary
    # (compiles complete by then) diffs the cache dir and publishes the new
    # entries.  Siblings on this host skip the round trip entirely.
    publish_key = [None]  # set -> publish at the first report boundary
    pre_files: set = set()
    if msg.get("artifact_origin"):
        from distributed_machine_learning_tpu import compilecache as cc

        key = cc.program_key(trial.config)
        with _SEEN_KEYS_LOCK:
            first_here = key not in _SEEN_PROGRAM_KEYS
            _SEEN_PROGRAM_KEYS.add(key)
        if first_here:
            pre_files = cc.snapshot_cache_dir(cc.cache_dir())
            if not _fetch_artifacts(state, key):
                publish_key[0] = key

    def report_fn(metrics: Dict[str, Any], checkpoint) -> str:
        if publish_key[0] is not None:
            # First report of the compiling incarnation: everything this
            # program needed is compiled; ship the fresh cache entries.
            _publish_artifacts(state, publish_key[0], pre_files)
            publish_key[0] = None
        # Chaos hooks (plan activated from DML_CHAOS_PLAN on this worker —
        # supervisors are separate processes): a hang sleeps HERE so the
        # driver-side progress watchdog sees real silence from a real
        # worker; a crash follows the ordinary error-frame path.
        from distributed_machine_learning_tpu import chaos

        plan = chaos.active_plan()
        if plan is not None:
            plan.maybe_hang_dispatch(trial_id, iteration[0] + 1)
            plan.maybe_crash_trial(trial_id, iteration[0] + 1)
        iteration[0] += 1
        ckpt_path = None
        if checkpoint is not None and ckpt_dir:
            # Storage-aware: ckpt_dir may be a local/shared filesystem path
            # or gs:// — the driver picked it (checkpoint_storage) and it
            # must be reachable from every worker host; workers just write.
            ckpt_path = ckpt_lib.checkpoint_path(
                ckpt_dir, iteration[0], ckpt_format
            )
            ckpt_lib.save_checkpoint(ckpt_path, checkpoint)
        _send(
            state.sock,
            state.send_lock,
            {
                "type": "result",
                "trial_id": trial_id,
                "incarnation": incarnation,
                "metrics": metrics,
                "checkpoint_path": ckpt_path,
            },
            state.secret,
        )
        return dq.get()

    def heartbeat_fn():
        # tune.heartbeat() inside a long epoch: piggyback a per-trial
        # progress frame on the control plane so the driver's watchdog
        # never misreads slow-but-alive as wedged.
        try:
            _send(
                state.sock,
                state.send_lock,
                {"type": "trial_beat", "trial_id": trial_id,
                 "incarnation": incarnation},
                state.secret,
            )
        except OSError:
            pass  # driver gone; the terminal path handles it

    def checkpoint_loader():
        if trial.restore_path:
            # Same corruption fallback as the local executors: a requeued
            # trial whose restore target was damaged restores the newest
            # checksum-valid generation instead of dying again.
            tree, used, used_it = ckpt_lib.load_checkpoint_with_fallback(
                trial.restore_path, ckpt_dir,
            )
            if used != trial.restore_path:
                print(
                    f"[worker] {trial_id}: restore fell back "
                    f"{trial.restore_path} -> {used} (it={used_it})",
                    flush=True,
                )
            return tree
        return None

    # The terminal frame is sent only AFTER session/decision-map cleanup: the
    # driver frees this trial's slot the moment it processes the frame, and a
    # redispatch into a slot whose previous thread is still tearing down
    # could briefly double-book the device (ADVICE r1).
    terminal: Dict[str, Any]
    try:
        trainable = resolve_trainable(msg["trainable"])
        set_session(Session(trial, report_fn, checkpoint_loader, devices,
                            heartbeat_fn=heartbeat_fn))
        import jax

        with jax.default_device(devices[0]), obs.span(
            "trial", {"trial_id": trial_id, "incarnation": incarnation}
        ):
            trainable(dict(trial.config))
        terminal = {"type": "complete", "trial_id": trial_id,
                    "incarnation": incarnation}
    except (StopTrial, PauseTrial):
        terminal = {"type": "complete", "trial_id": trial_id,
                    "incarnation": incarnation}
    except BaseException:  # noqa: BLE001 - ship the traceback to the driver
        terminal = {
            "type": "error",
            "trial_id": trial_id,
            "incarnation": incarnation,
            "traceback": traceback.format_exc(),
        }
    finally:
        set_session(None)
        obs.flush()
        # Head-node aggregation frame: this worker process's whole
        # registry snapshot rides the terminal frame; the head keeps the
        # latest per worker and sums across workers at experiment end.
        terminal["obs_counters"] = obs.get_registry().scalar_snapshot()
        with state.dec_lock:
            # The same-incarnation guard stays even though the terminal frame
            # now follows cleanup: a worker-death requeue on the driver can
            # still race a slow teardown here.
            if state.decisions.get(dec_key) is dq:
                del state.decisions[dec_key]
        try:
            _send(state.sock, state.send_lock, terminal, state.secret)
        except OSError:
            pass  # driver went away; its reader already flagged the death


def _worker_run_gang_member(state: _WorkerState, msg: Dict[str, Any],
                            devices: List):
    """Run ONE member of a process-spanning gang trial (multihost/):
    spawn a fresh gang-child subprocess (jax.distributed must initialize
    before the backend — this supervisor's is long gone) and relay its
    frames up the control plane.  Only the coordinator member (gang
    process 0) produces result/beat/complete frames; every other member
    reports only its bootstrap join and its terminal state."""
    import cloudpickle

    from distributed_machine_learning_tpu import obs
    from distributed_machine_learning_tpu.multihost.bootstrap import GangSpec
    from distributed_machine_learning_tpu.multihost.spawn import (
        GangChildHandle,
        member_child_env,
    )

    trial_id = msg["trial_id"]
    incarnation = int(msg.get("incarnation", 0))
    process_id = int(msg["process_id"])
    gang_id = msg["gang_id"]
    obs.configure_from_frame(msg.get("obs"), label=f"worker{os.getpid()}")
    dec_key = (trial_id, incarnation)
    dq: Optional["queue.Queue[str]"] = None
    if process_id == 0:
        dq = queue.Queue()
        with state.dec_lock:
            state.decisions[dec_key] = dq

    # Compile-artifact origin, gang edition: the key folds the PROCESS
    # TOPOLOGY (compilecache.gang_program_key) — reshaping the gang splits
    # it; the second same-topology gang fetches instead of compiling.
    # Fetch installs into this host's persistent cache dir, which the
    # child inherits below; publish happens at the first result boundary.
    publish_key = [None]
    pre_files: set = set()
    gang_key = None
    if msg.get("artifact_origin"):
        from distributed_machine_learning_tpu import compilecache as cc

        n = int(msg["num_processes"])
        gang_key = cc.gang_program_key(
            dict(msg["config"]),
            process_count=n,
            local_device_counts=[int(msg["local_device_count"])] * n,
        )
        with _SEEN_KEYS_LOCK:
            first_here = gang_key not in _SEEN_PROGRAM_KEYS
            _SEEN_PROGRAM_KEYS.add(gang_key)
        if first_here:
            pre_files = cc.snapshot_cache_dir(cc.cache_dir())
            if not _fetch_artifacts(state, gang_key):
                publish_key[0] = gang_key

    # Test/chaos knob: stretch THIS member's spawn the way a straggler
    # host does (same pattern as DML_CLUSTER_STARTUP_SLEEP_S) — how the
    # head's gang-bootstrap deadline + absent-process flight dump are
    # exercised deterministically.
    _spawn_hold = float(os.environ.get("DML_GANG_SPAWN_HOLD_S", "0") or 0.0)
    if _spawn_hold > 0:
        time.sleep(_spawn_hold)

    spec = GangSpec(
        gang_id=gang_id,
        coordinator_address=msg["coordinator_address"],
        num_processes=int(msg["num_processes"]),
        process_id=process_id,
        local_device_count=int(msg["local_device_count"]),
        join_deadline_s=float(msg.get("join_deadline_s", 120.0)),
    )
    child_env = member_child_env(
        spec, devices=devices,
        platform=getattr(devices[0], "platform", None) if devices else None,
    )
    from distributed_machine_learning_tpu import compilecache as _cc

    if _cc.cache_dir():
        # The child's compiles must land in THIS host's persistent cache
        # so the origin fetch/publish diff sees them.
        child_env["JAX_COMPILATION_CACHE_DIR"] = _cc.cache_dir()

    terminal: Dict[str, Any]
    handle = None
    try:
        trainable = resolve_trainable(msg["trainable"])
        init_msg = {
            "trial_id": trial_id,
            "incarnation": incarnation,
            "config": dict(msg["config"]),
            "trainable": cloudpickle.dumps(trainable),
            "restore_path": msg.get("restore_path"),
            "checkpoint_dir": msg.get("checkpoint_dir"),
            "checkpoint_format": msg.get("checkpoint_format", "sharded"),
            "start_iteration": int(msg.get("start_iteration", 0)),
            "obs": msg.get("obs"),
        }
        handle = GangChildHandle(spec, init_msg, devices=devices,
                                 env=child_env)
        with state.gang_lock:
            state.gang_children[dec_key] = handle
        saw_terminal = None
        while True:
            try:
                frame = handle.read()
            except EOFError:
                break
            kind = frame[0]
            if kind == "joined":
                _send(state.sock, state.send_lock, {
                    "type": "gang_joined",
                    "trial_id": trial_id,
                    "incarnation": incarnation,
                    "gang_id": gang_id,
                    "process_id": process_id,
                }, state.secret)
            elif kind == "result":
                if publish_key[0] is not None:
                    # First report boundary: the child's compiles are in
                    # the shared cache dir; ship the fresh entries.
                    _publish_artifacts(state, publish_key[0], pre_files)
                    publish_key[0] = None
                _send(state.sock, state.send_lock, {
                    "type": "result",
                    "trial_id": trial_id,
                    "incarnation": incarnation,
                    "metrics": frame[1],
                    "checkpoint_path": frame[2],
                }, state.secret)
                handle.send_decision(dq.get())
            elif kind == "beat":
                _send(state.sock, state.send_lock, {
                    "type": "trial_beat", "trial_id": trial_id,
                    "incarnation": incarnation,
                }, state.secret)
            elif kind in ("complete", "error"):
                saw_terminal = frame
                break
        if saw_terminal is None:
            # Child died without a terminal frame: SIGKILL from a gang
            # abort, a chaos kill_process_at, or a real preemption.
            rc = handle.wait(timeout=5.0)
            saw_terminal = (
                "error",
                f"gang member {process_id} of {gang_id} died without a "
                f"terminal frame (rc={rc})",
            )
        if process_id == 0:
            if saw_terminal[0] == "complete":
                terminal = {"type": "complete", "trial_id": trial_id,
                            "incarnation": incarnation}
            else:
                terminal = {
                    "type": "error",
                    "trial_id": trial_id,
                    "incarnation": incarnation,
                    "traceback": saw_terminal[1],
                }
        else:
            terminal = {
                "type": "gang_member_done",
                "trial_id": trial_id,
                "incarnation": incarnation,
                "gang_id": gang_id,
                "process_id": process_id,
                "ok": saw_terminal[0] == "complete",
            }
            if saw_terminal[0] != "complete":
                terminal["traceback"] = saw_terminal[1]
    except BaseException:  # noqa: BLE001 - ship the traceback to the driver
        tb = traceback.format_exc()
        if process_id == 0:
            terminal = {"type": "error", "trial_id": trial_id,
                        "incarnation": incarnation, "traceback": tb}
        else:
            terminal = {
                "type": "gang_member_done", "trial_id": trial_id,
                "incarnation": incarnation, "gang_id": gang_id,
                "process_id": process_id, "ok": False, "traceback": tb,
            }
    finally:
        if handle is not None and handle.wait(timeout=2.0) is None:
            handle.kill()  # wedged child (abort path): reap hard
        obs.flush()
        terminal["obs_counters"] = obs.get_registry().scalar_snapshot()
        with state.gang_lock:
            if state.gang_children.get(dec_key) is not None:
                del state.gang_children[dec_key]
        with state.dec_lock:
            if dq is not None and state.decisions.get(dec_key) is dq:
                del state.decisions[dec_key]
        try:
            _send(state.sock, state.send_lock, terminal, state.secret)
        except OSError:
            pass  # driver went away; its reader already flagged the death


def serve_worker(
    host: str = "127.0.0.1",
    port: int = 0,
    slots: Optional[int] = None,
    ready_file: Optional[str] = None,
    secret: Optional[bytes] = None,
) -> None:
    """Run a host supervisor until the driver sends shutdown (blocking).

    ``slots`` defaults to the host's jax device count — one trial per core,
    the TPU analogue of the reference's one-trial-per-GPU placement
    (`ray-tune-hpo-regression.py:475`).
    """
    # Bind and announce readiness BEFORE importing jax: jax cold-import takes
    # tens of seconds, and the driver's connect queues in the backlog while
    # device enumeration finishes (it blocks on the hello frame, not connect).
    startup_t0 = time.monotonic()
    secret = secret if secret is not None else _cluster_secret()
    if not _is_loopback(host) and not secret:
        print(
            "[cluster] WARNING: supervisor bound to a routable interface "
            f"({host}) without DML_CLUSTER_SECRET — anyone who can reach the "
            "port can run code on this host (pickled control frames). Set a "
            "shared secret or keep the bind on loopback/private networks.",
            flush=True,
        )
    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    server.bind((host, port))
    server.listen(8)
    actual_port = server.getsockname()[1]
    print(f"LISTENING {host}:{actual_port}", flush=True)
    if ready_file:
        with open(ready_file, "w") as f:
            f.write(f"{host}:{actual_port}\n")

    # Test/chaos knob: stretch this worker's startup the way a loaded host
    # does (the jax import below is the real cost; the sleep stands in for
    # it deterministically in the loaded-host regression test).
    _startup_sleep = float(
        os.environ.get("DML_CLUSTER_STARTUP_SLEEP_S", "0") or 0.0
    )
    if _startup_sleep > 0:
        time.sleep(_startup_sleep)

    import jax

    from distributed_machine_learning_tpu import chaos
    from distributed_machine_learning_tpu import compilecache as _cc

    # Supervisors are separate processes — a chaos harness reaches them
    # through the spawn environment, not chaos.activate() in the driver.
    if chaos.activate_from_env() is not None:
        print("[worker] chaos plan activated from environment", flush=True)

    # Workers own compile amortization the way tune.run does: the host's
    # persistent cache catches repeats across trials AND across sweeps
    # ($JAX_COMPILATION_CACHE_DIR scopes it per host), and the artifact origin
    # fetches/publishes entries for it by program key.
    _cc.enable_persistent_cache()

    devices = list(jax.devices())
    slots = slots or len(devices)
    # MEASURED spawn time (bind + jax import + device enum + cache attach):
    # the driver scales per-trial first-beat grace from it, because the
    # same host load that stretched THIS stretches every trial's cold
    # start (startup_scaled_grace; the PR 9/11 full-run flake).
    startup_s = time.monotonic() - startup_t0

    debug = bool(os.environ.get("DML_CLUSTER_DEBUG"))

    def dbg(msg: str):
        if debug:
            print(f"[worker] {msg}", flush=True)

    # Head-incarnation fencing watermark.  It OUTLIVES individual driver
    # connections: after a head crash the resumed head (incarnation N+1)
    # may connect while the dead head's ghost — a partitioned, not actually
    # dead, incarnation N whose frames heal late — still speaks.  Frames
    # stamped with an incarnation below the highest seen are dropped, the
    # exact mirror of per-trial zombie fencing.  Surfaced via the worker's
    # obs registry so the head's cluster aggregation reports
    # ``fenced_head_frames``.
    from distributed_machine_learning_tpu import obs as _obs

    head_watermark: Dict[str, Any] = {
        "experiment": None, "incarnation": 0, "fenced_head_frames": 0,
    }
    _obs.get_registry().register_family(
        "head_fencing",
        lambda: {
            "head_incarnation": head_watermark["incarnation"],
            "fenced_head_frames": head_watermark["fenced_head_frames"],
        },
    )

    while True:
        sock, peer = server.accept()
        dbg(f"accepted driver {peer}")
        shutdown = _serve_driver_connection(
            sock, secret, devices, slots, dbg, startup_s=startup_s,
            head_watermark=head_watermark,
        )
        if shutdown:
            break
    server.close()


def _serve_driver_connection(
    sock: socket.socket,
    secret: Optional[bytes],
    devices: List,
    slots: int,
    dbg: Callable[[str], None],
    startup_s: float = 0.0,
    head_watermark: Optional[Dict[str, int]] = None,
) -> bool:
    """Serve one driver over an established socket (either direction: a
    connection the supervisor accepted, or one ``join_driver`` dialed).
    Sends the hello, runs trials until driver EOF or shutdown; returns
    True when the driver requested shutdown."""
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    state = _WorkerState(sock, secret)
    _send(
        sock,
        state.send_lock,
        {
            "type": "hello",
            "slots": slots,
            "host": socket.gethostname(),
            "num_devices": len(devices),
            # Measured spawn->ready seconds: the driver's load signal for
            # scaling first-beat grace (startup_scaled_grace).
            "startup_s": round(float(startup_s), 3),
        },
        secret,
    )
    # Liveness heartbeats, piggybacked on the control plane: the driver's
    # lease expiry measures the gap between ANY frames from this worker, so
    # an idle-but-healthy supervisor must keep speaking.  A worker whose
    # supervisor process wedges entirely stops beating (the point); a
    # worker with one hung trial thread keeps beating (per-trial progress
    # watchdogs on the driver catch that case).
    hb_interval = float(os.environ.get("DML_CLUSTER_HEARTBEAT_S", "2.0"))
    stop_hb = threading.Event()

    def _heartbeat_loop():
        while not stop_hb.wait(hb_interval):
            try:
                with state.dec_lock:
                    running = sorted({k[0] for k in state.decisions})
                _send(
                    sock,
                    state.send_lock,
                    {"type": "heartbeat", "running": running},
                    secret,
                )
            except OSError:
                return  # connection gone; the main recv loop notices too

    threading.Thread(
        target=_heartbeat_loop, name="worker-heartbeat", daemon=True
    ).start()
    shutdown = False
    while True:
        msg = _recv(sock, secret)
        if msg is None:
            dbg("driver EOF")
            break  # driver went away
        mtype = msg.get("type")
        dbg(f"recv {mtype} {msg.get('trial_id', '')}")
        if head_watermark is not None:
            hinc = msg.get("head_incarnation")
            if hinc is not None:
                # The watermark is scoped PER EXPERIMENT: incarnations only
                # order heads of the same experiment (a fresh experiment on
                # this pool legitimately starts back at incarnation 1).
                hexp = msg.get("head_experiment")
                if hexp != head_watermark.get("experiment"):
                    head_watermark["experiment"] = hexp
                    head_watermark["incarnation"] = 0
                hinc = int(hinc)
                if hinc < head_watermark["incarnation"]:
                    # Ghost head: a lower incarnation than the highest this
                    # worker has served means the sending head already died
                    # and was replaced — its late/healed frames must not
                    # dispatch work or answer decisions.
                    head_watermark["fenced_head_frames"] += 1
                    dbg(
                        f"fenced head frame {mtype} (incarnation {hinc} < "
                        f"{head_watermark['incarnation']})"
                    )
                    continue
                head_watermark["incarnation"] = hinc
        if mtype == "run_trial":
            # Round-robin device assignment by slot index keeps concurrent
            # trials on distinct cores.  A mesh trial (num_devices > 1)
            # takes a contiguous GROUP of local devices — contiguous
            # enumeration order is ICI-adjacent on TPU (same preference as
            # DeviceManager._pick_adjacent); start workers with
            # slots = len(devices) // num_devices so groups never overlap.
            slot = int(msg.get("slot", 0))
            n = max(int(msg.get("num_devices", 1)), 1)
            if n <= 1:
                dev = [devices[slot % len(devices)]]
            else:
                groups = max(len(devices) // n, 1)
                g = slot % groups
                dev = devices[g * n:(g + 1) * n] or devices[:n]
            threading.Thread(
                target=_worker_run_trial,
                args=(state, msg, dev),
                name=f"trial-{msg['trial_id']}",
                daemon=True,
            ).start()
        elif mtype == "gang_prepare":
            # Reserve a coordinator port for a gang this host will anchor
            # (member 0 binds it inside jax.distributed.initialize).
            from distributed_machine_learning_tpu.multihost.bootstrap import (
                allocate_coordinator_port,
            )

            try:
                port = allocate_coordinator_port()
            except OSError as exc:  # pragma: no cover - no free ports
                dbg(f"gang_prepare failed: {exc!r}")
                continue
            _send(sock, state.send_lock, {
                "type": "gang_port",
                "gang_id": msg.get("gang_id", ""),
                "port": port,
            }, secret)
        elif mtype == "run_gang_member":
            # A gang member leases a contiguous local device group by slot,
            # exactly like a local mesh trial.
            slot = int(msg.get("slot", 0))
            n = max(int(msg.get("local_device_count", 1)), 1)
            if n <= 1:
                dev = [devices[slot % len(devices)]]
            else:
                groups = max(len(devices) // n, 1)
                g = slot % groups
                dev = devices[g * n:(g + 1) * n] or devices[:n]
            threading.Thread(
                target=_worker_run_gang_member,
                args=(state, msg, dev),
                name=f"gang-{msg['gang_id']}-p{msg['process_id']}",
                daemon=True,
            ).start()
        elif mtype == "gang_abort":
            # Head-side gang teardown: SIGKILL the member child (it may be
            # wedged in a collective against a dead peer — no report
            # boundary will ever come).  The relay thread sees EOF and
            # ships the terminal frame.
            with state.gang_lock:
                handle = state.gang_children.get(
                    (msg["trial_id"], int(msg.get("incarnation", 0)))
                )
            if handle is not None:
                dbg(f"gang_abort {msg['trial_id']}")
                handle.kill()
        elif mtype == "decision":
            with state.dec_lock:
                dq = state.decisions.get(
                    (msg["trial_id"], int(msg.get("incarnation", 0)))
                )
            if dq is not None:
                dq.put(msg["decision"])
        elif mtype == "artifact":
            # Head's answer to an artifact_get: wake the trial thread
            # blocked in _fetch_artifacts (None files = origin miss).
            with state.art_lock:
                aq = state.artifact_replies.get(msg.get("key", ""))
            if aq is not None:
                aq.put(msg.get("files"))
        elif mtype == "fence":
            # Self-fencing: the driver requeued this trial elsewhere (we
            # looked hung or partitioned).  Pre-load a stop decision so the
            # named incarnation(s) end at their next report boundary instead
            # of racing the replacement for the rest of the sweep.  Without
            # an incarnation, fence every incarnation of the trial.
            inc = msg.get("incarnation")
            with state.dec_lock:
                targets = [
                    dq for key, dq in state.decisions.items()
                    if key[0] == msg["trial_id"]
                    and (inc is None or key[1] == int(inc))
                ]
            for dq in targets:
                dbg(f"fenced {msg['trial_id']}")
                dq.put("stop")
        elif mtype == "shutdown":
            shutdown = True
            break
    # Unblock any trials still waiting on decisions so threads exit.
    stop_hb.set()
    with state.dec_lock:
        for dq in state.decisions.values():
            dq.put("stop")
    # Gang children must never outlive the driver connection that spawned
    # them (a stop decision only reaches a child sitting at a report
    # boundary; one wedged in a collective needs the kill).
    with state.gang_lock:
        handles = list(state.gang_children.values())
    for handle in handles:
        handle.kill()
    sock.close()
    return shutdown


def join_driver(
    driver_address: str,
    slots: Optional[int] = None,
    secret: Optional[bytes] = None,
) -> bool:
    """Elastically join a running driver (the reverse of ``serve_worker``).

    The worker dials the driver's ``elastic_listen`` endpoint and serves the
    same protocol over that connection — how capacity is ADDED to a live
    experiment (a freshly provisioned/recovered TPU host joins mid-run; the
    driver immediately starts dispatching queued trials to it).  Dialing
    out also suits hosts behind NAT where the driver can't dial in.
    Blocks until the driver disconnects or shuts the worker down; returns
    True on an explicit shutdown (callers looping for driver restarts can
    stop then)."""
    startup_t0 = time.monotonic()
    secret = secret if secret is not None else _cluster_secret()
    host, port = driver_address.rsplit(":", 1)
    if not _is_loopback(host) and not secret:
        # Same trust model (and warning) as the listening endpoints, inverse
        # direction: frames FROM the dialed driver are pickled too, so an
        # unauthenticated non-loopback driver can run code on this worker.
        print(
            "[cluster] WARNING: dialing a non-loopback driver "
            f"({host}) without DML_CLUSTER_SECRET — a spoofed or compromised "
            "driver can run code on this host (pickled control frames). Set "
            "a shared secret or join drivers on loopback/private networks.",
            flush=True,
        )
    sock = socket.create_connection((host, int(port)), timeout=30)
    # Clear the connect timeout: it would otherwise persist on every recv,
    # and a >30s gap between driver frames (idle worker, long epoch) would
    # be misread as driver EOF, tearing the worker down mid-run.
    sock.settimeout(None)

    import jax

    from distributed_machine_learning_tpu import compilecache as _cc

    _cc.enable_persistent_cache()  # same amortization as serve_worker

    devices = list(jax.devices())
    slots = slots or len(devices)
    startup_s = time.monotonic() - startup_t0

    debug = bool(os.environ.get("DML_CLUSTER_DEBUG"))

    def dbg(msg: str):
        if debug:
            print(f"[worker->{driver_address}] {msg}", flush=True)

    return _serve_driver_connection(
        sock, secret, devices, slots, dbg, startup_s=startup_s,
        # Per-connection watermark: a joiner serves exactly one driver, but
        # the same ghost-head frames can heal late inside that connection.
        head_watermark={"experiment": None, "incarnation": 0,
                        "fenced_head_frames": 0},
    )


# --------------------------------------------------------------------------
# driver side
# --------------------------------------------------------------------------


# How many multiples of a worker's measured spawn time the first-beat
# grace must cover.  Spawn = process start + jax import + device enum; a
# trial's cold start (trainable import + storage setup + first epoch) is
# empirically lighter than that, so 5x is comfortable headroom while
# still being LOAD-PROPORTIONAL: an idle host (~5-10s spawn) keeps tight
# deadlines, a thrashing CI host (60s+ spawn) gets minutes of grace
# instead of a spurious stall->requeue (the PR 9/11 full-run flake).
STARTUP_GRACE_SCALE = 5.0


def startup_scaled_grace(
    deadline_s: float,
    grace_s: Optional[float],
    worker_startup_s: float,
) -> float:
    """Per-trial first-beat grace scaled from the worker's MEASURED spawn
    time, never below the configured (or default) fixed grace.

    The fixed grace answers "how long may a healthy cold start take on an
    idle host"; the scaled term answers the question the flake actually
    asked — "on THIS host, under ITS current load".  Both are floors, so
    scaling can only make expiry more conservative; steady-state stall
    detection (after the first beat) is untouched.
    """
    base = (
        float(grace_s) if grace_s is not None
        else max(3.0 * float(deadline_s), 30.0)
    )
    return max(base, STARTUP_GRACE_SCALE * max(float(worker_startup_s), 0.0))


class RemoteWorker:
    """Driver-side handle for one host supervisor connection."""

    # Stamped by run_distributed once its journal assigns this head an
    # incarnation number; every frame sent to the worker then carries it
    # (plus the experiment name scoping it) so the worker can fence a dead
    # head's ghost (see serve_worker).
    head_incarnation: Optional[int] = None
    head_experiment: Optional[str] = None

    def __init__(self, address: str, secret: Optional[bytes] = None):
        self.address = address
        self.secret = secret if secret is not None else _cluster_secret()
        host, port = address.rsplit(":", 1)
        self.sock = socket.create_connection((host, int(port)), timeout=30)
        self._handshake()

    @classmethod
    def from_socket(
        cls,
        sock: socket.socket,
        address: str,
        secret: Optional[bytes] = None,
    ) -> "RemoteWorker":
        """Wrap a connection the DRIVER accepted (elastic join): the worker
        dialed us via ``join_driver`` and speaks the same protocol."""
        self = cls.__new__(cls)
        self.address = address
        self.secret = secret if secret is not None else _cluster_secret()
        self.sock = sock
        self._handshake()
        return self

    def _handshake(self):
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.send_lock = named_lock("cluster.head.send")
        # The hello frame waits on the worker's jax cold-import; give it time.
        self.sock.settimeout(300)
        hello = _recv(self.sock, self.secret)
        self.sock.settimeout(None)
        if not hello or hello.get("type") != "hello":
            raise ConnectionError(
                f"Bad hello from worker {self.address}: {hello!r}"
            )
        self.slots: int = int(hello["slots"])
        self.hostname: str = hello.get("host", self.address)
        # The worker's MEASURED spawn->ready time: under host load (CI
        # neighbors, bench children) jax import stretches from seconds to
        # minutes, and the same load stretches every trial's cold start —
        # so per-trial first-beat grace scales from this instead of
        # trusting a fixed constant (startup_scaled_grace).
        self.startup_s: float = float(hello.get("startup_s", 0.0) or 0.0)
        self.running: Dict[str, int] = {}  # trial_id -> slot
        self.alive = True
        # Liveness bookkeeping (driver clock): last frame seen, and the
        # suspect state a silent worker enters when its lease expires —
        # no dispatches, trials requeued, connection kept for the
        # reconnect-grace window (a partition heals; a dead host doesn't).
        # Monotonic clock throughout: lease expiry and reconnect grace are
        # DEADLINES, and an NTP step must not expire a live worker
        # (dmlint DML004 wallclock-deadline).
        self.last_seen = time.monotonic()
        self.suspect = False
        self.expired_at = 0.0
        # Chaos partition (injected by the driver's fault plan): while
        # active, frames in BOTH directions are buffered, not dropped —
        # TCP delays delivery across a real partition, so on heal the
        # backlog lands all at once and stale frames get fenced.
        self._pt_lock = named_lock("cluster.head.partition")
        self._partition_until = 0.0
        self._in_buffer: List[Dict[str, Any]] = []
        self._out_buffer: List[Dict[str, Any]] = []

    @property
    def free_slots(self) -> int:
        if not self.alive or self.suspect:
            return 0
        return self.slots - len(self.running)

    def send(self, msg: Dict[str, Any]):
        if self.head_incarnation is not None:
            msg.setdefault("head_incarnation", self.head_incarnation)
            msg.setdefault("head_experiment", self.head_experiment)
        with self._pt_lock:
            if time.monotonic() < self._partition_until:
                self._out_buffer.append(msg)
                return
        _send(self.sock, self.send_lock, msg, self.secret)

    # -- injected partition (chaos) -----------------------------------------

    def partition(self, duration_s: float):
        with self._pt_lock:
            self._partition_until = time.monotonic() + float(duration_s)

    def receive_frames(self, msg: Dict[str, Any]) -> List[Dict[str, Any]]:
        """Reader-thread choke point: buffer ``msg`` while partitioned;
        on the first frame after the partition elapses, flush the held
        outgoing frames to the worker and release the held incoming ones
        (in arrival order, before ``msg``)."""
        with self._pt_lock:
            if time.monotonic() < self._partition_until:
                self._in_buffer.append(msg)
                return []
            if not self._in_buffer and not self._out_buffer:
                return [msg]
            backlog_in = self._in_buffer
            backlog_out = self._out_buffer
            self._in_buffer = []
            self._out_buffer = []
        for held in backlog_out:
            try:
                _send(self.sock, self.send_lock, held, self.secret)
            except OSError:
                self.alive = False
                break
        return backlog_in + [msg]

    def close(self, shutdown: bool = False):
        try:
            if shutdown and self.alive:
                self.send({"type": "shutdown"})
        except OSError:
            pass
        try:
            # shutdown() (not just close()) is required: the reader thread
            # blocked in recv() holds the file description open, so a bare
            # close() would never send FIN and the worker would never see
            # EOF — wedging it for the next driver.
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass
        self.alive = False


def run_distributed(
    trainable: Union[str, Callable],
    param_space: Union[Dict[str, Any], SearchSpace],
    *,
    metric: str,
    workers: Sequence[str],
    mode: str = "min",
    num_samples: int = 10,
    scheduler: Optional[TrialScheduler] = None,
    search_alg: Optional[Searcher] = None,
    storage_path: str = "~/dml_tpu_results",
    name: Optional[str] = None,
    seed: int = 0,
    max_failures: int = 0,
    time_budget_s: Optional[float] = None,
    time_limit_per_trial_s: Optional[float] = None,
    verbose: int = 1,
    callbacks: Optional[List] = None,
    shutdown_workers: bool = False,
    keep_checkpoints_num: int = 0,
    checkpoint_storage: Optional[str] = None,
    checkpoint_format: str = "msgpack",
    mesh_shape: Optional[Dict[str, int]] = None,
    processes_per_trial: int = 1,
    gang_join_deadline_s: float = 120.0,
    input_mode: Optional[str] = None,
    elastic_listen: Union[str, socket.socket, None] = None,
    artifact_origin: Union[bool, "ArtifactRegistry"] = True,
    resume: Union[bool, str] = False,
    points_to_evaluate: Optional[Sequence[Dict[str, Any]]] = None,
    stop=None,
    progress_deadline_s: Optional[float] = None,
    progress_grace_s: Optional[float] = None,
    worker_heartbeat_timeout_s: Optional[float] = 60.0,
    worker_reconnect_grace_s: float = 30.0,
    trace: bool = False,
) -> ExperimentAnalysis:
    """``tune.run`` across multiple host supervisors (see module docstring).

    ``trainable`` should be a ``"module:function"`` spec (resolved on each
    worker host); a module-level callable also works (pickled by reference).
    ``workers``: list of ``"host:port"`` supervisor addresses. Supervisors
    outlive the experiment (they re-accept the next driver) unless
    ``shutdown_workers=True``.

    ``elastic_listen``: a ``"host:port"`` endpoint (or an already-bound
    listening socket) on which the driver accepts workers joining mid-run
    via ``join_driver`` — elastic scale-up: queued trials dispatch to a
    joiner the moment its hello lands, and ``workers`` may be empty (the
    driver then waits for the first joiner instead of failing).

    ``artifact_origin``: the head doubles as a **compile-artifact origin**
    (compile-once tentpole).  Before compiling a program key it has not
    seen, a worker asks the head for that key's cache artifacts
    (``artifact_get``/``artifact`` frames); a worker that does compile
    publishes the new cache entries (``artifact_put``), so a sweep of N
    trials over K distinct shape classes compiles each program once per
    slice topology instead of once per worker.  Fetch failures (chaos
    ``artifact_fetch_error_rate``, timeouts, partitions) always fall back
    to local compilation.  Head counters (``origin_publishes``,
    ``origin_fetch_hits``/``misses``, ``distinct_keys``) land in
    ``experiment_state.json["compile"]``; worker-side fetch/publish
    counters stay on the workers.  ``False`` answers every fetch empty and
    drops publishes.  Pass a ``compilecache.ArtifactRegistry`` instead of
    ``True`` to keep the registry alive ACROSS sweeps on a long-lived
    head — the next experiment's workers then warm-start from everything
    earlier sweeps compiled.
    ``resume``: continue an interrupted distributed experiment (requires an
    explicit ``name``) — same semantics as ``tune.run(resume=True)``:
    finished trials kept and replayed, interrupted trials redispatched from
    their newest shared-storage checkpoint, sampling continued.
    ``resume="auto"`` resumes IFF the head's decision journal
    (``<experiment>/journal.jsonl``) was left uncommitted by a crashed
    head — replaying it restores searcher/scheduler state bit-identically
    (docs/operations.md, "Head crash recovery") — and otherwise starts
    fresh, so supervisor loops can pass it unconditionally.  Resuming
    without ``checkpoint_storage`` is a hard error unless every worker is
    loopback (worker-local restore points are invisible across hosts).
    ``checkpoint_format``: ``"msgpack"`` (default) or ``"sharded"`` —
    same knob as ``tune.run``; workers write whichever the driver picked,
    and every requeue/restore path reads both.  With ``"sharded"`` each
    worker writes per-shard chunk files + an atomic COMMIT marker, so a
    worker preempted mid-save never leaves a half-visible checkpoint and
    requeue lands on the newest COMMITTED generation.
    ``mesh_shape``: sweep-wide per-trial device mesh (same knob as
    ``tune.run``), e.g. ``{"dp": 2, "tp": 2}`` — stamped into every
    sampled config, and each dispatch asks its worker for the mesh's
    total device count: the worker assigns that many distinct local
    devices to the trial's slot group (start workers with
    ``slots = len(devices) // prod(mesh_shape)`` so slot groups never
    overlap).  The sharded trainable then builds the named mesh from the
    model family's partition rules (``models/partition_rules.py``).
    ``processes_per_trial``: >1 makes every trial a **gang** — one trial
    owning a DP×TP mesh that SPANS that many worker processes
    (``multihost/``).  The head brokers the ``jax.distributed`` bootstrap:
    it picks N workers, asks member 0's supervisor to reserve a
    coordinator port (``gang_prepare``/``gang_port``), assigns dense
    process ids, and ships each member a GangSpec; each supervisor spawns
    a FRESH gang-member subprocess (``jax.distributed`` must initialize
    before the backend, which a long-lived supervisor already did).
    Dispatch gates on an all-members-joined barrier with
    ``gang_join_deadline_s`` — expiry dumps the flight recorder naming
    the absent process ids and requeues the trial.  Only the gang
    coordinator (process 0) reports/saves; decisions broadcast in-band to
    the other members.  Any member death (preemption, chaos
    ``kill_process_at``) tears the whole gang down — surviving members
    are killed mid-collective — and the trial requeues from its newest
    valid checkpoint within ``max_failures`` (counters
    ``gang_teardowns`` / ``gang_requeues`` / ``gang_bootstrap_timeouts``
    in the liveness block).  Requires ``checkpoint_format="sharded"``
    (a process-spanning pytree saves per-process chunks; the resharding
    restore reads them back on ANY topology).  ``mesh_shape``'s total
    device count must divide evenly across the gang; without
    ``mesh_shape`` each member contributes one device (pure dp).
    Compile-cache keys fold the gang's process topology
    (``compilecache.gang_program_key``): reshaping the gang splits the
    key; a second same-topology gang fetches the first gang's artifacts
    from the head's origin and compiles nothing.
    ``input_mode``: sweep-wide data staging mode (same knob as
    ``tune.run``), stamped into every sampled config: ``"resident"``,
    ``"streaming"`` (the out-of-core prefetch ring, ``data/pipeline.py``),
    or ``"auto"``.  The trainable resolves it against the budget of the
    devices its WORKER leased; host_input counters stay worker-side (they
    describe each worker host's own input path).
    ``stop`` / ``points_to_evaluate``: same surface as ``tune.run`` (dict /
    callable / Stopper; warm-start configs run first).
    ``callbacks`` / ``verbose=2``: the same observer surface as ``tune.run``
    (LoggerCallback, JsonlCallback, TensorBoardCallback, ProgressReporter —
    verbose>=2 auto-attaches the live trial table); hooks run on the
    driver's single event-loop thread.

    Fail-slow liveness (the fault class socket EOF cannot catch — a hung
    worker keeps its TCP connection open):

    * ``worker_heartbeat_timeout_s`` — supervisors heartbeat on the control
      plane (every ``DML_CLUSTER_HEARTBEAT_S``, default 2s); a worker
      silent for this long has its lease expired: no new dispatches, its
      in-flight trials are requeued to live workers from their newest
      checksum-valid checkpoints within ``max_failures``.  ``None``
      disables.  A partitioned worker that speaks again within
      ``worker_reconnect_grace_s`` of expiry rejoins the pool (its old
      trials stay requeued; any late frames for them are fenced and the
      zombie incarnations told to stop); one that stays silent past the
      grace is closed and treated as dead.
    * ``progress_deadline_s`` — per-TRIAL progress watchdog (liveness.py):
      a dispatched trial with no result/heartbeat frame for this long is
      counted stalled, fenced on its worker, and requeued — this catches a
      single wedged trial thread on an otherwise-healthy (still
      heartbeating) host.  ``progress_grace_s`` adds first-signal
      allowance for startup/compile (default ``max(3 * deadline, 30)``).

    Counters (lease expiries, stalls, requeues, fenced frames, reconnects)
    land in ``experiment_state.json["liveness"]`` and TensorBoard.  Note
    the fencing model is at-least-once: until a fenced incarnation reaches
    its next report boundary it may still write checkpoint generations —
    atomic per file, so restores stay safe, but non-deterministic
    trainables can interleave generations from two incarnations.
    """
    if mode not in ("min", "max"):
        raise ValueError(f"mode must be 'min' or 'max', got {mode!r}")
    processes_per_trial = int(processes_per_trial)
    if processes_per_trial < 1:
        raise ValueError(
            f"processes_per_trial must be >= 1, got {processes_per_trial}"
        )
    gang_devices_per_member = 1
    if processes_per_trial > 1:
        if checkpoint_format != "sharded":
            raise ValueError(
                "processes_per_trial > 1 checkpoints from a process-"
                "spanning mesh, which only the sharded format can write "
                "(per-process chunks + COMMIT): pass "
                "checkpoint_format='sharded'"
            )
        if mesh_shape:
            total_mesh_devices = 1
            for v in mesh_shape.values():
                total_mesh_devices *= max(int(v), 1)
            if total_mesh_devices % processes_per_trial != 0:
                raise ValueError(
                    f"mesh_shape {dict(mesh_shape)} has "
                    f"{total_mesh_devices} devices, not divisible across "
                    f"{processes_per_trial} gang members"
                )
            gang_devices_per_member = (
                total_mesh_devices // processes_per_trial
            )
    if input_mode is not None and input_mode not in (
        "auto", "resident", "streaming"
    ):
        raise ValueError(
            f"input_mode must be 'auto', 'resident' or 'streaming', "
            f"got {input_mode!r}"
        )
    # resume="auto": resume IFF a prior head left its decision journal
    # uncommitted (crashed mid-sweep); otherwise run fresh.  Same contract
    # as tune.run(resume="auto").
    journal_resume = False
    if resume == "auto":
        if not name:
            raise ValueError(
                'resume="auto" needs the explicit experiment `name`'
            )
        journal_resume = journal_lib.is_uncommitted(
            ExperimentStore.root_for(storage_path, name)
        )
        resume = journal_resume
    if resume:
        from distributed_machine_learning_tpu.tune.runner import _validate_resume

        _validate_resume(storage_path, name)
        if checkpoint_storage is None:
            # On a real multi-host pool, workers checkpoint to THEIR local
            # filesystems; the resuming driver would find nothing and re-run
            # interrupted trials from scratch (discarding their progress).
            # Hard error, same discipline as _validate_resume — a resume
            # that silently discards progress is worse than one that fails.
            # The one provably-safe case: every worker on loopback, where
            # "a filesystem shared with the workers" is trivially this
            # host's own.
            remote = [
                w for w in workers
                if not _is_loopback(w.rsplit(":", 1)[0])
            ]
            if remote or not workers:
                raise ValueError(
                    "resume without checkpoint_storage: workers checkpoint "
                    "to their own local filesystems, so this driver would "
                    "find no restore points and re-run interrupted trials "
                    "from scratch ("
                    + (f"non-loopback workers: {remote}"
                       if remote else "elastic joiners may be remote")
                    + "). Pass checkpoint_storage='gs://...' or another "
                    "path shared with every worker."
                )
    if not workers and elastic_listen is None:
        raise ValueError(
            "run_distributed needs at least one worker address "
            "(or elastic_listen for join-based capacity)"
        )
    if (
        processes_per_trial > 1
        and elastic_listen is None
        and len(workers) < processes_per_trial
    ):
        raise ValueError(
            f"processes_per_trial={processes_per_trial} needs at least "
            f"that many worker supervisors (got {len(workers)}; gang "
            f"members must live in distinct processes), or elastic_listen "
            f"for join-based capacity"
        )
    if checkpoint_storage and checkpoint_storage.startswith("mem://"):
        raise ValueError(
            "checkpoint_storage='mem://...' is process-local (a test fake): "
            "worker subprocesses would write checkpoints into their own "
            "memory and restores on other workers would silently find "
            "nothing. Use a shared filesystem path or gs:// for distributed "
            "runs."
        )
    space = (
        param_space
        if isinstance(param_space, SearchSpace)
        else SearchSpace(param_space)
    )
    from distributed_machine_learning_tpu.tune.stoppers import resolve_stop

    stop = resolve_stop(stop)  # validate dict/callable/Stopper up front
    searcher = maybe_warm_start(search_alg or RandomSearch(), points_to_evaluate)
    searcher.set_search_space(space, seed)
    sched = scheduler or FIFOScheduler()
    sched.set_experiment(metric, mode)

    name = name or f"dist_{time.strftime('%Y%m%d_%H%M%S')}_{uuid.uuid4().hex[:6]}"
    store = ExperimentStore(storage_path, name, checkpoint_storage,
                            checkpoint_format=checkpoint_format)
    from distributed_machine_learning_tpu.ckpt import get_metrics
    from distributed_machine_learning_tpu import compilecache

    ckpt_metrics_base = get_metrics().snapshot()
    compile_tracker_base = compilecache.get_tracker().snapshot()
    compile_counters_base = compilecache.get_counters().snapshot()
    # Head-side artifact registry: program key -> the cache files the first
    # compiling worker published (see the artifact_origin docstring).  A
    # caller-provided registry persists across runs; counters are scoped to
    # this run via the baseline snapshot.
    if isinstance(artifact_origin, compilecache.ArtifactRegistry):
        artifacts = artifact_origin
        artifact_origin = True
    else:
        from distributed_machine_learning_tpu import store as store_lib

        # Store-backed registry when the CAS layer is on: executables and
        # their cost sidecars land as content-addressed blobs under the
        # experiment root's store (dedup against re-publishes, durable
        # across a head restart, collected by the same reachability GC as
        # checkpoints) instead of head RAM.
        cas = (
            store_lib.get_store(
                store_lib.store_root_for(
                    os.path.join(store.root, "artifacts")
                )
            )
            if store_lib.store_enabled()
            else None
        )
        artifacts = compilecache.ArtifactRegistry(store=cas)
    artifacts_base = artifacts.snapshot()
    store.set_context(metric, mode)

    # Observability plane (obs/, same surface as tune.run): flight dumps
    # land in the experiment root; with ``trace`` (or DML_OBS_TRACE=1) the
    # driver AND every worker stream spans into <root>/trace/ — workers
    # reach it through the dispatch frame's trace context, so one trial's
    # spans share one trace id across the head/worker boundary.  Shared
    # storage is assumed exactly as it is for checkpoints.
    from distributed_machine_learning_tpu import obs as obs_lib

    trace = trace or os.environ.get("DML_OBS_TRACE") == "1"
    trace_dir = os.path.join(store.root, "trace") if trace else None
    prev_dump_dir = obs_lib.dump_dir()
    # Journal-based resume adopts the dead head's trace identity BEFORE the
    # tracer is configured: one trace id spans both head incarnations.
    replay = journal_lib.parse_journal(store.root) if journal_resume else None
    prior_frame = (replay.trace_frame if replay is not None else None) or {}
    obs_lib.configure(trace_dir=trace_dir, label="head",
                      dump_dir=store.root,
                      trace_id=prior_frame.get("trace_id"),
                      parent_span_id=prior_frame.get("parent_span_id"))
    # Write-ahead decision journal: every scheduling decision is durable
    # BEFORE its effect (dispatch frame, decision answer) leaves the head.
    journal = journal_lib.ExperimentJournal(store.root)
    head_incarnation = journal.open(obs_frame=obs_lib.trace_context_frame())
    obs_counters_base = obs_lib.get_registry().counters_snapshot()
    worker_obs: Dict[str, Dict[str, float]] = {}  # addr -> last snapshot
    trial_spans: Dict[str, Any] = {}

    events: "queue.Queue[Tuple]" = queue.Queue()
    pool: List[RemoteWorker] = []

    def log(msg: str):
        if verbose:
            print(f"[tune.cluster] {msg}", flush=True)

    from distributed_machine_learning_tpu.tune.callbacks import (
        dispatch_safely,
        with_default_reporter,
    )

    callbacks = with_default_reporter(callbacks, verbose)

    def safe_cb(hook: str, *args):
        dispatch_safely(callbacks, hook, *args, log=log)

    def reader(worker: RemoteWorker):
        while True:
            msg = _recv(worker.sock, worker.secret)
            if msg is None:
                events.put(("worker_dead", worker))
                return
            # receive_frames is the injected-partition choke point: during
            # a partition frames are held (last_seen frozen — the lease
            # expiry this exercises), and the heal flushes the backlog.
            for held in worker.receive_frames(msg):
                worker.last_seen = time.monotonic()
                events.put(("msg", worker, held))

    def add_worker(w: RemoteWorker):
        # Every frame to this worker carries the head's incarnation (scoped
        # by experiment name) so the supervisor can fence a dead head's
        # ghost (serve_worker watermark).
        w.head_incarnation = head_incarnation
        w.head_experiment = name
        pool.append(w)
        threading.Thread(
            target=reader, args=(w,), name=f"reader-{w.address}", daemon=True
        ).start()

    for addr in workers:
        add_worker(RemoteWorker(addr))

    # Elastic scale-up: accept join_driver workers for the whole run. The
    # accept thread only performs the handshake and queues the worker; the
    # single-threaded main loop adds it to the pool (no pool races).
    elastic_server: Optional[socket.socket] = None
    if elastic_listen is not None:
        if isinstance(elastic_listen, socket.socket):
            elastic_server = elastic_listen
        else:
            ehost, eport = elastic_listen.rsplit(":", 1)
            elastic_server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            elastic_server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            elastic_server.bind((ehost, int(eport)))
            elastic_server.listen(8)
        try:
            bind_host = elastic_server.getsockname()[0]
        except OSError:
            bind_host = "?"
        if not _is_loopback(bind_host) and not _cluster_secret():
            # Same trust model (and warning) as serve_worker: hellos are
            # pickled frames, so a routable bind without a shared secret
            # means anyone who can reach the port runs code on the DRIVER.
            log(
                f"WARNING: elastic_listen bound to a routable interface "
                f"({bind_host}) without DML_CLUSTER_SECRET — any host that "
                f"can reach the port can execute code on this driver. Set a "
                f"shared secret or bind loopback/private networks."
            )

        def handshake_joiner(sock: socket.socket, peer):
            # Per-connection thread: one stalled or garbage-sending client
            # must neither kill the accept loop nor block other joiners.
            try:
                w = RemoteWorker.from_socket(sock, f"{peer[0]}:{peer[1]}")
            except Exception as exc:  # noqa: BLE001 - bad frame, bad pickle,...
                log(f"rejected joining worker {peer}: {exc!r}")
                try:
                    sock.close()
                except OSError:
                    pass
                return
            events.put(("worker_joined", w))

        def accept_joiners(server: socket.socket):
            while True:
                try:
                    sock, peer = server.accept()
                except OSError:
                    return  # server closed at teardown
                threading.Thread(
                    target=handshake_joiner,
                    args=(sock, peer),
                    name=f"elastic-handshake-{peer[1]}",
                    daemon=True,
                ).start()

        threading.Thread(
            target=accept_joiners,
            args=(elastic_server,),
            name="elastic-accept",
            daemon=True,
        ).start()

    trainable_spec: Any = trainable
    assignment: Dict[str, RemoteWorker] = {}
    # Gang trials (processes_per_trial > 1): head-side records of each
    # trial's process-spanning mesh (multihost/gang.py).
    from distributed_machine_learning_tpu.multihost.gang import (
        Gang,
        GangMember,
    )

    gangs: Dict[str, Gang] = {}
    gang_by_trial: Dict[str, Gang] = {}

    from distributed_machine_learning_tpu import chaos as chaos_lib

    watchdog = None
    if progress_deadline_s is not None:
        from distributed_machine_learning_tpu.liveness import DispatchWatchdog

        # Polled from the event loop below (ticks every <=0.5s).
        watchdog = DispatchWatchdog(
            progress_deadline_s, first_beat_grace_s=progress_grace_s
        )
    liveness = {
        "stalls_detected": 0,
        "stall_requeues": 0,
        "lease_expiries": 0,
        "silent_worker_requeues": 0,
        "fenced_frames": 0,
        "worker_reconnects": 0,
        "quarantined_checkpoints": 0,
        "gang_teardowns": 0,
        "gang_requeues": 0,
        "gang_bootstrap_timeouts": 0,
    }
    # Live view of the head's liveness counters in the unified registry
    # (the published experiment_state.json block keeps its shape below).
    obs_lib.get_registry().register_family(
        "liveness",
        lambda: {
            **liveness,
            **(watchdog.snapshot() if watchdog is not None else {}),
        },
    )

    lifecycle = TrialLifecycle(
        searcher=searcher,
        scheduler=sched,
        store=store,
        metric=metric,
        mode=mode,
        num_samples=num_samples,
        max_failures=max_failures,
        stop_rules=stop,
        time_budget_s=time_budget_s,
        keep_checkpoints_num=keep_checkpoints_num,
        # Soft enforcement only: the limit takes effect at report boundaries
        # (worker trials run in supervisor threads; hard preemption needs
        # the local process executor, runner.py).
        time_limit_per_trial_s=time_limit_per_trial_s,
        log=log,
        config_overlay={
            **({"mesh_shape": dict(mesh_shape)} if mesh_shape else {}),
            **({"input_mode": input_mode} if input_mode else {}),
        } or None,
        journal=journal,
    )
    trials = lifecycle.trials
    by_id = lifecycle.by_id
    pending = lifecycle.pending
    start_time = lifecycle.start_time

    if journal_resume and replay is not None:
        counts = lifecycle.restore_from_journal(replay)
        log(
            f"resumed {name} from journal (head incarnation "
            f"{head_incarnation}): {counts['finished']} finished trials "
            f"kept, {counts['requeued']} interrupted trials requeued, "
            f"{counts['suppress_windows']} replay suppression windows"
        )
    elif resume:
        counts = lifecycle.restore_experiment()
        log(
            f"resumed {name}: {counts['finished']} finished trials kept, "
            f"{counts['requeued']} interrupted trials requeued"
        )

    def dispatch(trial: Trial, worker: RemoteWorker):
        slot = next(
            s for s in range(worker.slots) if s not in worker.running.values()
        )
        worker.running[trial.trial_id] = slot
        assignment[trial.trial_id] = worker
        lifecycle.mark_running(trial, worker=worker.address)
        if watchdog is not None:
            # First-beat grace scales from THIS worker's measured spawn
            # time: a loaded host that took a minute to import jax will
            # also start trials slowly, and a fixed grace there reads
            # "slow" as "stalled" (the worker-startup deadline flake).
            watchdog.track(
                trial.trial_id,
                first_beat_grace_s=startup_scaled_grace(
                    progress_deadline_s, progress_grace_s,
                    worker.startup_s,
                ),
            )
        # Head-side dispatch span; its context rides the dispatch frame so
        # the worker's trial span lands in the SAME trace (id included).
        span = obs_lib.detached_span(
            "trial.dispatch",
            {"trial_id": trial.trial_id, "incarnation": trial.incarnation,
             "worker": worker.address},
            parent=obs_lib.current_context(),
        )
        trial_spans[trial.trial_id] = span
        obs_lib.event("trial_dispatch", {
            "trial_id": trial.trial_id, "worker": worker.address,
        })
        safe_cb("on_trial_start", trial)
        try:
            trial_mesh = trial.config.get("mesh_shape") or {}
            num_devices = 1
            for v in trial_mesh.values():
                num_devices *= max(int(v), 1)
            worker.send(
                {
                    "type": "run_trial",
                    "trial_id": trial.trial_id,
                    "incarnation": trial.incarnation,
                    "config": dict(trial.config),
                    "trainable": trainable_spec,
                    "slot": slot,
                    "num_devices": num_devices,
                    "checkpoint_dir": store.checkpoint_dir(trial),
                    "checkpoint_format": store.checkpoint_format,
                    "restore_path": trial.restore_path,
                    "start_iteration": trial.training_iteration,
                    "artifact_origin": artifact_origin,
                    "obs": obs_lib.trace_context_frame(parent=span.context),
                }
            )
        except OSError:
            # Reader thread will (or already did) flag the death; requeue now
            # so the trial isn't stranded on a dead worker.
            worker.alive = False
            release(trial)
            lifecycle.requeue(trial)

    def dispatch_gang(trial: Trial) -> bool:
        """Reserve one slot on ``processes_per_trial`` DISTINCT workers and
        start the gang bootstrap (coordinator-port reservation on member
        0's supervisor).  False — with no side effects — when too few
        workers currently have capacity; the trial stays pending."""
        avail = [w for w in pool if w.free_slots > 0]
        if len(avail) < processes_per_trial:
            return False
        members = []
        for i, worker in enumerate(avail[:processes_per_trial]):
            slot = next(
                s for s in range(worker.slots)
                if s not in worker.running.values()
            )
            worker.running[trial.trial_id] = slot
            members.append(GangMember(worker=worker, slot=slot,
                                      process_id=i))
        # mark_running bumps the incarnation; the gang id carries the
        # bumped value so member frames and the stale-frame guard agree.
        lifecycle.mark_running(trial, worker=members[0].worker.address)
        gang = Gang(
            gang_id=f"{trial.trial_id}.i{trial.incarnation}",
            trial_id=trial.trial_id,
            incarnation=trial.incarnation,
            members=members,
        )
        gang.prepare_deadline = time.monotonic() + float(
            gang_join_deadline_s
        )
        gangs[gang.gang_id] = gang
        gang_by_trial[trial.trial_id] = gang
        # Result/decision traffic flows through the COORDINATOR member's
        # supervisor: that worker is the trial's assignment.
        assignment[trial.trial_id] = members[0].worker
        if watchdog is not None:
            # First-beat grace must additionally cover the gang bootstrap
            # (fresh interpreter + jax import + distributed join per
            # member) — floor it at the join deadline.
            watchdog.track(
                trial.trial_id,
                first_beat_grace_s=max(
                    startup_scaled_grace(
                        progress_deadline_s, progress_grace_s,
                        max(m.worker.startup_s for m in members),
                    ),
                    float(gang_join_deadline_s),
                ),
            )
        span = obs_lib.detached_span(
            "trial.dispatch",
            {"trial_id": trial.trial_id, "incarnation": trial.incarnation,
             "gang_id": gang.gang_id,
             "workers": [m.worker.address for m in members]},
            parent=obs_lib.current_context(),
        )
        trial_spans[trial.trial_id] = span
        obs_lib.event("gang_dispatch", {
            "gang_id": gang.gang_id,
            "trial_id": trial.trial_id,
            "workers": [m.worker.address for m in members],
        })
        safe_cb("on_trial_start", trial)
        try:
            members[0].worker.send(
                {"type": "gang_prepare", "gang_id": gang.gang_id}
            )
        except OSError:
            members[0].worker.alive = False
            teardown_gang(gang, "coordinator worker died at gang prepare")
        return True

    def launch_ready():
        while pending:
            if processes_per_trial > 1:
                if not dispatch_gang(pending[0]):
                    return
                pending.pop(0)
                continue
            worker = max(pool, key=lambda w: w.free_slots, default=None)
            if worker is None or worker.free_slots <= 0:
                return
            dispatch(pending.pop(0), worker)

    def release(trial: Trial):
        gang = gang_by_trial.pop(trial.trial_id, None)
        if gang is not None:
            gangs.pop(gang.gang_id, None)
            for m in gang.members:
                m.worker.running.pop(trial.trial_id, None)
        worker = assignment.pop(trial.trial_id, None)
        if worker is not None:
            worker.running.pop(trial.trial_id, None)
        if watchdog is not None:
            watchdog.untrack(trial.trial_id)
        span = trial_spans.pop(trial.trial_id, None)
        if span is not None:
            span.end()

    def teardown_gang(gang: Gang, why: str, requeue: bool = True):
        """Abort every member (supervisors SIGKILL their gang children —
        peers of a dead member sit wedged in a collective), release all
        reserved slots, and requeue the trial from its newest valid
        checkpoint through the ordinary retry budget."""
        if gang_by_trial.get(gang.trial_id) is not gang:
            return  # stale: the trial already moved on
        liveness["gang_teardowns"] += 1
        log(f"gang {gang.gang_id} teardown: {why.splitlines()[-1]}")
        obs_lib.event("gang_teardown", {
            "gang_id": gang.gang_id, "why": why.splitlines()[-1],
        })
        for m in gang.members:
            try:
                m.worker.send({
                    "type": "gang_abort",
                    "trial_id": gang.trial_id,
                    "incarnation": gang.incarnation,
                })
            except OSError:
                m.worker.alive = False
        trial = by_id.get(gang.trial_id)
        if trial is None:
            gang_by_trial.pop(gang.trial_id, None)
            gangs.pop(gang.gang_id, None)
            return
        if requeue:
            requeue_lost(trial, why, counter="gang_requeues")
            launch_ready()
        else:
            release(trial)

    def requeue_lost(trial: Trial, why: str,
                     counter: str = "silent_worker_requeues"):
        """Requeue a trial whose worker went silent or whose dispatch
        stalled: rewind the restore target to the newest CHECKSUM-VALID
        generation AT OR BELOW the trial's last REPORTED iteration and
        route through fail_trial so the per-trial retry budget bounds
        requeue storms.

        The bound + quarantine fix the at-least-once fencing race: the
        lost incarnation saves each checkpoint BEFORE its report frame,
        so (especially across a partition, where checkpoint writes reach
        shared storage while frames sit buffered) the newest valid
        generation can be one whose report the driver never processed.
        Restoring it would resume PAST the last report and that epoch
        would never be re-reported.  Unreported generations are renamed
        (quarantined — forensics, not deletion) so the worker-side
        corruption fallback can't rediscover them either; the retry
        replays from the last *reported* generation."""
        release(trial)
        quarantined = ckpt_lib.quarantine_unreported(
            store.checkpoint_dir(trial), trial.training_iteration,
            tag=f"i{trial.incarnation}", log=log,
        )
        if quarantined:
            liveness["quarantined_checkpoints"] += quarantined
        path, it = ckpt_lib.newest_valid_checkpoint(
            store.checkpoint_dir(trial),
            max_iteration=trial.training_iteration,
        )
        trial.restore_path = None
        trial.latest_checkpoint = path
        trial.latest_checkpoint_iteration = it
        # The valid generation may be older than what this incarnation had
        # restored from; progress accounting must rewind with it.
        trial.restore_base = min(trial.restore_base, it)
        safe_cb("on_trial_error", trial, why)
        retried = lifecycle.fail_trial(trial, why)
        if retried:
            liveness[counter] += 1
        else:
            store.write_state(trials)
        return retried

    last_enforce = [0.0]
    last_sched_persist = [0.0]

    def revive_if_suspect(worker: RemoteWorker):
        """Any frame from a suspect worker means the silence was a
        partition, not a death.  Within the reconnect grace it rejoins the
        pool (its requeued trials stay requeued — late frames for them are
        fenced); past the grace it is closed as presumed dead."""
        if not worker.suspect or not worker.alive:
            return
        if time.monotonic() - worker.expired_at <= worker_reconnect_grace_s:
            worker.suspect = False
            liveness["worker_reconnects"] += 1
            log(
                f"worker {worker.address} reconnected within grace "
                f"({time.monotonic() - worker.expired_at:.1f}s after lease "
                f"expiry); rejoining pool"
            )
            launch_ready()
        else:
            log(
                f"worker {worker.address} reappeared after the reconnect "
                f"grace ({worker_reconnect_grace_s:.0f}s); closing"
            )
            worker.close()

    def enforce_liveness():
        """Lease expiry for silent WORKERS + progress deadlines for
        dispatched TRIALS.  Rate-limited; runs every loop iteration so a
        busy event stream cannot starve detection."""
        now = time.monotonic()
        if now - last_enforce[0] < 0.25:
            return
        last_enforce[0] = now
        if worker_heartbeat_timeout_s is not None:
            for worker in pool:
                if not worker.alive:
                    continue
                silent = now - worker.last_seen
                if not worker.suspect and silent > worker_heartbeat_timeout_s:
                    worker.suspect = True
                    worker.expired_at = now
                    liveness["lease_expiries"] += 1
                    # Head-side forensics for a silent worker: the last
                    # ~2048 driver events (dispatches, results, beats)
                    # around the moment the lease expired.
                    obs_lib.dump_flight_recorder(
                        f"lease_expiry_{worker.address}",
                        extra={"worker": worker.address,
                               "silent_s": round(silent, 2)},
                    )

                    lost = [by_id[tid] for tid in list(worker.running)]
                    # Bookkeeping record (no decision counter bump): a
                    # resumed head reading the journal sees WHY these
                    # trials were requeued away from their worker.
                    journal.record_note(
                        "lease_expiry", worker=worker.address,
                        silent_s=round(silent, 2),
                        trials=[t.trial_id for t in lost],
                    )
                    log(
                        f"worker {worker.address} silent for {silent:.1f}s "
                        f"(> {worker_heartbeat_timeout_s:.1f}s); lease "
                        f"expired, requeueing {len(lost)} in-flight trials"
                    )
                    for trial in lost:
                        why = (
                            f"worker {worker.address} lease expired "
                            f"(silent {silent:.1f}s — hung or partitioned)"
                        )
                        gang = gang_by_trial.get(trial.trial_id)
                        if gang is not None:
                            teardown_gang(gang, why)
                        else:
                            requeue_lost(trial, why)
                    launch_ready()
                elif worker.suspect and (
                    now - worker.expired_at > worker_reconnect_grace_s
                ):
                    log(
                        f"worker {worker.address} silent past the "
                        f"reconnect grace; presumed dead, closing"
                    )
                    worker.close()
        if watchdog is not None:
            for event in watchdog.expired():
                trial = by_id.get(event.key)
                worker = assignment.get(event.key)
                if trial is None or worker is None:
                    watchdog.untrack(event.key)
                    continue
                trial.stall_count += 1
                liveness["stalls_detected"] += 1
                obs_lib.dump_flight_recorder(
                    f"stall_{trial.trial_id}",
                    extra={"trial_id": trial.trial_id,
                           "worker": worker.address,
                           "age_s": round(event.age_s, 2)},
                )
                why = (
                    f"stalled: no progress signal in {event.age_s:.1f}s "
                    f"on {worker.address} (deadline "
                    f"{event.deadline_s:.1f}s)"
                )
                log(f"{trial.trial_id} {why}; fencing and requeueing")
                gang = gang_by_trial.get(trial.trial_id)
                if gang is not None:
                    # A stalled gang cannot self-fence at a report
                    # boundary — members may be wedged in a collective;
                    # the abort path SIGKILLs them.
                    teardown_gang(gang, why)
                    continue
                try:
                    # Pre-load the stop decision so the wedged incarnation
                    # self-fences at its next report boundary.
                    worker.send(
                        {"type": "fence", "trial_id": trial.trial_id,
                         "incarnation": trial.incarnation}
                    )
                except OSError:
                    worker.alive = False
                requeue_lost(trial, why, counter="stall_requeues")
                launch_ready()
        # Gang bootstrap deadlines: a gang stuck preparing (coordinator
        # port never reserved) or bootstrapping (members never all joined)
        # past its deadline becomes a flight dump NAMING the absent
        # process ids, then a teardown + requeue.
        for gang in list(gangs.values()):
            if gang.prepare_expired() or gang.join_expired():
                absent = gang.absent_ids()
                liveness["gang_bootstrap_timeouts"] += 1
                obs_lib.dump_flight_recorder(
                    f"gang_bootstrap_timeout_{gang.trial_id}",
                    extra={
                        "gang": gang.describe(),
                        "absent_process_ids": absent,
                        "state": gang.state,
                    },
                )
                teardown_gang(
                    gang,
                    f"gang bootstrap deadline expired in state "
                    f"{gang.state!r}; absent process ids {absent}",
                )

    # ---- main loop ----
    exp_span = obs_lib.span("experiment", {"name": name})
    exp_span.__enter__()
    clean_end = False
    try:
        # Inside the try so every setup is paired with on_experiment_end in
        # the finally (a ProfilerCallback's process-global trace must stop
        # even when the loop dies early); setup errors propagate, matching
        # tune.run — a misconfigured observer should fail loudly up front.
        for cb in callbacks:
            cb.setup(store.root, metric, mode)
        while True:
            while not lifecycle.exhausted() and len(pending) < sum(
                max(w.free_slots, 0) for w in pool
            ) + 2:
                if lifecycle.create_trial() is None:
                    break
            launch_ready()

            active = bool(pending) or any(w.running for w in pool)
            if not active:
                # (With elastic_listen, pending only stays empty once the
                # sample budget is exhausted — trial creation above refills
                # it — so waiting for joiners happens in the common
                # events.get below, not here.)
                if lifecycle.exhausted():
                    break
                if not any(w.alive for w in pool) and elastic_server is None:
                    break
                continue
            alive_workers = sum(1 for w in pool if w.alive)
            if pending and elastic_server is None and (
                alive_workers == 0
                or (processes_per_trial > 1
                    and alive_workers < processes_per_trial
                    and not any(w.running for w in pool))
            ):
                # Cluster died (or shrank below one gang's width with
                # nothing left in flight) with work outstanding and no way
                # to regrow.
                why = (
                    "no live workers" if alive_workers == 0 else
                    f"only {alive_workers} live workers for "
                    f"processes_per_trial={processes_per_trial}"
                )
                for trial in list(pending):
                    pending.remove(trial)
                    trial.error = why
                    safe_cb("on_trial_error", trial, trial.error)
                    lifecycle.finish(trial, TrialStatus.ERROR)
                break

            enforce_liveness()
            try:
                event = events.get(timeout=0.5)
            except queue.Empty:
                safe_cb("on_heartbeat")
                continue

            if event[0] == "worker_joined":
                add_worker(event[1])
                log(f"worker {event[1].address} joined "
                    f"({event[1].slots} slots)")
                launch_ready()
                continue

            if event[0] == "worker_dead":
                worker = event[1]
                if getattr(worker, "_death_handled", False):
                    continue
                worker._death_handled = True
                worker.alive = False
                lost = [by_id[tid] for tid in list(worker.running)]
                log(
                    f"worker {worker.address} died with "
                    f"{len(lost)} running trials"
                )
                for trial in lost:
                    gang = gang_by_trial.get(trial.trial_id)
                    if gang is not None:
                        teardown_gang(
                            gang,
                            f"worker {worker.address} died (gang member)",
                        )
                        continue
                    release(trial)
                    err = f"worker {worker.address} died"
                    safe_cb("on_trial_error", trial, err)
                    lifecycle.fail_trial(trial, err)
                continue

            _, worker, msg = event
            mtype = msg.get("type")
            # Any frame from a suspect worker is proof of life — the
            # partition healed (or the hang cleared); decide rejoin/close.
            revive_if_suspect(worker)

            if mtype == "heartbeat":
                continue  # liveness only; last_seen already stamped

            if mtype == "artifact_get":
                # Compile-artifact origin: answer from the registry (None =
                # miss; the worker compiles locally and publishes).  Served
                # inline on the event loop — payloads are cache entries
                # (KBs..MBs), not checkpoints.
                files = (
                    artifacts.fetch(msg.get("key", ""))
                    if artifact_origin else None
                )
                try:
                    worker.send({
                        "type": "artifact",
                        "key": msg.get("key", ""),
                        "files": files,
                    })
                except OSError:
                    worker.alive = False
                continue

            if mtype == "artifact_put":
                if artifact_origin:
                    artifacts.publish(
                        msg.get("key", ""), msg.get("files") or {}
                    )
                continue

            if mtype == "gang_port":
                # Member 0's supervisor reserved the coordinator port:
                # assign process ids and spawn every member.
                gang = gangs.get(msg.get("gang_id", ""))
                if gang is None or gang.state != "preparing":
                    continue  # torn down while the reply was in flight
                trial = by_id.get(gang.trial_id)
                if trial is None:
                    continue
                chost = gang.coordinator.worker.address.rsplit(":", 1)[0]
                gang.coordinator_address = f"{chost}:{int(msg['port'])}"
                span = trial_spans.get(gang.trial_id)
                spawn_failed = False
                for m in gang.members:
                    try:
                        m.worker.send({
                            "type": "run_gang_member",
                            "trial_id": gang.trial_id,
                            "incarnation": gang.incarnation,
                            "gang_id": gang.gang_id,
                            "process_id": m.process_id,
                            "num_processes": gang.num_processes,
                            "coordinator_address":
                                gang.coordinator_address,
                            "local_device_count": gang_devices_per_member,
                            "slot": m.slot,
                            "config": dict(trial.config),
                            "trainable": trainable_spec,
                            "checkpoint_dir": store.checkpoint_dir(trial),
                            "checkpoint_format": store.checkpoint_format,
                            "restore_path": trial.restore_path,
                            "start_iteration": trial.training_iteration,
                            "artifact_origin": artifact_origin,
                            "join_deadline_s": float(gang_join_deadline_s),
                            "obs": obs_lib.trace_context_frame(
                                parent=span.context
                                if span is not None else None
                            ),
                        })
                    except OSError:
                        m.worker.alive = False
                        spawn_failed = True
                        teardown_gang(
                            gang,
                            f"worker {m.worker.address} died at gang spawn",
                        )
                        break
                if not spawn_failed:
                    gang.arm_join_deadline(gang_join_deadline_s)
                continue

            if mtype == "gang_joined":
                gang = gangs.get(msg.get("gang_id", ""))
                if gang is not None and int(
                    msg.get("incarnation", -1)
                ) == gang.incarnation:
                    if gang.mark_joined(int(msg.get("process_id", -1))):
                        log(
                            f"gang {gang.gang_id} fully joined "
                            f"({gang.num_processes} processes)"
                        )
                        obs_lib.event("gang_running", {
                            "gang_id": gang.gang_id,
                        })
                continue

            if mtype == "gang_member_done":
                gang = gangs.get(msg.get("gang_id", ""))
                if gang is None or int(
                    msg.get("incarnation", -1)
                ) != gang.incarnation:
                    liveness["fenced_frames"] += 1
                    continue
                member = gang.member(int(msg.get("process_id", -1)))
                if msg.get("ok"):
                    # A non-coordinator member finished its SPMD program;
                    # its slot frees now, the trial completes when the
                    # coordinator's terminal lands.
                    if member is not None:
                        member.done = True
                        member.worker.running.pop(gang.trial_id, None)
                else:
                    tb = msg.get("traceback") or "gang member failed"
                    obs_lib.dump_flight_recorder(
                        f"gang_member_failure_{gang.trial_id}",
                        extra={
                            "gang": gang.describe(),
                            "process_id": msg.get("process_id"),
                            "traceback_tail": tb[-1500:],
                        },
                    )
                    teardown_gang(
                        gang,
                        f"gang member {msg.get('process_id')} on "
                        f"{worker.address} failed: {tb.splitlines()[-1]}",
                    )
                continue

            trial = by_id.get(msg.get("trial_id", ""))
            if trial is None:
                continue

            if mtype == "trial_beat":
                # Piggybacked tune.heartbeat(): per-trial progress without
                # a result.  Only the CURRENT incarnation's beats count — a
                # fenced zombie must not keep its replacement looking live.
                if watchdog is not None and (
                    assignment.get(trial.trial_id) is worker
                    and int(msg.get("incarnation", trial.incarnation))
                    == trial.incarnation
                ):
                    watchdog.beat(trial.trial_id)
                continue

            frame_inc = int(msg.get("incarnation", trial.incarnation))
            if (
                assignment.get(trial.trial_id) is not worker
                or frame_inc != trial.incarnation
            ):
                # Stale frame: this incarnation was requeued away (lease
                # expiry, stall fence) while the frame was in flight or
                # buffered behind a partition — possibly superseded on this
                # very worker.  Never apply it — and for results, answer
                # "stop" TO THAT INCARNATION so the zombie self-fences
                # instead of grinding on.
                liveness["fenced_frames"] += 1
                if mtype == "result":
                    try:
                        worker.send(
                            {
                                "type": "decision",
                                "trial_id": trial.trial_id,
                                "incarnation": frame_inc,
                                "decision": "stop",
                            }
                        )
                    except OSError:
                        worker.alive = False
                continue

            if mtype == "result":
                if watchdog is not None:
                    watchdog.beat(trial.trial_id)
                if msg.get("checkpoint_path"):
                    trial.latest_checkpoint = msg["checkpoint_path"]
                    trial.latest_checkpoint_iteration = int(
                        msg["metrics"].get(
                            "training_iteration", trial.training_iteration + 1
                        )
                    )
                decision = lifecycle.process_result(
                    trial, msg["metrics"], extra={"hostname": worker.hostname}
                )
                plan = chaos_lib.active_plan()
                if plan is not None:
                    # Deterministic partition injection: keyed to the Nth
                    # processed result frame, not wall time.
                    due = plan.poll_worker_partition()
                    if due is not None:
                        idx, duration = due
                        if 0 <= idx < len(pool):
                            log(
                                f"chaos: partitioning worker "
                                f"{pool[idx].address} for {duration:.1f}s"
                            )
                            pool[idx].partition(duration)
                # Decision frame FIRST: the worker's report() blocks on it,
                # so a slow observer must never sit between a result and
                # its decision (same rule as runner.py's trial threads).
                try:
                    worker.send(
                        {
                            "type": "decision",
                            "trial_id": trial.trial_id,
                            "incarnation": frame_inc,
                            "decision": decision,
                        }
                    )
                except OSError:
                    worker.alive = False  # reader will requeue its trials
                safe_cb("on_trial_result", trial, trial.last_result)
                # Forensics: scheduler/searcher debug snapshot at report
                # boundaries, throttled (same cadence as tune.run).
                if time.time() - last_sched_persist[0] > 2.0:
                    last_sched_persist[0] = time.time()
                    store.write_state(trials, extra={
                        "scheduler": scheduler_debug_block(searcher, sched),
                    })

            elif mtype == "complete":
                if msg.get("obs_counters"):
                    # Head-node aggregation frame: the worker's whole
                    # registry snapshot (latest wins per worker; totals
                    # are summed across workers at teardown).
                    worker_obs[worker.address] = msg["obs_counters"]
                gang = gang_by_trial.get(trial.trial_id)
                if gang is not None:
                    # Coordinator finished: reap any member whose own
                    # terminal has not landed yet (the SPMD program ended
                    # everywhere — a straggler here is teardown, not
                    # progress) so slots free deterministically.
                    for m in gang.members[1:]:
                        if not m.done:
                            try:
                                m.worker.send({
                                    "type": "gang_abort",
                                    "trial_id": gang.trial_id,
                                    "incarnation": gang.incarnation,
                                })
                            except OSError:
                                m.worker.alive = False
                release(trial)
                # complete_trial returns True when the scheduler REQUEUEs
                # (PBT exploit): the trial keeps living, so no completion
                # event — same guard as tune.run.
                if not lifecycle.complete_trial(trial):
                    safe_cb("on_trial_complete", trial)
                store.write_state(trials, extra={
                    "scheduler": scheduler_debug_block(searcher, sched),
                })

            elif mtype == "error":
                if msg.get("obs_counters"):
                    worker_obs[worker.address] = msg["obs_counters"]
                trial.error = msg.get("traceback", "unknown error")
                gang = gang_by_trial.get(trial.trial_id)
                if gang is not None:
                    # Coordinator errored: the whole gang goes — peers may
                    # already be wedged in a collective against the dead
                    # program.  teardown_gang routes through requeue_lost
                    # (quarantine + newest valid generation + retry
                    # budget).
                    teardown_gang(
                        gang,
                        f"gang coordinator failed: "
                        f"{trial.error.splitlines()[-1]}",
                    )
                    store.write_state(trials)
                    continue
                release(trial)
                safe_cb("on_trial_error", trial, trial.error)
                lifecycle.fail_trial(trial, trial.error)
                store.write_state(trials, extra={
                    "scheduler": scheduler_debug_block(searcher, sched),
                })
        # Reaching here means the loop drained normally: only then is the
        # journal committed in the finally below — an exception leaves it
        # uncommitted so resume="auto" picks the run back up.
        clean_end = True
    finally:
        exp_span.__exit__(None, None, None)
        wall = time.time() - start_time
        if elastic_server is not None:
            try:
                elastic_server.close()  # unblocks the accept thread
            except OSError:
                pass
            # Workers whose join was queued but never pooled: close them so
            # their join_driver returns (EOF) instead of blocking forever.
            while True:
                try:
                    event = events.get_nowait()
                except queue.Empty:
                    break
                if event[0] == "worker_joined":
                    event[1].close()
        for w in pool:
            # Plain close for joined workers unless shutdown was requested:
            # their join_driver returns on EOF, and an operator loop around
            # it can then re-join the next driver.
            w.close(shutdown=shutdown_workers)
        extra: Dict[str, Any] = {"wall_clock_s": wall}
        if watchdog is not None or any(liveness.values()):
            counters = dict(liveness)
            if watchdog is not None:
                counters.update(
                    {
                        k: v
                        for k, v in watchdog.snapshot().items()
                        if k not in ("stalls_detected",)  # driver-counted
                    }
                )
            extra["liveness"] = counters
        plan = chaos_lib.active_plan()
        if plan is not None:
            extra["injected_faults"] = plan.snapshot()
        # Driver-side checkpoint accounting (restores during requeue and
        # fallback walks; worker-side saves count on the workers).
        ckpt_counters = get_metrics().delta_since(ckpt_metrics_base)
        if any(ckpt_counters.values()):
            extra["checkpoint"] = ckpt_counters
        # Compile block: head-side tracker/counter deltas + the origin
        # registry ("<= K head-side compiles for K shape classes" reads
        # origin_publishes; worker-side fetch counters stay worker-local).
        reg = artifacts.snapshot()
        extra["compile"] = {
            **compilecache.state_block(
                compile_tracker_base, compile_counters_base
            ),
            **{k: v - artifacts_base.get(k, 0) for k, v in reg.items()
               if k != "distinct_keys"},
            "distinct_keys": reg["distinct_keys"],
        }
        from distributed_machine_learning_tpu.tune.schedulers.pbt import (
            pbt_state_block,
        )

        pbt_block = pbt_state_block(sched)
        if pbt_block is not None:
            extra["pbt"] = pbt_block
        # Observability teardown: close straggler dispatch spans, merge
        # the per-process trace files (driver + every worker that shares
        # the storage), publish the obs counter delta AND the cluster-wide
        # aggregation of the workers' registry snapshots — the head-node
        # view the six scattered counter families never had.
        for span in trial_spans.values():
            span.end()
        trial_spans.clear()
        merged_trace = None
        if trace_dir is not None:
            obs_lib.flush()
            merged_trace = obs_lib.merge_trace_dir(trace_dir)
            obs_lib.shutdown()
        # Control-plane forensics: final scheduler/searcher snapshot + the
        # journal counters the crash-recovery runbook keys off
        # (docs/operations.md — head_incarnations / journal_replays /
        # duplicate_reports_suppressed / fenced_head_frames, the last
        # arriving worker-side via the obs cluster aggregation).
        extra["scheduler"] = scheduler_debug_block(searcher, sched)
        extra["journal"] = {
            "head_incarnation": head_incarnation,
            "decisions": journal.n,
            "journal_replays": (
                (replay.replays if replay is not None else 0)
                + (1 if journal_resume else 0)
            ),
            "duplicate_reports_suppressed":
                lifecycle.duplicate_reports_suppressed,
            "committed": clean_end,
        }
        obs_delta = obs_lib.get_registry().delta_since(obs_counters_base)
        obs_block: Dict[str, Any] = {
            k: v for k, v in obs_delta.items() if v
        }
        if merged_trace is not None:
            obs_block["trace"] = merged_trace
        if worker_obs:
            obs_block["cluster"] = obs_lib.aggregate_scalars(worker_obs)
            obs_block["cluster_workers"] = len(worker_obs)
        if obs_block:
            extra["obs"] = obs_block
        obs_lib.get_registry().unregister_family("liveness")
        obs_lib.set_dump_dir(prev_dump_dir)
        try:
            store.write_state(trials, extra=extra)
            store.close()
        except Exception as exc:  # noqa: BLE001
            log(f"store teardown failed: {exc!r}")
        # Commit AFTER the final state write (resume="auto" stops looking
        # at this experiment the moment the commit record lands).
        try:
            if clean_end:
                journal.commit()
            journal.close()
        except Exception as exc:  # noqa: BLE001
            log(f"journal teardown failed: {exc!r}")
        counter_scalars = {
            **{f"liveness/{k}": v
               for k, v in (extra.get("liveness") or {}).items()},
            **{f"faults/{k}": v
               for k, v in (extra.get("injected_faults") or {}).items()},
            **{f"checkpoint/{k}": v
               for k, v in (extra.get("checkpoint") or {}).items()},
            **{f"compile/{k}": v
               for k, v in (extra.get("compile") or {}).items()},
            **{f"pbt/{k}": v
               for k, v in (extra.get("pbt") or {}).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)},
            **{f"obs/{k}": v
               for k, v in (extra.get("obs") or {}).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)},
            **{f"journal/{k}": v
               for k, v in (extra.get("journal") or {}).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)},
        }
        if counter_scalars:
            safe_cb("on_experiment_counters", counter_scalars)
        safe_cb("on_experiment_end", trials, wall)

    analysis = ExperimentAnalysis(
        trials, metric=metric, mode=mode, root=store.root, wall_clock_s=wall
    )
    log(
        f"experiment {name}: {analysis.num_terminated()}/{len(trials)} trials "
        f"terminated in {wall:.1f}s across {len(pool)} workers"
        + (f" ({len(pool) - len(workers)} joined elastically)"
           if len(pool) > len(workers) else "")
    )
    return analysis


# --------------------------------------------------------------------------
# local worker spawning (dev / tests / single-machine multi-process)
# --------------------------------------------------------------------------


def start_local_workers(
    n: int,
    slots: int = 2,
    env: Optional[Dict[str, str]] = None,
    timeout: float = 180.0,
) -> Tuple[List[subprocess.Popen], List[str]]:
    """Spawn ``n`` worker supervisor subprocesses on localhost.

    Each worker writes its bound address to a ready-file; returns
    (processes, addresses). Caller terminates the processes (or
    ``run_distributed`` shuts them down via the protocol).
    """
    import tempfile

    procs: List[subprocess.Popen] = []
    addrs: List[str] = []
    measured_spawns: List[float] = []
    for i in range(n):
        fd, ready = tempfile.mkstemp(prefix=f"dml_worker_{i}_")
        os.close(fd)
        os.unlink(ready)
        child_env = dict(os.environ)
        if env:
            child_env.update(env)
        log_path = os.path.join(
            tempfile.gettempdir(), f"dml_worker_{os.getpid()}_{i}.log"
        )
        log_f = open(log_path, "w")
        proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "distributed_machine_learning_tpu.tune.cluster",
                "--host",
                "127.0.0.1",
                "--port",
                "0",
                "--slots",
                str(slots),
                "--ready-file",
                ready,
            ],
            env=child_env,
            stdout=log_f,
            stderr=subprocess.STDOUT,
        )
        log_f.close()
        proc.log_path = log_path  # type: ignore[attr-defined]
        procs.append(proc)
        # The ready deadline scales from the measured spawn of earlier
        # workers: host load stretches every spawn alike, so worker 0's
        # actual latency is a better budget predictor for worker 1 than
        # any fixed constant (the worker-startup deadline flake).
        spawn_t0 = time.monotonic()
        budget = max(
            float(timeout),
            STARTUP_GRACE_SCALE * max(measured_spawns, default=0.0),
        )
        deadline = spawn_t0 + budget
        # Poll for a COMPLETE address, not mere file existence: the worker
        # creates the ready file and then writes "host:port\n" — reading
        # in between hands the driver an empty address (observed flake).
        addr = ""
        while ":" not in addr:
            if proc.poll() is not None:
                raise RuntimeError(f"worker {i} exited rc={proc.returncode}")
            if time.monotonic() > deadline:
                raise TimeoutError(f"worker {i} did not become ready")
            if os.path.exists(ready):
                with open(ready) as f:
                    addr = f.read().strip()
                if ":" in addr:
                    break
            time.sleep(0.05)
        measured_spawns.append(time.monotonic() - spawn_t0)
        addrs.append(addr)
        os.unlink(ready)
    return procs, addrs


def _main(argv: Optional[Sequence[str]] = None):
    import argparse

    parser = argparse.ArgumentParser(description="dml-tpu host trial supervisor")
    parser.add_argument(
        "--host", default="127.0.0.1",
        help="bind address; use a routable address only on a trusted network "
        "and set DML_CLUSTER_SECRET (see module docstring)",
    )
    parser.add_argument("--port", type=int, default=7711)
    parser.add_argument("--slots", type=int, default=None)
    parser.add_argument("--ready-file", default=None)
    parser.add_argument(
        "--join", default=None, metavar="DRIVER_HOST:PORT",
        help="instead of listening, dial a driver's elastic_listen endpoint "
        "and serve it (elastic scale-up); re-dials until the driver sends "
        "shutdown",
    )
    parser.add_argument(
        "--join-retry-s", type=float, default=5.0,
        help="with --join: seconds between re-dial attempts",
    )
    args = parser.parse_args(argv)
    if args.join:
        while True:
            try:
                if join_driver(args.join, slots=args.slots):
                    break  # explicit shutdown
            except (ConnectionError, OSError) as exc:
                print(f"[worker] driver unreachable ({exc}); retrying",
                      flush=True)
            time.sleep(args.join_retry_s)
    else:
        serve_worker(
            args.host, args.port, slots=args.slots, ready_file=args.ready_file
        )


if __name__ == "__main__":
    _main()
