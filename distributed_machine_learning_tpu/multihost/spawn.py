"""Worker-supervisor side of gang trials: spawn + relay one gang member.

``jax.distributed.initialize`` must run BEFORE the backend initializes,
and a long-lived worker supervisor enumerated its devices at startup — so
each gang member runs in a FRESH subprocess
(``multihost/_gang_child.py``), exactly like the process-per-trial
executor's children, speaking the same length-prefixed pickle protocol
over binary stdio:

    parent -> child   {"trial_id", "incarnation", "config",
                       "trainable": bytes, "restore_path",
                       "checkpoint_dir", "checkpoint_format",
                       "start_iteration", "obs"}          (init)
    child  -> parent  ("joined", describe_dict)   (gang bootstrap done)
    child  -> parent  ("result", metrics, ckpt_path|None)  (coordinator)
    parent -> child   ("decision", "continue"|"stop"|"pause")
    child  -> parent  ("beat",)                   (coordinator heartbeat)
    child  -> parent  ("complete",) | ("error", traceback_str)

The supervisor's relay thread (``tune/cluster.py``) forwards these up the
control plane and routes the head's decisions back down.  ``kill()`` is
the gang-teardown path: SIGKILL, because a member wedged in a collective
whose peer died will not honour SIGTERM from native code.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
from typing import Dict, List, Optional

from distributed_machine_learning_tpu.multihost.bootstrap import (
    GANG_SPEC_ENV,
    GangSpec,
)
from distributed_machine_learning_tpu.tune._process_child import (
    read_frame,
    write_frame,
)


def member_child_env(
    spec: GangSpec,
    devices: Optional[List] = None,
    platform: Optional[str] = None,
    base_env: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """The spawn environment for one gang member.

    Device visibility is fixed HERE (the TPU analogue of per-actor
    ``CUDA_VISIBLE_DEVICES``): on TPU the leased local group becomes
    ``TPU_VISIBLE_CHIPS``; on CPU the member gets exactly
    ``spec.local_device_count`` virtual devices.  Any inherited
    ``JAX_COORDINATOR_*`` env is stripped — the :class:`GangSpec` is the
    single source of bootstrap truth for a gang child.
    """
    env = dict(base_env if base_env is not None else os.environ)
    env[GANG_SPEC_ENV] = spec.to_env()
    for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "JAX_PROCESS_ID"):
        env.pop(var, None)
    platform = platform or env.get("JAX_PLATFORMS", "")
    if platform.startswith("tpu") and devices:
        env["TPU_VISIBLE_CHIPS"] = ",".join(
            str(getattr(d, "id", i)) for i, d in enumerate(devices)
        )
    else:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            env.get("XLA_FLAGS", ""),
        ).strip()
        env["XLA_FLAGS"] = (
            flags
            + f" --xla_force_host_platform_device_count="
              f"{spec.local_device_count}"
        ).strip()
    return env


class GangChildHandle:
    """One spawned gang member and its frame pipes.

    ``module`` selects the child entrypoint: the default trains one gang
    trial (``multihost/_gang_child.py``); the serving plane spawns its
    members with ``serve/_gang_member.py`` — same spec env, same frame
    pipes, same SIGKILL teardown."""

    DEFAULT_MODULE = "distributed_machine_learning_tpu.multihost._gang_child"

    def __init__(
        self,
        spec: GangSpec,
        init_msg: Dict,
        devices: Optional[List] = None,
        platform: Optional[str] = None,
        env: Optional[Dict[str, str]] = None,
        module: Optional[str] = None,
    ):
        self.spec = spec
        self.proc = subprocess.Popen(
            [sys.executable, "-m", module or self.DEFAULT_MODULE],
            env=env if env is not None else member_child_env(
                spec, devices, platform
            ),
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL if os.environ.get(
                "DML_GANG_CHILD_QUIET"
            ) else None,
        )
        write_frame(self.proc.stdin, init_msg)

    def read(self):
        """Next child frame; raises EOFError when the child is gone."""
        return read_frame(self.proc.stdout)

    def send_decision(self, decision: str) -> None:
        write_frame(self.proc.stdin, ("decision", decision))

    def kill(self) -> None:
        """Gang teardown: SIGKILL (a member wedged in a collective whose
        peer died sits in native code; SIGTERM may never be delivered)."""
        if self.proc.poll() is None:
            try:
                self.proc.kill()
            except OSError:
                pass

    def wait(self, timeout: Optional[float] = None) -> Optional[int]:
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None

    @property
    def returncode(self) -> Optional[int]:
        return self.proc.poll()
