"""Serving gang member: one process of a TP-sharded inference replica.

Spawned by :class:`~distributed_machine_learning_tpu.serve.gang.GangReplica`
with its :class:`~...multihost.bootstrap.GangSpec` in the environment and
the same frame pipes the training gangs use
(``multihost/spawn.GangChildHandle`` with this module as the entrypoint):

    parent -> child   {"bundle_dir", "max_bucket", "buckets",
                       "warmup_sample"|None, "incarnation", "obs"}  (init)
    child  -> parent  ("joined", describe_dict)   (gang bootstrap done)
    child  -> parent  ("ready", stats)            (bundle loaded + warmed)
    parent -> child   ("predict", x_np)                       (coordinator)
    child  -> parent  ("result", out_np, stats)               (coordinator)
    parent -> child   ("warmup", sample_np)                   (coordinator)
    child  -> parent  ("warmed", stats)                       (coordinator)
    parent -> child   ("stop",)                               (coordinator)
    child  -> parent  ("complete",) | ("error", traceback_str)

**Only the coordinator (gang process 0) talks to the parent** after
bootstrap.  Every predict round is collective: the coordinator broadcasts
a fixed-shape int64 header (opcode + batch shape + dtype code + round
number) through ``runtime.broadcast_from_coordinator``, then the batch
itself; every member runs the SAME engine call over the process-spanning
``runtime.serving_mesh`` — identical padding, identical bucket, identical
:func:`~...compilecache.gang_program_key` — and only the coordinator reads
the replicated output back and answers up the pipe.  Warmup rounds ship
the header only (members synthesize zeros), so off-path warming never
moves batch bytes.

**Chaos reaches serving gangs.**  ``DML_CHAOS_PLAN`` rides the spawn env:
``gang_bootstrap_hang`` stalls THIS member before the join (its peers'
barrier deadline names it absent in a flight dump), and
``kill_gang_member_at_request`` hard-exits it at the start of a scheduled
predict round — the mid-traffic member death the parent's teardown/
rebuild/redispatch path exists for.
"""

from __future__ import annotations

import os
import sys
import traceback

from distributed_machine_learning_tpu.tune._process_child import (
    read_frame,
    write_frame,
)

OP_STOP = 0
OP_PREDICT = 1
OP_WARMUP = 2

# Wire dtype codes for the broadcast header (batches are numeric arrays;
# anything outside this table is rejected at the HTTP layer long before a
# gang sees it).
DTYPE_CODES = {"float32": 0, "float64": 1, "int32": 2, "int64": 3,
               "bfloat16": 4, "float16": 5}
CODE_DTYPES = {v: k for k, v in DTYPE_CODES.items()}

MAX_NDIM = 6
HEADER_LEN = 4 + MAX_NDIM  # opcode, round_n, ndim, dtype_code, dims...


def encode_header(opcode: int, round_n: int, shape, dtype) -> "np.ndarray":
    import numpy as np

    name = np.dtype(dtype).name
    if name not in DTYPE_CODES:
        raise ValueError(f"unsupported serving dtype: {name}")
    if len(shape) > MAX_NDIM:
        raise ValueError(f"batch rank {len(shape)} > {MAX_NDIM}")
    header = np.zeros((HEADER_LEN,), dtype=np.int64)
    header[0] = opcode
    header[1] = round_n
    header[2] = len(shape)
    header[3] = DTYPE_CODES[name]
    for i, d in enumerate(shape):
        header[4 + i] = int(d)
    return header


def decode_header(header) -> tuple:
    import numpy as np

    header = np.asarray(header)
    opcode = int(header[0])
    round_n = int(header[1])
    ndim = int(header[2])
    dtype = CODE_DTYPES[int(header[3])]
    shape = tuple(int(d) for d in header[4: 4 + ndim])
    return opcode, round_n, shape, dtype


def main() -> None:
    stdin = sys.stdin.buffer
    stdout = sys.stdout.buffer
    sys.stdout = sys.stderr  # user prints must not corrupt the frame stream

    try:
        init = read_frame(stdin)
    except EOFError:
        return  # parent died before dispatching

    try:
        from distributed_machine_learning_tpu import chaos
        from distributed_machine_learning_tpu.multihost.bootstrap import (
            GangSpec,
        )

        chaos.activate_from_env()
        spec = GangSpec.from_env()
        if spec is None:
            raise RuntimeError(
                "serve gang member spawned without DML_GANG_SPEC"
            )

        import jax

        # Decide from the ENV only — jax.default_backend() would
        # initialize the backend, which must not happen before
        # jax.distributed.initialize below.
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # Cross-process CPU collectives need a backend; gloo ships in jaxlib.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")

        from distributed_machine_learning_tpu import obs
        from distributed_machine_learning_tpu.compilecache import (
            enable_persistent_cache,
        )
        from distributed_machine_learning_tpu.multihost import (
            bootstrap,
            runtime,
        )

        obs.configure_from_frame(
            init.get("obs"),
            label=f"servegang{spec.process_id}-{os.getpid()}",
        )
        incarnation = int(init.get("incarnation", 1))
        plan = chaos.active_plan()
        if plan is not None:
            # The straggler-bootstrap fault: THIS member stalls before the
            # join, its peers' barrier deadline expires and the flight
            # dump names this process id absent.
            plan.maybe_gang_bootstrap_hang(spec.process_id, incarnation)
        described = bootstrap.join_gang(spec)
        enable_persistent_cache()
        write_frame(stdout, ("joined", described))

        import numpy as np

        from distributed_machine_learning_tpu.serve.engine import (
            InferenceEngine,
        )
        from distributed_machine_learning_tpu.serve.export import load_bundle

        coordinator = runtime.is_coordinator()
        mesh = runtime.serving_mesh()
        # Every member loads the SAME host tree from shared storage and
        # places exactly its addressable shards (the ckpt resharding
        # restore applied to a bundle) — the source topology recorded in
        # the manifest never constrains the serving one.
        bundle = load_bundle(init["bundle_dir"], mesh=mesh)
        engine = InferenceEngine(
            bundle,
            max_bucket=int(init.get("max_bucket", 256)),
            buckets=init.get("buckets"),
            mesh=mesh,
        )

        def _warm(shape, dtype) -> None:
            # Warmup is collective too; members synthesize the sample from
            # the header so only 80 bytes cross the pipe/broadcast.
            engine.warmup(np.zeros(shape, dtype=dtype))

        def _stats() -> dict:
            return {
                "topology": runtime.process_topology(),
                "source_topology": bundle.source_topology,
                **engine.program_stats(),
            }

        warm_sample = init.get("warmup_sample")
        if warm_sample is not None:
            warm_sample = np.asarray(warm_sample)
            _warm(warm_sample.shape, warm_sample.dtype)
        write_frame(stdout, ("ready", _stats()))

        round_n = 0
        while True:
            if coordinator:
                msg = read_frame(stdin)
                op = msg[0]
                if op == "stop":
                    runtime.broadcast_from_coordinator(
                        encode_header(OP_STOP, round_n, (), "float32")
                    )
                    break
                x = np.asarray(msg[1])
                opcode = OP_PREDICT if op == "predict" else OP_WARMUP
                round_n += 1
                header = runtime.broadcast_from_coordinator(
                    encode_header(opcode, round_n, x.shape, x.dtype)
                )
                _, _, shape, dtype = decode_header(header)
            else:
                # Non-coordinators contribute zeros; broadcast_one_to_all
                # returns the coordinator's header everywhere.
                header = runtime.broadcast_from_coordinator(
                    np.zeros((HEADER_LEN,), dtype=np.int64)
                )
                opcode, round_n, shape, dtype = decode_header(header)
                if opcode == OP_STOP:
                    break
            if opcode == OP_WARMUP:
                _warm(shape, dtype)
                if coordinator:
                    write_frame(stdout, ("warmed", _stats()))
                continue
            # Predict round.  The scheduled member death lands HERE —
            # before the batch broadcast, so the survivors wedge in the
            # round's first collective exactly like a preempted host.
            if plan is not None:
                plan.maybe_kill_gang_member(
                    round_n, spec.process_id, incarnation
                )
            if coordinator:
                batch = runtime.broadcast_from_coordinator(x)
            else:
                batch = runtime.broadcast_from_coordinator(
                    np.zeros(shape, dtype=dtype)
                )
            out = engine.predict(np.asarray(batch))
            if coordinator:
                write_frame(stdout, ("result", out, _stats()))
        obs.flush()  # BEFORE the terminal frame: the parent may
        write_frame(stdout, ("complete",))  # reap us right after it
    except BaseException:  # noqa: BLE001 - everything goes to the parent
        try:
            write_frame(stdout, ("error", traceback.format_exc()))
        except (OSError, ValueError):
            pass


if __name__ == "__main__":
    main()
