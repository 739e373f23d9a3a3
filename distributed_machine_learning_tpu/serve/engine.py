"""Jit-compiled inference programs with padded-shape bucketing.

Serving traffic arrives at arbitrary batch sizes; jit would compile one XLA
program per distinct shape — unbounded compile work on the request path,
the serving analogue of the HPO compile-amortization problem
(``utils/compile_cache.py``).  The engine instead pads every batch up to a
small fixed grid of power-of-two buckets, so steady-state traffic runs a
handful of compiled programs and a request's cost is execution only.

One engine serves one bundle (one architecture cohort); its program cache
is keyed by ``(bucket, trailing feature shape, dtype)``.  ``warmup()``
pre-compiles the grid so the first real request never pays a compile, and
``program_stats()`` exposes the counters the acceptance check reads
("zero recompiles after warmup").
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from distributed_machine_learning_tpu import obs
from distributed_machine_learning_tpu.analysis.locks import named_lock
from distributed_machine_learning_tpu.compilecache import (
    ExecutableCache,
    enable_persistent_cache,
    gang_program_key,
    get_tracker,
    program_key,
)
from distributed_machine_learning_tpu.serve.export import ServableBundle

DEFAULT_MAX_BUCKET = 1024


def bucket_sizes(max_bucket: int = DEFAULT_MAX_BUCKET) -> Tuple[int, ...]:
    """The power-of-two padding grid: 1, 2, 4, ... max_bucket."""
    sizes = []
    b = 1
    while b < max_bucket:
        sizes.append(b)
        b *= 2
    sizes.append(max_bucket)
    return tuple(sizes)


class InferenceEngine:
    """Compiled forward pass over a bundle's params, bucketed by batch size.

    Thread-safe: the program cache is lock-guarded.
    """

    def __init__(
        self,
        bundle: ServableBundle,
        max_bucket: int = DEFAULT_MAX_BUCKET,
        buckets: Optional[Sequence[int]] = None,
        device=None,
        persistent_cache: bool = True,
        aot_cache: bool = True,
        mesh=None,
    ):
        if persistent_cache:
            # Same on-disk XLA cache as tune: a server restart (or a second
            # replica process) skips backend compilation for programs any
            # earlier process already built.
            enable_persistent_cache()
        self.bundle = bundle
        self.model = bundle.build_model()
        self._variables = bundle.variables
        # Storage precision from the manifest (quant/): selects the
        # dequant-fused apply path and splits program identity, so an f32
        # and an int8 replica of the same architecture never share (or
        # clobber) a compiled program.
        self._precision = getattr(bundle, "precision", "f32")
        self._device = device
        # Mesh mode (serve/gang.py): programs lower over a named —
        # possibly process-spanning — mesh with replicated outputs, keyed
        # by gang_program_key so process topology, mesh shape, and rule
        # fingerprint all split program identity.  The bundle's variables
        # must already be placed on the mesh (load_bundle(mesh=...)).
        self._mesh = mesh
        self._buckets = tuple(sorted(set(buckets or bucket_sizes(max_bucket))))
        self._flag_name: Optional[str] = None
        self._lock = named_lock("serve.engine")
        self._programs: Dict[Tuple, Any] = {}
        self._program_hits = 0
        self._tracker = get_tracker()
        # AOT tier (compile-once tentpole): bucket programs resolve through
        # the ExecutableCache, keyed by (bundle shape class, padded input
        # shape, dtype, device) — a breaker-triggered replica restart or a
        # second serving process DESERIALIZES the finished executable
        # instead of re-tracing and re-compiling (the persistent XLA cache
        # only spares the backend stage; this spares all three).  On a
        # process-spanning mesh executable serialization is NOT portable
        # (the payload bakes in a device assignment only this exact gang
        # incarnation has), so gang members skip the AOT tier and lean on
        # the persistent XLA cache — same zero-backend-compile outcome,
        # honest trace/lower cost (the PR-14 gang-trial precedent).
        multiproc = mesh is not None and jax.process_count() > 1
        self._aot = ExecutableCache() if (
            aot_cache and persistent_cache and not multiproc
        ) else None

    # -- shape bucketing -----------------------------------------------------

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self._buckets

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (the largest bucket for oversize chunks —
        ``predict`` splits those)."""
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    # -- call convention -----------------------------------------------------

    def _eval_flag(self) -> str:
        """The model's eval-mode kwarg (``deterministic=True`` vs
        ``train=False``), probed from the signature — not from exception
        text, which interpreter rewording would break."""
        if self._flag_name is None:
            import inspect

            try:
                params = inspect.signature(type(self.model).__call__).parameters
            except (TypeError, ValueError):
                params = {}
            self._flag_name = "train" if (
                "train" in params and "deterministic" not in params
            ) else "deterministic"
        return self._flag_name

    # -- programs ------------------------------------------------------------

    @property
    def precision(self) -> str:
        return self._precision

    def _apply_fn(self):
        model, flag = self.model, self._eval_flag()
        precision = self._precision

        if precision != "f32":
            from distributed_machine_learning_tpu import quant as _quant

            # Quantized path: weights dequantize INSIDE the program (XLA
            # fuses int8->bf16 + scale into the consuming matmul), inputs
            # join the bf16 compute dtype, and the one f32 upcast on the
            # way out is quant's designated dequant helper (DML018).
            def apply(variables, x):
                kwargs = {flag: flag == "deterministic"}
                fvars = _quant.dequantize_variables(variables, precision)
                out = model.apply(
                    fvars, _quant.cast_input(x, precision), **kwargs
                )
                return _quant.dequantize_output(out)

            return apply

        def apply(variables, x):
            kwargs = {flag: flag == "deterministic"}
            return model.apply(variables, x, **kwargs)

        return apply

    def _program(self, key: Tuple, x: np.ndarray):
        """Resolve the compiled program for one padded bucket.

        ``x`` is the already-padded batch (exact shapes/dtypes the program
        runs at) — on an AOT-cache miss it is the lowering example.  Must
        be called with the engine's device context active so the compile
        lands on the pinned device."""
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self._program_hits += 1
                return prog
        bucket, trailing, dtype = key
        if self._mesh is not None:
            prog = self._mesh_build(key, x)
        elif self._aot is not None:
            pk = program_key(
                self.bundle.config,
                batch_shape=[(bucket, *trailing)],
                dtype=dtype,
                extra={
                    "serve": 1,
                    # Storage precision is program identity: the int8
                    # program embeds dequant ops and bf16 accumulation the
                    # f32 program does not, at identical input shapes.
                    "precision": self._precision,
                    # AOT executables embed their device assignment; a
                    # deserialized program silently runs THERE, so the
                    # device is program identity (a restarted replica of
                    # the same slot sees the same device and hits).
                    "device": (
                        lambda d: f"{getattr(d, 'platform', 'cpu')}:"
                                  f"{getattr(d, 'id', 0)}"
                    )(self._device if self._device is not None
                      else jax.devices()[0]),
                },
            )
            prog = self._aot.get_or_compile(pk, self._apply_fn(),
                                            self._variables, x)
        else:
            prog = jax.jit(self._apply_fn())
        with self._lock:
            # Keep the first resolution if two requests raced the build.
            prog = self._programs.setdefault(key, prog)
        return prog

    def _mesh_build(self, key: Tuple, x):
        """Build (or AOT-resolve, single-process only) one bucket program
        lowered over the serving mesh.

        The program's identity is :func:`gang_program_key` — process
        topology, padded bucket shape, dtype, storage precision, mesh
        shape, and partition-rule fingerprint all fold in, so every
        member of a gang (and every future gang of the same topology)
        computes the identical key while any reshape splits it.  Inputs
        arrive replicated (``stage_global`` in ``_run_bucket``), params
        arrive laid out by ``load_bundle(mesh=...)``; in_shardings are
        inferred from those committed arrays and outputs are pinned
        replicated so the coordinator can read one addressable shard back.
        """
        from jax.sharding import NamedSharding, PartitionSpec

        from distributed_machine_learning_tpu.models.partition_rules import (
            rules_fingerprint_for,
        )
        from distributed_machine_learning_tpu.multihost import (
            runtime as _runtime,
        )
        from distributed_machine_learning_tpu.parallel.partition import (
            mesh_axis_sizes,
        )

        bucket, trailing, dtype = key
        topology = _runtime.process_topology()
        pk = gang_program_key(
            self.bundle.config,
            process_count=topology["process_count"],
            local_device_counts=topology["local_device_counts"],
            batch_shape=[(bucket, *trailing)],
            dtype=dtype,
            extra={
                "serve": 1,
                "precision": self._precision,
                "mesh_shape": mesh_axis_sizes(self._mesh),
                "rules_fp": rules_fingerprint_for(self.bundle.config),
            },
        )
        jit_kwargs = {
            "out_shardings": NamedSharding(self._mesh, PartitionSpec())
        }
        if self._aot is not None:
            return self._aot.get_or_compile(
                pk, self._apply_fn(), self._variables, x,
                jit_kwargs=jit_kwargs,
            )
        return jax.jit(self._apply_fn(), **jit_kwargs)

    def program_stats(self) -> Dict[str, Any]:
        """Compile counters for /metrics and the zero-recompile check."""
        with self._lock:
            stats = {
                "precision": self._precision,
                "programs": len(self._programs),
                "program_hits": self._program_hits,
                "backend_compile_s": round(
                    self._tracker.total_seconds(), 4
                ),
                "compile_cache_hits": self._tracker.total_cache_hits(),
            }
        if self._aot is not None:
            stats["aot"] = self._aot.stats()
        return stats

    @property
    def num_programs(self) -> int:
        with self._lock:
            return len(self._programs)

    # -- inference -----------------------------------------------------------

    def _run_bucket(self, x: np.ndarray) -> np.ndarray:
        """One padded chunk: pad batch dim to its bucket, run, slice back."""
        n = x.shape[0]
        bucket = self.bucket_for(n)
        if n < bucket:
            pad = np.zeros((bucket - n, *x.shape[1:]), dtype=x.dtype)
            x = np.concatenate([x, pad], axis=0)
        key = (bucket, x.shape[1:], str(x.dtype))
        if self._mesh is not None:
            return self._run_bucket_mesh(key, x)[:n]
        with obs.span("engine.step", {"bucket": bucket}):
            ctx = (
                jax.default_device(self._device)
                if self._device is not None
                else _null_ctx()
            )
            with ctx:
                # Resolution inside the device context: an AOT-cache miss
                # lowers+compiles here, and the executable must land on
                # the pinned device (thread-local jax config).
                prog = self._program(key, x)
                out = prog(self._variables, x)
            out = np.asarray(out)  # readback inside the span (sync point)
        return out[:n]

    def _run_bucket_mesh(self, key: Tuple, x: np.ndarray) -> np.ndarray:
        """One padded chunk over the serving mesh.  Collective in effect:
        every gang member must call this with the SAME padded batch (the
        member loop broadcasts it), stage_global places each member's
        addressable shards of the replicated input, and the program's
        cross-process collectives do the rest.  Readback takes one
        addressable shard — outputs are pinned replicated, so shard 0 IS
        the full answer on every member."""
        from jax.sharding import NamedSharding, PartitionSpec

        from distributed_machine_learning_tpu.multihost import (
            runtime as _runtime,
        )

        bucket = key[0]
        with obs.span("engine.step", {"bucket": bucket}):
            staged = _runtime.stage_global(
                x, NamedSharding(self._mesh, PartitionSpec())
            )
            prog = self._program(key, staged)
            out = prog(self._variables, staged)
            # np.asarray rejects non-fully-addressable arrays; the
            # replicated out_shardings guarantee any one local shard
            # carries the whole value.
            out = np.asarray(out.addressable_data(0))
        return out

    def predict(self, x) -> np.ndarray:
        """Batched forward pass; axis 0 is the batch dimension.  Requests
        larger than the top bucket are answered in top-bucket chunks."""
        x = np.asarray(x)
        if x.ndim == 0:
            raise ValueError("predict() needs at least a batch dimension")
        n = x.shape[0]
        if n == 0:
            return np.zeros((0,), dtype=np.float32)
        top = self._buckets[-1]
        if n <= top:
            return self._run_bucket(x)
        outs = [self._run_bucket(x[i: i + top]) for i in range(0, n, top)]
        return np.concatenate(outs, axis=0)

    def warmup(
        self,
        sample: Any,
        buckets: Optional[Sequence[int]] = None,
    ) -> Dict[str, Any]:
        """Compile the bucket grid for ``sample``'s trailing shape/dtype so
        live traffic starts at zero compiles.  Returns ``program_stats()``
        after the pass."""
        sample = np.asarray(sample)
        trailing = sample.shape[1:] if sample.ndim > 1 else ()
        for b in buckets or self._buckets:
            x = np.zeros((b, *trailing), dtype=sample.dtype)
            self._run_bucket(x)
        return self.program_stats()


class _null_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False
