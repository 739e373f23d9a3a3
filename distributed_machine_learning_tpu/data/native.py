"""ctypes bindings for the native C++ data-layer kernels.

The hot host-side data-prep ops (windowing, shuffled batch gather,
standardization — the work the reference does in Python loops / delegates to
torch DataLoaders, `ray-tune-hpo-regression.py:403-411,452-457`) live in
``native/window_ops.cpp`` as a C-ABI shared library with OpenMP. This module
compiles it with the system ``g++`` on first use (cached by source hash under the
in-checkout cache root, ``compilecache.tracker.cache_root()``), binds it with ctypes, and exposes numpy-signature
wrappers. Every wrapper has a pure-numpy fallback, so the package works
identically (slower) where no C++ toolchain exists; ``native_available()``
reports which path is active.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional, Tuple

import numpy as np
from distributed_machine_learning_tpu.analysis.locks import named_lock
from distributed_machine_learning_tpu.compilecache.tracker import cache_root

_SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native", "window_ops.cpp")
_CACHE_DIR = os.path.join(cache_root(), "native")

_lock = named_lock("data.native")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    """Compile window_ops.cpp -> .so (hash-cached) and dlopen it."""
    try:
        with open(_SRC, "rb") as f:
            src = f.read()
    except OSError:
        return None
    tag = hashlib.sha256(src).hexdigest()[:16]
    so_path = os.path.join(_CACHE_DIR, f"libdmlnative_{tag}.so")
    if not os.path.exists(so_path):
        os.makedirs(_CACHE_DIR, exist_ok=True)
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_CACHE_DIR)
        os.close(fd)
        cmd = [
            "g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-fopenmp",
            _SRC, "-o", tmp,
        ]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so_path)
        except (subprocess.SubprocessError, OSError):
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
    try:
        lib = ctypes.CDLL(so_path)
    except OSError:
        return None

    i64 = ctypes.c_int64
    u64 = ctypes.c_uint64
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")

    lib.dml_window.argtypes = [f32p, i64, i64, i64, i64, f32p]
    lib.dml_window.restype = i64
    lib.dml_gather.argtypes = [f32p, i64, i64, i64p, i64, f32p]
    lib.dml_gather.restype = i64
    lib.dml_shuffled_indices.argtypes = [i64, u64, i64p]
    lib.dml_shuffled_indices.restype = i64
    lib.dml_column_stats.argtypes = [f32p, i64, i64, f64p, f64p]
    lib.dml_column_stats.restype = i64
    lib.dml_standardize.argtypes = [f32p, i64, i64, f64p, f64p, ctypes.c_double]
    lib.dml_standardize.restype = i64
    lib.dml_rolling_stats.argtypes = [f32p, i64, i64p, i64, f32p]
    lib.dml_rolling_stats.restype = i64
    return lib


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if not _tried:
            if os.environ.get("DML_TPU_DISABLE_NATIVE"):
                _lib = None
            else:
                _lib = _build_and_load()
            _tried = True
    return _lib


def native_available() -> bool:
    return _get_lib() is not None


def window(array: np.ndarray, interval: int, stride: int) -> np.ndarray:
    """[T, F] float32 -> [n_windows, interval, F]; native parallel memcpy."""
    if array.ndim == 1:
        array = array[:, None]
    T, F = array.shape
    if T < interval:
        return np.empty((0, interval, F), dtype=np.float32)
    n_windows = (T - interval) // stride + 1
    lib = _get_lib()
    arr = np.ascontiguousarray(array, dtype=np.float32)
    if lib is None:
        w = np.lib.stride_tricks.sliding_window_view(arr, interval, axis=0)
        return np.ascontiguousarray(np.transpose(w[::stride], (0, 2, 1)))
    out = np.empty((n_windows, interval, F), dtype=np.float32)
    rc = lib.dml_window(arr, T, F, interval, stride, out)
    if rc != n_windows:  # pragma: no cover
        raise RuntimeError(f"dml_window failed: rc={rc}")
    return out


_SM64_MIX = np.uint64(0xD1B54A32D192ED03)
_SM64_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_SM64_M1 = np.uint64(0xBF58476D1CE4E5B9)
_SM64_M2 = np.uint64(0x94D049BB133111EB)


def _splitmix64_draws(seed: int, count: int) -> np.ndarray:
    """The first ``count`` splitmix64 outputs for ``seed`` (vectorized;
    bit-identical to ``splitmix64`` in native/window_ops.cpp)."""
    state = np.uint64(seed & (2**64 - 1)) ^ _SM64_MIX
    with np.errstate(over="ignore"):
        z = state + np.arange(1, count + 1, dtype=np.uint64) * _SM64_GOLDEN
        z = (z ^ (z >> np.uint64(30))) * _SM64_M1
        z = (z ^ (z >> np.uint64(27))) * _SM64_M2
        return z ^ (z >> np.uint64(31))


def shuffled_indices(n: int, seed: int) -> np.ndarray:
    """Deterministic permutation of [0, n) (the epoch shuffle in
    Dataset.batches). The numpy fallback implements the same splitmix64
    Fisher-Yates as the native path, so a given seed produces the same batch
    order whether or not the C++ toolchain built — training runs stay
    reproducible across hosts with and without g++."""
    lib = _get_lib()
    out = np.empty(n, dtype=np.int64)
    if lib is None:
        out[:] = np.arange(n)
        draws = _splitmix64_draws(seed, max(n - 1, 0))
        for k, i in enumerate(range(n - 1, 0, -1)):
            j = int(draws[k] % np.uint64(i + 1))
            out[i], out[j] = out[j], out[i]
        return out
    lib.dml_shuffled_indices(n, np.uint64(seed & (2**64 - 1)), out)
    return out


def gather(x: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """x[idx] for row-major float32 x of any trailing shape.

    Negative indices are rejected on both paths (numpy's wrap-around would
    otherwise make behavior toolchain-dependent).
    """
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    if idx.size and (idx.min() < 0 or idx.max() >= len(x)):
        raise IndexError("gather index out of range")
    lib = _get_lib()
    if lib is None:
        return x[idx]
    x = np.ascontiguousarray(x, dtype=np.float32)
    row_elems = int(np.prod(x.shape[1:], dtype=np.int64)) if x.ndim > 1 else 1
    out = np.empty((len(idx),) + x.shape[1:], dtype=np.float32)
    lib.dml_gather(x.reshape(len(x), -1) if x.ndim > 1 else x[:, None],
                   len(x), max(row_elems, 1), idx, len(idx),
                   out.reshape(len(idx), -1) if out.ndim > 1 else out[:, None])
    return out


def rolling_stats(series: np.ndarray, windows, ddof: int = 0) -> np.ndarray:
    """Trailing rolling mean/std of a 1-D series over several windows.

    Returns [n, len(windows)*2], columns (mean_w0, std_w0, mean_w1, ...).
    Semantics match ``pandas.rolling(w, min_periods=1)``, including NaN
    handling: NaN entries are skipped per-window (sensor gaps), and a
    window with no finite entries yields NaN. ``ddof=0`` (default) is
    population std; ``ddof=1`` matches pandas' ``.rolling().std()``
    default (NaN wherever the finite count is <= ddof) — the reference's
    precomputed '*_std_*min' data columns may use either convention, so
    both are exposed. Both paths compute through the same double prefix
    sums, so results agree to float32 rounding with or without the C++
    toolchain.
    """
    x = np.ascontiguousarray(np.asarray(series).reshape(-1), dtype=np.float32)
    ws = np.ascontiguousarray(np.asarray(list(windows)), dtype=np.int64)
    n, k = len(x), len(ws)
    if ddof < 0:
        raise ValueError(f"ddof must be >= 0: {ddof}")
    if n == 0 or k == 0:
        return np.empty((n, k * 2), dtype=np.float32)
    if (ws <= 0).any():
        raise ValueError(f"window lengths must be positive: {ws}")
    lib = _get_lib()
    if lib is not None:
        out = np.empty((n, k * 2), dtype=np.float32)
        rc = lib.dml_rolling_stats(x, n, ws, k, out)
        if rc != n:  # pragma: no cover
            raise RuntimeError(f"dml_rolling_stats failed: rc={rc}")
        return _apply_ddof(out, x, ws, ddof)
    xd = x.astype(np.float64)
    ok = np.isfinite(xd)
    xz = np.where(ok, xd, 0.0)
    s1 = np.concatenate([[0.0], np.cumsum(xz)])
    s2 = np.concatenate([[0.0], np.cumsum(xz * xz)])
    sc = np.concatenate([[0.0], np.cumsum(ok.astype(np.float64))])
    idx = np.arange(n)
    out = np.empty((n, k * 2), dtype=np.float32)
    with np.errstate(invalid="ignore", divide="ignore"):
        for j, w in enumerate(ws):
            lo = np.maximum(idx - int(w) + 1, 0)
            cnt = sc[idx + 1] - sc[lo]
            mu = np.where(cnt > 0, (s1[idx + 1] - s1[lo]) / cnt, np.nan)
            var = np.maximum((s2[idx + 1] - s2[lo]) / cnt - mu * mu, 0.0)
            out[:, j * 2] = mu
            out[:, j * 2 + 1] = np.sqrt(var) * _ddof_factor(cnt, ddof)
    return out


def _ddof_factor(cnt: np.ndarray, ddof: int) -> np.ndarray:
    """Population-std -> ddof-std rescale per window: sqrt(cnt/(cnt-ddof)),
    NaN where cnt <= ddof (pandas convention). 1.0 at ddof=0."""
    if ddof == 0:
        return np.ones_like(cnt)
    return np.sqrt(
        np.where(cnt > ddof, cnt / np.maximum(cnt - ddof, 1e-300), np.nan)
    )


def _apply_ddof(out: np.ndarray, x: np.ndarray, ws: np.ndarray,
                ddof: int) -> np.ndarray:
    """Rescale the native kernel's population-std columns to ``ddof``
    freedom. The per-window finite counts come from one prefix sum over
    the finite mask — O(n*k) numpy, so the native kernel stays a single
    population-stats entry point."""
    if ddof == 0:
        return out
    n = len(x)
    sc = np.concatenate(
        [[0.0], np.cumsum(np.isfinite(x).astype(np.float64))]
    )
    idx = np.arange(n)
    with np.errstate(invalid="ignore", divide="ignore"):
        for j, w in enumerate(ws):
            lo = np.maximum(idx - int(w) + 1, 0)
            cnt = sc[idx + 1] - sc[lo]
            out[:, j * 2 + 1] = (
                out[:, j * 2 + 1].astype(np.float64) * _ddof_factor(cnt, ddof)
            ).astype(np.float32)
    return out


def standardize(
    x: np.ndarray, eps: float = 1e-8
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-column z-score of [N, F] float32; returns (standardized, mean, std)."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    n, m = x.shape
    lib = _get_lib()
    if lib is None:
        mean = x.mean(axis=0, dtype=np.float64)
        std = x.std(axis=0, dtype=np.float64)
        scaled = (x - mean) / np.where(std > eps, std, 1.0)
        return scaled.astype(np.float32), mean, std
    mean = np.empty(m, dtype=np.float64)
    std = np.empty(m, dtype=np.float64)
    lib.dml_column_stats(x, n, m, mean, std)
    out = x.copy()
    lib.dml_standardize(out, n, m, mean, std, eps)
    return out, mean, std
