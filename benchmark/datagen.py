"""The one generator of every cell's data: windows of a synthetic sensor
series with a forecastable target, made from ``--seed`` and the traffic
file's numbers alone.

Sinusoids of a few periods plus smoothed noise, cut into windows of
``seq_len`` steps; the target is the mean of channel 0 over the steps that
follow the window, so a model that reads the window's end can learn it and
the validation loss responds to training.  The same seed gives the same
arrays; rows all differ.
"""

from __future__ import annotations

import numpy as np


def make_windows(seed: int, *, n_train: int, n_val: int, seq_len: int,
                 features: int, horizon: int = 4):
    """``(x_train, y_train, x_val, y_val)`` as float32 arrays:
    x ``[n, seq_len, features]``, y ``[n, 1]``."""
    rng = np.random.default_rng([int(seed), n_train, n_val, seq_len, features])
    n = n_train + n_val
    steps = n * seq_len + horizon
    t = np.arange(steps, dtype=np.float32)[:, None]
    periods = rng.choice([96.0, 288.0, 1440.0], features).astype(np.float32)
    phases = rng.uniform(0.0, 2 * np.pi, features).astype(np.float32)
    series = np.sin(2 * np.pi * t / periods[None] + phases[None])
    noise = rng.standard_normal((steps, features), dtype=np.float32)
    # Smoothed noise by a running mean: bulk, no per-step loop.
    kernel = 8
    csum = np.cumsum(noise, axis=0)
    noise[kernel:] = (csum[kernel:] - csum[:-kernel]) / np.sqrt(kernel)
    series = (series + 0.5 * noise).astype(np.float32)
    x = series[: n * seq_len].reshape(n, seq_len, features)
    ends = (np.arange(n) + 1) * seq_len
    y = np.stack(
        [series[e:e + horizon, 0].mean() for e in ends]
    ).astype(np.float32)[:, None]
    order = rng.permutation(n)
    x, y = x[order], y[order]
    return x[:n_train], y[:n_train], x[n_train:], y[n_train:]
