"""Token sequences for the language-model cells, from ``--seed`` and the
traffic file's numbers alone.

A first-order Markov chain over the vocabulary slice: every id has four
likely successors (drawn once from the seed) taken with probabilities
0.55, 0.2, 0.1 and 0.05, and with the remaining 0.1 the next id is any id
of the slice.  So the next token can be learnt from the last one and the
loss falls from ``log(vocab)`` as a model trains; each sequence is one
document.  The target of a position is the token that follows it.
"""

from __future__ import annotations

import numpy as np

SUCCESSOR_P = (0.55, 0.2, 0.1, 0.05)


def make_sequences(seed: int, *, n_train: int, n_val: int, seq_len: int,
                   vocab: int):
    """``(x_train, y_train, x_val, y_val)`` as int32 arrays [n, seq_len]:
    y is x one position on."""
    rng = np.random.default_rng([int(seed), n_train, n_val, seq_len, vocab])
    n = n_train + n_val
    successors = rng.integers(0, vocab, (vocab, len(SUCCESSOR_P)), dtype=np.int32)
    # Drawn in bulk: which successor (or a free draw) at every position.
    edges = np.cumsum(SUCCESSOR_P)
    which = np.searchsorted(edges, rng.random((seq_len, n)), side="right")
    free = rng.integers(0, vocab, (seq_len, n), dtype=np.int32)
    seq = np.empty((seq_len + 1, n), np.int32)
    seq[0] = rng.integers(0, vocab, n, dtype=np.int32)
    last = len(SUCCESSOR_P)
    for t in range(seq_len):
        pick = successors[seq[t], np.minimum(which[t], last - 1)]
        seq[t + 1] = np.where(which[t] == last, free[t], pick)
    seq = seq.T
    x, y = seq[:, :-1], seq[:, 1:]
    return (np.ascontiguousarray(x[:n_train]), np.ascontiguousarray(y[:n_train]),
            np.ascontiguousarray(x[n_train:]), np.ascontiguousarray(y[n_train:]))
