"""Token documents for the training traffic: a copy of the program's
``data/synthetic.make_mixture_tokens`` (cluster-specific Markov chains,
per-client mixture fractions drawn from U[0.1, 0.9]), kept here so the
yardstick does not move when the program's generator does."""
from __future__ import annotations

import numpy as np


def _mixture_counts(rng, n_clients: int, s: int, m: int,
                    lo: float = 0.1, hi: float = 0.9) -> np.ndarray:
    """Counts (N, S) per client per cluster, U[lo, hi] fractions."""
    if s == 1:
        return np.full((n_clients, 1), m, dtype=np.int64)
    counts = np.zeros((n_clients, s), dtype=np.int64)
    for i in range(n_clients):
        fracs = rng.uniform(lo, hi, size=s)
        fracs = fracs / fracs.sum()
        c = np.floor(fracs * m).astype(np.int64)
        c[rng.integers(s)] += m - c.sum()
        counts[i] = c
    return counts


def make_mixture_tokens(n_clients: int, n_clusters: int, docs_per_client: int,
                        seq_len: int, vocab: int, seed: int,
                        concentration: float = 0.25) -> np.ndarray:
    """(N, D, L) int32 documents; each cluster's next-token statistics come
    from its own sparse-ish Dirichlet transition matrix."""
    rng = np.random.default_rng(seed)
    trans = [rng.dirichlet(np.full(vocab, concentration), size=vocab)
             for _ in range(n_clusters)]
    counts = _mixture_counts(rng, n_clients, n_clusters, docs_per_client)
    tokens = np.zeros((n_clients, docs_per_client, seq_len), dtype=np.int32)
    for i in range(n_clients):
        d = 0
        for s, c in enumerate(counts[i]):
            for _ in range(c):
                seq = tokens[i, d]
                seq[0] = rng.integers(vocab)
                for k in range(1, seq_len):
                    seq[k] = rng.choice(vocab, p=trans[s][seq[k - 1]])
                d += 1
        tokens[i] = tokens[i][rng.permutation(docs_per_client)]
    return tokens
