"""``uniform``: Bernoulli sparsity, every position equally likely (a copy
of the program's ``synth_uniform``, drawn to a count of nonzeros).  Needs
nothing beyond the dimension and the nonzeros."""

import numpy as np


def generate(n: int, nnz: int, spec: dict, seed: int):
    rng = np.random.default_rng(seed)
    draw = int(nnz * 1.05) + 8
    key = np.unique(rng.integers(0, n, draw).astype(np.int64) * n
                    + rng.integers(0, n, draw))
    vals = rng.standard_normal(key.shape[0]).astype(np.float32)
    if key.shape[0] > nnz:
        keep = np.sort(rng.choice(key.shape[0], nnz, replace=False))
        key, vals = key[keep], vals[keep]
    return (n, n), key // n, key % n, vals
