"""``banded``: FEM/stencil surrogate, nonzeros clustered near the
diagonal (a copy of the program's ``synth_banded``).  Needs
``bandwidth_frac``: the band's standard width as a share of the
dimension."""

import numpy as np


def _dedupe(m, n, rows, cols, rng):
    key = np.unique(rows.astype(np.int64) * n + cols.astype(np.int64))
    rows = key // n
    cols = key % n
    vals = rng.standard_normal(rows.shape[0]).astype(np.float32)
    return rows, cols, vals


def generate(n: int, nnz: int, spec: dict, seed: int):
    rng = np.random.default_rng(seed)
    bw = max(int(n * spec["bandwidth_frac"]), 4)
    rows = rng.integers(0, n, int(nnz * 1.2) + 8)
    offs = np.rint(rng.standard_normal(rows.shape[0]) * bw / 3.0).astype(np.int64)
    cols = np.clip(rows + offs, 0, n - 1)
    rows, cols, vals = _dedupe(n, n, rows, cols, rng)
    if rows.shape[0] > nnz:
        keep = np.sort(rng.choice(rows.shape[0], nnz, replace=False))
        rows, cols, vals = rows[keep], cols[keep], vals[keep]
    return (n, n), rows, cols, vals
