"""Structure-matched surrogates of the paper's Table-3 matrices, kept
with the benchmark so that no change to the program moves the matrix a
cell measures.

A configuration's ``matrix`` entry gives the matrix's dimension, its
nonzeros and its ``structure``, which names the generator
``bench/lib/structures/<structure>.py`` (``generate(n, nnz, spec,
seed)``).  The positions are the same for every seed (the generator's
draw for seed 0), so every seed gives the plan the same colouring and
the kernel the same stream: the same work.  The seed draws the values.
Returns plain ``(shape, rows, cols, vals)`` numpy arrays, sorted by row
and then column.
"""

from __future__ import annotations

import os

STRUCTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "structures")


def surrogate(spec: dict, seed: int, structures_dir: str = STRUCTURES):
    """The matrix ``spec`` describes at its ``scale`` (dimension times
    scale, nonzeros times scale squared), its values drawn from
    ``seed``."""
    import numpy as np

    from .harness import RunFailure, load_module

    path = os.path.join(structures_dir, spec["structure"] + ".py")
    if not os.path.isfile(path):
        raise RunFailure(f"no matrix structure bench/lib/structures/"
                         f"{spec['structure']}.py")
    scale = float(spec.get("scale", 1.0))
    dim = max(int(spec["dim"] * scale), 256)
    nnz = min(max(int(spec["nnz"] * scale * scale), 512), dim * dim // 2)
    gen = load_module(path, "structure_" + spec["structure"])
    shape, rows, cols, _ = gen.generate(dim, nnz, spec, 0)
    rng = np.random.default_rng([3, int(seed) % (1 << 64)])
    return shape, rows, cols, rng.standard_normal(rows.shape[0]).astype(
        np.float32)
