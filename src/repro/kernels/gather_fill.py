"""Buffer-Filler vector-gather Pallas kernel.

The paper's Buffer Filler holds the input vector on-chip and fills each
multiplier's vector FIFO with ``v[Col_sch[c, j]]`` (§3.3, "Streaming the
Inputs").  This kernel is the standalone TPU analogue: the vector sits
resident in VMEM in segment-major layout and the scheduled column indices
stream through, producing the gathered vector stream ``V_sch``.

It exists as its own kernel for two reasons: (a) it lets the gather logic
be tested/swept independently of the routing matmul, and (b) it is the
building block for the *unfused* execution path (gather kernel -> XLA
elementwise/segment ops), which is the honest TPU analogue of GUST's
hardware pipeline stages when fusion is disabled.

Gather mechanism (the flagship kernel's, shared code): the scheduler only
ever maps a column to its own lane (``off == lane``) or — after
load-balance step 3 — to the lane-reversed slot (``off == l-1-lane``), so
the gather decomposes into a select over the ``S = ceil(n/l)`` column
segments plus a straight/flipped select.  No random access is ever
issued.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .gust_spmv import _batch_pad, _gather_resident, _resolve_interpret

__all__ = ["make_gather_fill"]


def _kernel(col_ref, xs_ref, out_ref, *, l, seg_count):
    gs = _gather_resident(col_ref[...], xs_ref, l=l, seg_count=seg_count)
    for c, g in enumerate(gs):
        out_ref[c] = g


@functools.lru_cache(maxsize=256)
def make_gather_fill(
    total_rows: int,
    l: int,
    seg_count: int,
    b: int,
    *,
    c_blk: int = 8,
    interpret: Optional[bool] = None,
):
    """pallas_call producing ``V_sch`` from ``Col_sch`` (total_rows, l)
    and the VMEM-resident vector in the SpMV kernels' layout
    ``(seg_count, B_pad, l)``: returns (total_rows, B_pad, l), row ``r``
    holding ``x[Col_sch[r, j], :]`` at lane ``j``.  Memoized on geometry
    like :func:`repro.kernels.gust_spmv.make_gust_spmv`."""
    if total_rows % c_blk:
        raise ValueError("total_rows must be a multiple of c_blk")
    bp = _batch_pad(b)
    grid = (total_rows // c_blk,)
    kernel = functools.partial(_kernel, l=l, seg_count=seg_count)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((c_blk, l), lambda i: (i, 0)),
            pl.BlockSpec((seg_count, bp, l), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((c_blk, bp, l), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((total_rows, bp, l), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_gather_fill",
    )
