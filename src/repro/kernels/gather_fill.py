"""Buffer-Filler vector-gather Pallas kernel.

The paper's Buffer Filler holds the input vector on-chip and fills each
multiplier's vector FIFO with ``v[Col_sch[c, j]]`` (§3.3, "Streaming the
Inputs").  This kernel is the standalone TPU analogue: the vector sits
resident in VMEM in the resident kernels' ``(b, S8, l)`` layout (batch
row, column segment padded to a multiple of eight, lane) and the
scheduled column indices stream through, producing the gathered vector
stream ``V_sch``.

It exists as its own kernel for two reasons: (a) it lets the gather logic
be tested/swept independently of the routing matmul, and (b) it is the
building block for the *unfused* execution path (gather kernel -> XLA
elementwise/segment ops), which is the honest TPU analogue of GUST's
hardware pipeline stages when fusion is disabled.

Gather mechanism (the flagship kernel's, shared code): the scheduler only
ever maps a column to its own lane (``off == lane``) or — after
load-balance step 3 — to the lane-reversed slot (``off == l-1-lane``), so
a slot's value is the sublane ``seg % 8`` of segment group ``seg // 8``
of x or of its lane-reversed twin (derived once into VMEM scratch).  The
walk visits the ``ceil(S / 8)`` groups of the ``S = ceil(n/l)`` column
segments, one sublane gather per group, batch row and orientation, then
a straight/flipped select.  No lane crosses to another lane.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gust_spmv import (
    _batch_pad,
    _gather_resident,
    _resident_x_rows,
    _resolve_interpret,
    _reverse_x,
)

__all__ = ["make_gather_fill"]


def _kernel(col_ref, xs_ref, out_ref, xr_scr, *, l, seg_count):
    # the grid runs in order on one core, so the twin made at the first
    # step serves every later one
    @pl.when(pl.program_id(0) == 0)
    def _reverse():
        _reverse_x(xs_ref, xr_scr)

    gs = _gather_resident(col_ref[...], xs_ref, xr_scr, l=l,
                          seg_count=seg_count)
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
    and the VMEM-resident vector in the resident SpMV kernels' layout
    ``(b, S8, l)``: returns (total_rows, B_pad, l), row ``r``
    holding ``x[Col_sch[r, j], :]`` at lane ``j``.  Memoized on geometry
    like :func:`repro.kernels.gust_spmv.make_gust_spmv`."""
    if total_rows % c_blk:
        raise ValueError("total_rows must be a multiple of c_blk")
    bp = _batch_pad(b)
    rows = _resident_x_rows(seg_count)
    kernel = functools.partial(_kernel, l=l, seg_count=seg_count)
    return pl.pallas_call(
        kernel,
        grid=(total_rows // c_blk,),
        in_specs=[
            pl.BlockSpec((c_blk, l), lambda i: (i, 0)),
            pl.BlockSpec((b, rows, l), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((c_blk, bp, l), lambda i: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((b, rows, l), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((total_rows, bp, l), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_gather_fill",
    )
