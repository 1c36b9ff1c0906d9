"""Pallas TPU kernel for GUST SpGEMM: color-block outer products over
condensed B rows with a VMEM dense-row accumulator.

SpArch organizes sparse×sparse as streamed outer products with condensed
partial-result merging; GUST's color-block stream is already exactly that
schedule — each ``(c_blk, l)`` block is a conflict-free set of multiply
lanes whose "vector gather" generalizes from one x element to one row of
B.  This kernel runs ``C = A @ B`` from A's packed schedule stream (either
layout, viewed as the ragged block stream) and B in the *condensed-row*
format built by :func:`repro.core.spgemm.condense_rows`: every row of B
padded to ``k_max`` ``(value, column)`` pairs, ``(R, k_max)`` value and
column planes.  Streaming condensed B costs ``R·k_max·8`` bytes instead
of the ``R·n_out·4`` a densified B would — the SpArch condensing win.

Per grid step (one stream block, scalar-prefetch steering identical to
``gust_spmv_ragged``):

  1. **condensed gather** — per cycle row, a one-hot over B's ``R`` rows
     on the MXU fetches the ``(l, k_max)`` value/column pairs of its
     slots (columns ride the same full-precision matmul as exact small
     integers in f32);
  2. **multipliers** — VPU multiply by the block's A values;
  3. **merge** — each slot's partial products densify into its output row
     through a weighted one-hot over ``n_out`` columns, then the crossbar
     routing matmul scatters slot rows onto adder rows; the result
     accumulates in a ``(l, n_out)`` **VMEM scratch row accumulator**
     that integrates across the window's blocks and dumps to the output
     tile on the window's last block (the paper's integrate-then-dump,
     with a dense row per adder instead of a scalar).

Collision-freedom of the edge coloring is what keeps the routing matmul
exact, just as in SpMV: within a cycle each adder row receives at most
one partial row.  The pure-jnp oracle is
:func:`repro.kernels.ref.gust_spgemm_ref`; kernel and oracle agree
bitwise on exact-arithmetic (integer-valued) inputs where every
summation order produces the same floats, and to float tolerance
otherwise (their merge orders differ — segment-sum vs blocked one-hot).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gust_spmv import _resolve_interpret

__all__ = ["make_gust_spgemm"]


def _kernel(bw_ref, bs_ref, m_ref, col_ref, row_ref, bv_ref, bc_ref, y_ref,
            acc_scr, *, l, r_rows, k_max, n_out, c_blk):
    t = pl.program_id(0)
    w = bw_ref[t]
    hi = jax.lax.Precision.HIGHEST

    # slots of the block with the lane on sublanes: column c of these
    # (l, c_blk) arrays is cycle c of the stream block
    m_t = m_ref[...].astype(jnp.float32).T
    col_t = col_ref[...].astype(jnp.int32).T
    row = row_ref[...].astype(jnp.int32)  # (c_blk, l)
    bv = bv_ref[...].astype(jnp.float32)  # (R, k_max)
    bc = bc_ref[...].astype(jnp.float32)  # exact small integers
    b_row = jax.lax.broadcasted_iota(jnp.int32, (l, r_rows), 1)
    out_col = jax.lax.broadcasted_iota(jnp.int32, (l, n_out), 1)
    adder = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)

    acc = jnp.zeros((l, n_out), jnp.float32)
    for c in range(c_blk):
        # ---- condensed gather: one-hot over B's rows on the MXU ---------
        sel = (b_row == col_t[:, c:c + 1]).astype(jnp.float32)  # (l, R)
        vals = jnp.dot(sel, bv, precision=hi,
                       preferred_element_type=jnp.float32)  # (l, k_max)
        cols = jnp.dot(sel, bc, precision=hi,
                       preferred_element_type=jnp.float32).astype(jnp.int32)
        # ---- multipliers (VPU) + merge: densify each slot's partial row
        partial = vals * m_t[:, c:c + 1]
        slot_rows = jnp.zeros((l, n_out), jnp.float32)
        for k in range(k_max):
            slot_rows = slot_rows + jnp.where(
                cols[:, k:k + 1] == out_col, partial[:, k:k + 1], 0.0
            )
        # ---- crossbar: route slot rows onto adder rows -------------------
        onehot_row = (adder == row[c:c + 1]).astype(jnp.float32)  # (l, l)
        acc = acc + jnp.dot(onehot_row, slot_rows, precision=hi,
                            preferred_element_type=jnp.float32)

    # ---- VMEM scratch row accumulator: integrate across the window's
    # blocks, dump on its last one ----------------------------------------
    first = t == bs_ref[w]

    @pl.when(first)
    def _init():
        acc_scr[...] = acc

    @pl.when(jnp.logical_not(first))
    def _accum():
        acc_scr[...] += acc

    @pl.when(t == bs_ref[w + 1] - 1)
    def _dump():
        y_ref[0] = acc_scr[...]


@functools.lru_cache(maxsize=256)
def make_gust_spgemm(
    num_blocks: int,
    num_windows: int,
    l: int,
    r_rows: int,
    k_max: int,
    n_out: int,
    *,
    c_blk: int = 8,
    interpret: Optional[bool] = None,
):
    """Build the SpGEMM scalar-prefetch pallas_call for one (A stream
    geometry, condensed-B geometry) pair.

    Call signature of the returned function:
    ``fn(block_window, block_starts, m_blk, col_blk, row_blk, b_vals,
    b_cols)`` with the A stream blocks ``(num_blocks * c_blk, l)``
    (``col_blk`` holds ORIGINAL A columns — B row ids), condensed B
    planes ``(r_rows, k_max)`` (f32 values, int32 columns), returning
    ``(num_windows, l, n_out)`` f32 per-window dense row accumulators.

    Both packed layouts execute here: a padded artifact is just the
    ragged stream whose every window owns ``C_pad/c_blk`` blocks
    (``block_window``/``block_starts`` synthesized from the strides), and
    its all-padding blocks contribute exactly zero.

    BlockSpecs:
      * A stream (m/col/row): HBM -> VMEM tiles of (c_blk, l), one real
        block per grid step;
      * condensed B (values + columns): full-array VMEM residency —
        ``R·k_max·8`` bytes, the condensed footprint;
      * y: the (1, l, n_out) tile of ``block_window[t]``, written once
        per window when the scratch accumulator dumps.

    Memoized on geometry, like every other kernel builder.
    """
    grid = (num_blocks,)
    sched_spec = pl.BlockSpec((c_blk, l), lambda t, bw, bs: (t, 0))
    b_spec = pl.BlockSpec((r_rows, k_max), lambda t, bw, bs: (0, 0))
    out_spec = pl.BlockSpec((1, l, n_out), lambda t, bw, bs: (bw[t], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[sched_spec, sched_spec, sched_spec, b_spec, b_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((l, n_out), jnp.float32)],
    )
    kernel = functools.partial(
        _kernel, l=l, r_rows=r_rows, k_max=k_max, n_out=n_out, c_blk=c_blk
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_windows, l, n_out), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_spgemm",
    )
