"""Ragged color-block streaming Pallas TPU kernels for GUST SpMV.

The padded flagship kernel (``gust_spmv.py``) runs a dense
``(W, C_pad/c_blk)`` grid: every window executes the color-block count of
the *heaviest* window, so on skewed (power-law) matrices most grid steps
stream and multiply all-zero padding blocks.  These kernels execute the
ragged block stream built by :func:`repro.core.packing.pack_ragged`
instead: a **grid over the real blocks only** (``T_blk`` steps,
``T_blk = Σ_w max(ceil(C_w / c_blk), 1)``), driven by scalar prefetch
(``pltpu.PrefetchScalarGridSpec``).

Two scalar-prefetch operands derived from ``window_starts`` steer the
pipeline before each kernel body runs:

  block_window (T_blk,)  — window id of block ``t``; indexes the output
                           BlockSpec so block ``t`` lands on its window's
                           (1, B_pad, l) accumulator tile;
  block_starts (W + 1,)  — per-window block prefix; ``t ==
                           block_starts[block_window[t]]`` marks a
                           window's first block.

Blocks of one window are contiguous in the stream, so the output tile is
revisited across exactly that window's blocks: the accumulator
initializes on the window's first block and is flushed when the grid
moves to the next window's tile — the paper's integrate-then-dump, minus
the dead padding cycles.

Like the padded flagship, the Buffer-Filler gather runs in one of two
modes, and every kernel shares the padded kernels' x / output layouts
(resident or local) and per-block math (:mod:`repro.kernels.gust_spmv`):

  * **resident** (:func:`make_gust_spmv_ragged`): x fully VMEM-resident
    in the ``(b, S8, l)`` layout, the walk gathers eight segments a step
    (``ceil(seg_count / 8)`` steps per block);
  * **segment-local** (:func:`make_gust_spmv_ragged_local`): a third
    scalar-prefetch operand — the pack-time ``seg_blk`` table — steers an
    inner ``S_blk`` grid dimension that streams only the x tiles block
    ``t`` references, shrinking per-block gather work from a walk over
    all ``seg_count`` segments to O(S_blk) tiles and x VMEM residency to
    a single (1, B_pad, l) tile.

Double-buffered variants (PR 6), bitwise-identical to their
single-buffered twins (same f32 additions in the same order):

  * :func:`make_gust_spmv_ragged_db`: grid ``(W,)``; each window walks
    its own block range ``block_starts[w]:block_starts[w+1]`` in an
    in-kernel fori_loop, ping/ponging the schedule block triple through
    manual async copies so the DMA of block ``t+1`` overlaps the math of
    block ``t`` (``block_window`` is not needed — the window IS the grid
    step);
  * :func:`make_gust_spmv_ragged_local_db`: grid ``(num_blocks,)``; the
    ``S_blk`` x tiles of each block ping/pong through VMEM scratch with
    the column decode hoisted out of the tile loop.

Every builder takes ``quantized=True`` to accept an int8 value stream
plus the per-block scales ``scale_blk`` (``(T_blk,)`` f32), the last
scalar-prefetch operand (_dequant fused into the accumulate — see
``gust_spmv.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gust_spmv import (
    _accumulate_out,
    _batch_pad,
    _dequant,
    _gather_resident,
    _local_db_block,
    _local_flush,
    _resident_x_rows,
    _resolve_interpret,
    _reverse_x,
    gather_local_step,
    route_rows,
    stream_copy,
)

__all__ = [
    "make_gust_spmv_ragged",
    "make_gust_spmv_ragged_local",
    "make_gust_spmv_ragged_db",
    "make_gust_spmv_ragged_local_db",
]


def _kernel(*refs, l, seg_count, quantized):
    bw_ref, bs_ref = refs[:2]
    scale_ref = refs[2] if quantized else None
    m_ref, col_ref, src_ref, xs_ref, y_ref, xr_scr = refs[2 + quantized:]
    t = pl.program_id(0)
    w = bw_ref[t]
    first = t == bs_ref[w]

    @pl.when(first)
    def _reverse():
        _reverse_x(xs_ref, xr_scr)

    scale = None if scale_ref is None else scale_ref[t]
    gs = _gather_resident(col_ref[...], xs_ref, xr_scr, l=l,
                          seg_count=seg_count)
    acc = route_rows(_dequant(m_ref[...], scale), gs, src_ref[...], l=l)
    _accumulate_out(y_ref, acc, first)


@functools.lru_cache(maxsize=256)
def make_gust_spmv_ragged(
    num_blocks: int,
    num_windows: int,
    l: int,
    seg_count: int,
    b: int,
    *,
    c_blk: int = 8,
    interpret: Optional[bool] = None,
    quantized: bool = False,
):
    """Build the resident-gather scalar-prefetch pallas_call for a
    ragged-stream geometry.

    Call signature of the returned function:
    ``fn(block_window, block_starts, [scale_blk,] m_blk, col_blk,
    src_blk, xs)`` with the stream blocks ``(num_blocks * c_blk, l)`` and
    the straight resident x layout ``(b, S8, l)`` (the lane-reversed twin
    is derived in-kernel, once per window); returns ``(num_windows,
    B_pad, l)`` f32 per-window accumulators.

    BlockSpecs:
      * schedule stream (m/col/src): HBM -> VMEM tiles of (c_blk, l), one
        real block per grid step — no padding blocks are ever streamed;
      * x (straight): full-array VMEM residency;
      * y: the (1, B_pad, l) accumulator tile of ``block_window[t]``,
        revisited across that window's contiguous blocks.

    Memoized on geometry, like the padded builder.
    """
    bp = _batch_pad(b)
    grid = (num_blocks,)
    rows = _resident_x_rows(seg_count)
    sched_spec = pl.BlockSpec((c_blk, l), lambda t, bw, bs, *_: (t, 0))
    x_spec = pl.BlockSpec((b, rows, l), lambda t, bw, bs, *_: (0, 0, 0))
    out_spec = pl.BlockSpec((1, bp, l), lambda t, bw, bs, *_: (bw[t], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + int(quantized),
        grid=grid,
        in_specs=[sched_spec, sched_spec, sched_spec, x_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((b, rows, l), jnp.float32)],
    )
    kernel = functools.partial(
        _kernel, l=l, seg_count=seg_count, quantized=quantized
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_windows, bp, l), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_spmv_ragged_resident",
    )


def _local_kernel(*refs, l, s_blk, quantized):
    bw_ref, bs_ref = refs[:2]
    scale_ref = refs[3] if quantized else None
    m_ref, col_ref, src_ref, xt_ref, y_ref, g_scr = refs[3 + quantized:]
    t, s = pl.program_id(0), pl.program_id(1)
    w = bw_ref[t]

    @pl.when(s == 0)
    def _zero():
        g_scr[...] = jnp.zeros_like(g_scr)

    gather_local_step(col_ref, xt_ref, s, g_scr, l=l)

    @pl.when(s == s_blk - 1)
    def _flush():
        scale = None if scale_ref is None else scale_ref[t]
        gs = tuple(g_scr[c] for c in range(g_scr.shape[0]))
        _local_flush(m_ref, src_ref, gs, y_ref, t == bs_ref[w],
                    l=l, scale=scale)


@functools.lru_cache(maxsize=256)
def make_gust_spmv_ragged_local(
    num_blocks: int,
    num_windows: int,
    l: int,
    s_blk: int,
    b: int,
    *,
    c_blk: int = 8,
    interpret: Optional[bool] = None,
    quantized: bool = False,
):
    """Build the segment-local scalar-prefetch pallas_call for a
    ragged-stream geometry.

    Call signature of the returned function:
    ``fn(block_window, block_starts, seg_flat, [scale_blk,] m_blk,
    col_loc, src_blk, xs)`` — ``seg_flat`` is the pack-time segment table
    flattened to ``(T_blk * S_blk,)`` int32 and ``col_loc`` the
    block-local columns.  Grid ``(num_blocks, S_blk)``: the inner
    dimension streams the x tile of segment ``seg_flat[t*S_blk + s]``
    (one (1, B_pad, l) tile in VMEM per step), the gathered block
    accumulates in VMEM scratch, and the multiply + route fire
    on the last tile.  Combines the ragged stream's "no dead padding
    cycles" with the segment-local gather's O(S_blk) per-block cost — the
    full GUST utilization story.
    """
    bp = _batch_pad(b)
    grid = (num_blocks, s_blk)
    sched_spec = pl.BlockSpec(
        (c_blk, l), lambda t, s, bw, bs, seg, *_: (t, 0)
    )
    x_spec = pl.BlockSpec(
        (1, bp, l), lambda t, s, bw, bs, seg, *_: (seg[t * s_blk + s], 0, 0)
    )
    out_spec = pl.BlockSpec(
        (1, bp, l), lambda t, s, bw, bs, seg, *_: (bw[t], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + int(quantized),
        grid=grid,
        in_specs=[sched_spec, sched_spec, sched_spec, x_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((c_blk, bp, l), jnp.float32)],
    )
    kernel = functools.partial(
        _local_kernel, l=l, s_blk=s_blk, quantized=quantized
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_windows, bp, l), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_spmv_ragged_local",
    )


# ---------------------------------------------------------------------------
# Double-buffered variants.
# ---------------------------------------------------------------------------


def _db_kernel(*refs, l, seg_count, c_blk, quantized):
    """Grid (W,): window ``w`` walks its own ragged block range in a
    fori_loop, the schedule block triple double-buffered through manual
    async copies.  Same f32 additions in the same order as the
    single-buffered ragged kernel's revisited accumulator tile —
    bitwise identical."""
    bs_ref = refs[0]
    scale_ref = refs[1] if quantized else None
    (m_ref, col_ref, src_ref, xs_ref, y_ref,
     m_scr, col_scr, src_scr, sems, xr_scr) = refs[1 + quantized:]
    w = pl.program_id(0)
    t0 = bs_ref[w]
    count = bs_ref[w + 1] - t0

    def copies(slot, t):
        start = t * c_blk
        return (
            stream_copy(m_ref, m_scr, sems.at[slot, 0], slot, start, c_blk),
            stream_copy(col_ref, col_scr, sems.at[slot, 1], slot, start,
                        c_blk),
            stream_copy(src_ref, src_scr, sems.at[slot, 2], slot, start,
                        c_blk),
        )

    for c in copies(0, t0):
        c.start()
    _reverse_x(xs_ref, xr_scr)

    def body(i, acc):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < count)
        def _prefetch():
            for c in copies(1 - slot, t0 + i + 1):
                c.start()

        for c in copies(slot, t0 + i):
            c.wait()
        scale = None if scale_ref is None else scale_ref[t0 + i]
        gs = _gather_resident(col_scr[slot], xs_ref, xr_scr, l=l,
                              seg_count=seg_count)
        return acc + route_rows(
            _dequant(m_scr[slot], scale), gs, src_scr[slot], l=l
        )

    y_ref[0] = jax.lax.fori_loop(
        0, count, body, jnp.zeros(y_ref.shape[1:], jnp.float32)
    )


@functools.lru_cache(maxsize=256)
def make_gust_spmv_ragged_db(
    num_blocks: int,
    num_windows: int,
    l: int,
    seg_count: int,
    b: int,
    *,
    c_blk: int = 8,
    interpret: Optional[bool] = None,
    quantized: bool = False,
    value_dtype: str = "float32",
    index_dtype: str = "int32",
):
    """Double-buffered twin of :func:`make_gust_spmv_ragged`, grid
    ``(W,)``.  Call signature:
    ``fn(block_starts, [scale_blk,] m_blk, col_blk, src_blk, xs)`` —
    ``block_window`` is not needed (the window is the grid step; its
    block range comes from ``block_starts`` alone).  The schedule stream
    lives in ANY-space memory and ping/pongs through VMEM scratch sized
    at the stream's actual dtypes."""
    bp = _batch_pad(b)
    vdt, idt = jnp.dtype(value_dtype), jnp.dtype(index_dtype)
    rows = _resident_x_rows(seg_count)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + int(quantized),
        grid=(num_windows,),
        in_specs=[
            any_spec, any_spec, any_spec,
            pl.BlockSpec((b, rows, l), lambda w, bs, *_: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bp, l), lambda w, bs, *_: (w, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, c_blk, l), vdt),
            pltpu.VMEM((2, c_blk, l), idt),
            pltpu.VMEM((2, c_blk, l), idt),
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.VMEM((b, rows, l), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _db_kernel, l=l, seg_count=seg_count, c_blk=c_blk, quantized=quantized
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_windows, bp, l), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_spmv_ragged_resident_db",
    )


def _local_db_kernel(*refs, l, s_blk, quantized):
    """Grid (num_blocks,): schedule blocks pipeline-managed, the block's
    S_blk x tiles double-buffered through manual async copies with the
    column decode hoisted out of the tile loop (the ragged twin of the
    padded ``_local_db_kernel``)."""
    bw_ref, bs_ref, seg_ref = refs[:3]
    scale_ref = refs[3] if quantized else None
    m_ref, col_ref, src_ref, xs_ref, y_ref, xt_scr, sems = refs[3 + quantized:]
    t = pl.program_id(0)
    w = bw_ref[t]
    gs = _local_db_block(seg_ref, col_ref, xs_ref, xt_scr, sems, t,
                        l=l, s_blk=s_blk)
    scale = None if scale_ref is None else scale_ref[t]
    _local_flush(m_ref, src_ref, gs, y_ref, t == bs_ref[w], l=l, scale=scale)


@functools.lru_cache(maxsize=256)
def make_gust_spmv_ragged_local_db(
    num_blocks: int,
    num_windows: int,
    l: int,
    s_blk: int,
    b: int,
    *,
    c_blk: int = 8,
    interpret: Optional[bool] = None,
    quantized: bool = False,
):
    """Double-buffered twin of :func:`make_gust_spmv_ragged_local`: same
    call signature and bitwise-identical output, grid ``(num_blocks,)``
    (the ``S_blk`` inner dimension collapses into the kernel).  x lives
    in ANY-space memory; the block's referenced tiles ping/pong through
    a two-slot VMEM scratch so the fetch of tile ``s+1`` overlaps the
    gather of tile ``s``."""
    bp = _batch_pad(b)
    sched_spec = pl.BlockSpec(
        (c_blk, l), lambda t, bw, bs, seg, *_: (t, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + int(quantized),
        grid=(num_blocks,),
        in_specs=[sched_spec, sched_spec, sched_spec,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, bp, l), lambda t, bw, bs, seg, *_: (bw[t], 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, 1, bp, l), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _local_db_kernel, l=l, s_blk=s_blk, quantized=quantized
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_windows, bp, l), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_spmv_ragged_local_db",
    )
