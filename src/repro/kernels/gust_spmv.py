"""Flagship Pallas TPU kernel: windowed scheduled GUST SpMV.

TPU adaptation of the paper's three hardware levels (DESIGN.md §2):

  multipliers  -> VPU elementwise multiply of the scheduled value block
                  with the gathered vector block;
  Buffer Filler-> two execution modes for the per-slot gather
                  ``v[Col_sch]``, both fused in-kernel (the scheduler
                  only ever assigns a column to its own lane or the
                  lane-reversed position — load-balance step 3 — so the
                  gather is a segment select plus a straight/flipped
                  select, never random access):

                  * **resident** (``make_gust_spmv``): the vector lives
                    whole in VMEM with its lane-reversed twin, and each
                    block walks the ``seg_count = ceil(n/l)`` column
                    segments eight at a time: a slot's segment ``seg``
                    is sublane ``seg % 8`` of group ``seg // 8``, so one
                    sublane gather per group, batch row and orientation
                    fetches it — ``ceil(seg_count / 8)`` walk steps per
                    block, O(n) VMEM;
                  * **segment-local** (``make_gust_spmv_local``): the
                    pack-time ``seg_blk`` table (scalar-prefetched)
                    steers the pipeline to stream only the ``S_blk``
                    x tiles a block actually references — one
                    (1, B_pad, l) tile per inner grid step — and the
                    select walks only the block-local segments:
                    O(S_blk) gather work per slot, O(l·B) VMEM.  This is
                    the paper's Buffer-Filler locality story (touch only
                    the vector entries a window needs) and removes the
                    VMEM-residency cap on matrix *width*.

  crossbar +   -> in-register lane gathers, one per cycle row of the
  adders          block: ``y_win[:, a] += M_c[Src_sch[c, a]] *
                  G_c[:, Src_sch[c, a]]``, where the pack-time table
                  ``src_blk`` (the inverse of ``row_blk`` over the nonzero
                  slots, -1 for an adder no product reaches, whose value
                  then reads 0) names the lane each adder takes.
                  Collision-freedom of the edge coloring is what makes this
                  exact — within a cycle each adder (output row) receives at
                  most one nonzero partial product, so the route is a
                  partial permutation and inverting it drops only zeros.
                  Mosaic gathers lanes within one (8, 128) tile, so each
                  8-row batch tile takes one gather per pair of 128-lane
                  output and source chunks and a select by source chunk:
                  ``c_blk * (l/128)**2`` gathers per block and tile
                  (``PlanCost.route_lane_gathers``), plus ``(l/128)**2``
                  per block that put the values in adder order; no MXU.

Layout.  The resident kernels take x as ``(B, S8, l)`` f32 — batch row,
column segment padded with zero rows to ``S8``, a multiple of 8, lane —
so eight segments share one sublane tile; the segment-local kernels take
``(seg_count, B_pad, l)`` — column segment, batch padded to a multiple of
8 sublanes, lane — one streamed tile per segment.  Every kernel writes
per-window accumulators ``(num_windows, B_pad, l)``: the lane axis is
the hardware length ``l`` throughout, so the batch costs sublanes, not
lanes.  :func:`repro.kernels.ops.execute_spmm` converts to and from
``(n, B)``.  The lane-reversed view is derived in-kernel
(``_lane_reverse``: once per window into VMEM scratch on the resident
path, once per tile on the local path), so only one copy of x crosses
HBM.

Grid: resident ``(num_windows, num_color_blocks)``; segment-local adds an
inner ``S_blk`` dimension that walks the block's x tiles.  Dimension 1
(and 2) are reductions — the output window tile initializes at the first
color block and accumulates across the rest, the Pallas analogue of the
adders' integrate-then-dump (the "dump signal" is the final grid step).

Double-buffered variants (PR 6).  The ``*_db`` builders collapse the
reduction grid dimensions into an in-kernel ``fori_loop`` and overlap the
fetch of step ``i+1`` with the accumulate of step ``i`` through manual
async copies (:func:`pltpu.make_async_copy`) into a two-slot ping/pong
VMEM scratch — the classic latency-hiding pipeline:

  * :func:`make_gust_spmv_db` streams the **schedule block triple**
    (m/col/src) from ANY-space memory, two ``(c_blk, l)`` tiles in
    flight, x VMEM-resident;
  * :func:`make_gust_spmv_local_db` keeps the schedule blocks
    pipeline-managed and ping/pongs the **x tiles** the block references
    (steered by the scalar-prefetched ``seg_blk`` table), with the
    column decode hoisted out of the tile loop.

Both are bit-identical to their single-buffered twins: the ``fori_loop``
carry performs the same f32 additions in the same order as the revisited
output tile / gather scratch.

Quantized variants (PR 6).  Every builder takes ``quantized=True`` to
accept an int8 value stream plus the pack-time per-block scales
``scale_blk`` (``(T_blk,)`` f32), scalar-prefetched after the steering
tables: the _dequant ``float32(q) * scale`` is fused into the accumulate
(one extra VPU multiply per block), bit-exact with
:func:`repro.kernels.ref.dequant_ref`.

``interpret=None`` on every builder means "interpret only off TPU"
(``_resolve_interpret``), the same rule as ``PlanConfig.interpret``.

All arithmetic accumulates in f32 regardless of input dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "make_gust_spmv",
    "make_gust_spmv_local",
    "make_gust_spmv_db",
    "make_gust_spmv_local_db",
    "route_rows",
    "gather_local_step",
    "stream_copy",
]

#: Sublane granularity of the batch axis in the kernel layout.
_SUBLANES = 8
#: Lanes in one vreg: the lane reversal gathers within this width.
_VREG_LANES = 128


def _resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The one "interpret only off TPU" rule: an explicit bool wins,
    ``None`` compiles on a TPU backend and interprets anywhere else."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def _batch_pad(b: int) -> int:
    """Batch width of the kernel layout: ``b`` rounded up to whole
    sublane groups."""
    return -(-b // _SUBLANES) * _SUBLANES


def _lane_reverse(x):
    """``x[:, ::-1]`` for a 2-D ``(R, l)`` array, spelled so Mosaic lowers
    it: a gather with the reversed-lane index inside each 128-lane chunk
    (the TPU's in-register lane gather), then the chunks in reverse
    order.  Pure data movement, so the result is bit-exact."""
    rows, l = x.shape
    w = _lane_chunk(l)
    rev = (w - 1) - jax.lax.broadcasted_iota(jnp.int32, (rows, w), 1)
    chunks = [
        jnp.take_along_axis(x[:, k * w:(k + 1) * w], rev, axis=1)
        for k in range(l // w)
    ]
    return chunks[0] if len(chunks) == 1 else jnp.concatenate(
        chunks[::-1], axis=1
    )


def _decode_cols(col_blk, *, l):
    """Decode a (C_blk, l) column block once: the column segment of every
    slot and whether it reads the lane-reversed position."""
    col = col_blk.astype(jnp.int32)
    seg = col // l
    lane = jax.lax.broadcasted_iota(jnp.int32, col.shape, 1)
    return seg, (col - seg * l) != lane


def _gather_tile(gs, seg, flip, s, tile):
    """Fold the straight x tile ``tile`` (B_pad, l) of segment ``s`` into
    the per-cycle gather accumulators ``gs`` (one (B_pad, l) array per
    block row): a slot takes its straight or lane-reversed value exactly
    when its segment is ``s``.  A select, never an add, so after every
    segment the accumulators hold ``x[col]`` bit-exactly."""
    rev = _lane_reverse(tile)
    return tuple(
        jnp.where(seg[c:c + 1] == s,
                  jnp.where(flip[c:c + 1], rev, tile), g)
        for c, g in enumerate(gs)
    )


def _zeros_gs(c_blk, bp, l):
    return tuple(jnp.zeros((bp, l), jnp.float32) for _ in range(c_blk))


def _resident_walk_steps(seg_count: int) -> int:
    """Steps of the resident walk per color block: one per group of
    eight column segments (one sublane gather picks among eight)."""
    return -(-seg_count // _SUBLANES)


def _resident_x_rows(seg_count: int) -> int:
    """Segment rows of the resident x layout ``(b, S8, l)``:
    ``seg_count`` rounded up to whole groups of eight."""
    return _resident_walk_steps(seg_count) * _SUBLANES


def _reverse_x(xs_ref, xr_ref):
    """Fill ``xr_ref`` with the lane-reversed twin of the resident x
    ``(b, S8, l)``, one batch row at a time."""
    for r in range(xs_ref.shape[0]):
        xr_ref[r] = _lane_reverse(xs_ref[r])


def _sublane_take(tile, idx):
    """``tile[idx[i, j], j]`` for an (8, l) tile: the sublane gather, one
    (8, l) index chunk at a time (block heights that are no multiple of
    8 run only in interpret mode, in one piece)."""
    c = idx.shape[0]
    step = _SUBLANES if c % _SUBLANES == 0 else c
    chunks = [
        jnp.take_along_axis(tile, idx[k:k + step], axis=0,
                            mode="promise_in_bounds")
        for k in range(0, c, step)
    ]
    return chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks)


def _gather_resident(col_blk, xs_ref, xr_ref, *, l, seg_count):
    """Resident Buffer Filler for one (C_blk, l) column block against the
    VMEM-resident x ``xs_ref`` (b, S8, l) and its lane-reversed twin
    ``xr_ref``.  Segment ``seg`` sits in group ``seg // 8`` at sublane
    ``seg % 8``, so each walk step gathers one group per batch row with
    two sublane gathers (straight, reversed), picks by the flip and keeps
    the slots whose group it is: ``ceil(seg_count / 8)`` steps.  Pure
    data movement, so every slot holds ``x[col]`` bit-exactly.  Returns
    the per-cycle tuple of (B_pad, l) arrays :func:`route_rows` takes."""
    seg, flip = _decode_cols(col_blk, l=l)
    lo, hi = seg % _SUBLANES, seg // _SUBLANES
    b, (c_blk, _) = xs_ref.shape[0], seg.shape

    def body(g, rows):
        at = pl.ds(pl.multiple_of(g * _SUBLANES, _SUBLANES), _SUBLANES)
        return tuple(
            jnp.where(hi == g,
                      jnp.where(flip, _sublane_take(xr_ref[r, at], lo),
                                _sublane_take(xs_ref[r, at], lo)),
                      acc)
            for r, acc in enumerate(rows)
        )

    rows = jax.lax.fori_loop(
        0, _resident_walk_steps(seg_count), body,
        tuple(jnp.zeros((c_blk, l), jnp.float32) for _ in range(b)),
    )
    bp = _batch_pad(b)
    pad = (jnp.zeros((c_blk, l), jnp.float32),) * (bp - b)
    per_cycle = jnp.swapaxes(jnp.stack(rows + pad), 0, 1)  # (c_blk, bp, l)
    return tuple(per_cycle[c] for c in range(c_blk))


def _lane_chunk(l: int) -> int:
    """Width of one in-register lane gather: a vreg's 128 lanes when
    ``l`` is a multiple of them, else all of ``l`` in one piece."""
    return _VREG_LANES if l > _VREG_LANES and l % _VREG_LANES == 0 else l


def _route_lane_gathers(c_blk: int, l: int) -> int:
    """Lane gathers of :func:`route_rows` per color block and 8-row batch
    tile: one per cycle row, output chunk and source chunk."""
    return c_blk * (l // _lane_chunk(l)) ** 2


def _gather_lanes(v, chunk, lane, *, w):
    """``y[:, a] = v[:, chunk[a] * w + lane[a]]`` for a (R, l) ``v`` and
    the decoded source table, one row per row of ``v`` or one (1, l) row
    for all of them; lanes whose ``chunk`` is -1 (no source) get some
    lane of ``v``.  Mosaic gathers lanes one (8, w) tile at a time with
    an index of the operand's own shape, so each 8-row tile builds every
    output chunk from one gather per source chunk and a select of the
    lanes whose source lies in it."""
    rows, l = v.shape
    step = _SUBLANES if rows % _SUBLANES == 0 else rows
    n = l // w
    tiles = []
    for t in range(0, rows, step):
        at = slice(0, 1) if lane.shape[0] == 1 else slice(t, t + step)
        outs = []
        for k in range(n):
            ks = slice(k * w, (k + 1) * w)
            idx = jnp.broadcast_to(lane[at, ks], (step, w))
            y = None
            for s in range(n):
                got = jnp.take_along_axis(v[t:t + step, s * w:(s + 1) * w],
                                          idx, axis=1,
                                          mode="promise_in_bounds")
                y = got if y is None else jnp.where(chunk[at, ks] == s, got, y)
            outs.append(y)
        tiles.append(outs[0] if n == 1 else jnp.concatenate(outs, axis=1))
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles)


def route_rows(m_blk, gs, src_blk, *, l):
    """Multipliers + crossbar + adders for one block, the crossbar as
    in-register lane gathers by the pack-time source-lane table ``src``
    (``-1``: no product reaches the adder): the block's values are put in
    adder order once, ``m_src[c, a] = m[c, src[c, a]]`` (0 where no
    source), then per cycle row ``c`` adder ``a`` takes ``m_src[c, a] *
    g[c][:, src[c, a]]``.  Returns the block's (B_pad, l) f32
    contribution to its window accumulator, summed over the cycle rows
    in order.  Collision-freedom gives each adder at most one nonzero
    product per cycle, and every lane the table leaves out carries
    m == 0, so each adder gets exactly the product the one-hot route
    sends it: exact up to the sign of a zero.  Routing the operands, not
    the products, keeps the multiply next to the add it feeds, so the
    interpreter's compiler fuses (or not) the two alike in every
    kernel."""
    w = _lane_chunk(l)
    src = src_blk.astype(jnp.int32)
    chunk = src // w  # floor: a missing source (-1) is chunk -1, lane w-1
    lane = src - chunk * w
    m_src = jnp.where(src >= 0, _gather_lanes(m_blk, chunk, lane, w=w), 0.0)
    acc = None
    for c, g in enumerate(gs):
        y = m_src[c:c + 1] * _gather_lanes(g, chunk[c:c + 1],
                                           lane[c:c + 1], w=w)
        acc = y if acc is None else acc + y
    return acc


def _dequant(m_blk, scale):
    """Value block as f32, times its block scale when quantized."""
    m = m_blk.astype(jnp.float32)
    return m if scale is None else m * scale


def _accumulate_out(y_ref, acc, first):
    """Integrate-then-dump into the revisited (1, B_pad, l) window tile."""

    @pl.when(first)
    def _init():
        y_ref[0] = acc

    @pl.when(jnp.logical_not(first))
    def _accum():
        y_ref[0] += acc


def _kernel(*refs, l, seg_count, num_cb, quantized):
    scale_ref = refs[0] if quantized else None
    m_ref, col_ref, src_ref, xs_ref, y_ref, xr_scr = refs[quantized:]
    w, cb = pl.program_id(0), pl.program_id(1)

    @pl.when(cb == 0)
    def _reverse():
        _reverse_x(xs_ref, xr_scr)

    scale = None if scale_ref is None else scale_ref[w * num_cb + cb]
    gs = _gather_resident(col_ref[...], xs_ref, xr_scr, l=l,
                          seg_count=seg_count)
    acc = route_rows(_dequant(m_ref[...], scale), gs, src_ref[...], l=l)
    _accumulate_out(y_ref, acc, cb == 0)


@functools.lru_cache(maxsize=256)
def make_gust_spmv(
    num_windows: int,
    c_pad: int,
    l: int,
    seg_count: int,
    b: int,
    *,
    c_blk: int = 8,
    interpret: Optional[bool] = None,
    quantized: bool = False,
):
    """Build the resident-gather pallas_call for a fixed packed-schedule
    geometry.

    Memoized on geometry (all args are hashable scalars): ``gust_spmm``
    calls this on every trace, and direct callers (tests, the unfused
    path) would otherwise rebuild the kernel closure — and retrace it —
    on every invocation.

    BlockSpecs:
      * schedule stream (m/col/src): HBM -> VMEM tiles of (c_blk, l), one
        per grid step — the Buffer Filler pipeline;
      * x (b, S8, l), straight only: full-array VMEM residency; its
        lane-reversed twin is derived into VMEM scratch at each window's
        first color block;
      * y: one (1, B_pad, l) accumulator tile per window, revisited
        across the color-block (reduction) grid dimension.

    Call signature: ``fn(m_blk, col_blk, src_blk, xs)``; with
    ``quantized=True`` the per-block scales lead as the scalar-prefetch
    operand: ``fn(scale_blk, m_blk, col_blk, src_blk, xs)``.
    """
    if c_pad % c_blk:
        raise ValueError("c_pad must be a multiple of c_blk")
    num_cb = c_pad // c_blk
    bp = _batch_pad(b)
    grid = (num_windows, num_cb)

    rows = _resident_x_rows(seg_count)

    sched_spec = pl.BlockSpec(
        (c_blk, l), lambda w, cb, *_: (w * num_cb + cb, 0)
    )
    x_spec = pl.BlockSpec((b, rows, l), lambda w, cb, *_: (0, 0, 0))
    out_spec = pl.BlockSpec((1, bp, l), lambda w, cb, *_: (w, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=int(quantized),
        grid=grid,
        in_specs=[sched_spec, sched_spec, sched_spec, x_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((b, rows, l), jnp.float32)],
    )
    kernel = functools.partial(
        _kernel, l=l, seg_count=seg_count, num_cb=num_cb, quantized=quantized
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_windows, bp, l), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_spmv_padded_resident",
    )


def gather_local_step(col_ref, xt_ref, s, g_scr, *, l):
    """One segment-local gather step, shared by the padded and ragged
    local kernels: fold the single streamed x tile ``xt_ref`` (the
    block's ``s``-th referenced segment) into the (C_blk, B_pad, l)
    gather scratch."""
    seg, flip = _decode_cols(col_ref[...], l=l)
    gs = tuple(g_scr[c] for c in range(g_scr.shape[0]))
    for c, g in enumerate(_gather_tile(gs, seg, flip, s, xt_ref[0])):
        g_scr[c] = g


def _local_flush(m_ref, src_ref, gs, y_ref, first, *, l, scale):
    """Shared flush of the local kernels: _dequant (when quantized), the
    route and multiply of the gathered block (:func:`route_rows`), then
    init-or-accumulate the window tile."""
    acc = route_rows(_dequant(m_ref[...], scale), gs, src_ref[...], l=l)
    _accumulate_out(y_ref, acc, first)


def _local_kernel(*refs, l, s_blk, num_cb, quantized):
    seg_ref = refs[0]
    scale_ref = refs[1] if quantized else None
    m_ref, col_ref, src_ref, xt_ref, y_ref, g_scr = refs[1 + quantized:]
    w, cb, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(s == 0)
    def _zero():
        g_scr[...] = jnp.zeros_like(g_scr)

    gather_local_step(col_ref, xt_ref, s, g_scr, l=l)

    @pl.when(s == s_blk - 1)
    def _flush():
        scale = None if scale_ref is None else scale_ref[w * num_cb + cb]
        gs = tuple(g_scr[c] for c in range(g_scr.shape[0]))
        _local_flush(m_ref, src_ref, gs, y_ref, cb == 0, l=l, scale=scale)


@functools.lru_cache(maxsize=256)
def make_gust_spmv_local(
    num_windows: int,
    c_pad: int,
    l: int,
    s_blk: int,
    b: int,
    *,
    c_blk: int = 8,
    interpret: Optional[bool] = None,
    quantized: bool = False,
):
    """Build the segment-local pallas_call for a padded-schedule geometry.

    Call signature of the returned function:
    ``fn(seg_flat, [scale_blk,] m_blk, col_loc, src_blk, xs)`` where
    ``seg_flat`` is the pack-time segment table flattened to
    ``(T_blk * S_blk,)`` int32 (scalar-prefetched: it steers the x-tile
    pipeline before each body runs), ``col_loc`` holds the block-local
    columns, and ``xs`` is the straight-layout x ``(seg_count, B_pad,
    l)`` — which stays in HBM-sized memory; only one (1, B_pad, l) tile
    is in VMEM per grid step.

    Grid ``(num_windows, c_pad/c_blk, S_blk)``: the inner dimension walks
    the ``S_blk`` x tiles the block references (``seg_flat[t*S_blk+s]``),
    accumulating the gathered block in VMEM scratch; the multiply +
    route fire on the last tile.  Gather work per block is
    O(S_blk · C_blk · l) instead of the resident walk's
    ``ceil(seg_count / 8)`` steps of every batch row, and x VMEM
    residency is one tile instead of the whole vector — the wide-matrix
    fast path.
    """
    if c_pad % c_blk:
        raise ValueError("c_pad must be a multiple of c_blk")
    num_cb = c_pad // c_blk
    bp = _batch_pad(b)
    grid = (num_windows, num_cb, s_blk)

    sched_spec = pl.BlockSpec(
        (c_blk, l), lambda w, cb, s, seg, *_: (w * num_cb + cb, 0)
    )
    x_spec = pl.BlockSpec(
        (1, bp, l),
        lambda w, cb, s, seg, *_: (seg[(w * num_cb + cb) * s_blk + s], 0, 0),
    )
    out_spec = pl.BlockSpec((1, bp, l), lambda w, cb, s, seg, *_: (w, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + int(quantized),
        grid=grid,
        in_specs=[sched_spec, sched_spec, sched_spec, x_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((c_blk, bp, l), jnp.float32)],
    )
    kernel = functools.partial(
        _local_kernel, l=l, s_blk=s_blk, num_cb=num_cb, quantized=quantized
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_windows, bp, l), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_spmv_padded_local",
    )


# ---------------------------------------------------------------------------
# Double-buffered variants: manual async-copy ping/pong pipelines.
# ---------------------------------------------------------------------------


def stream_copy(src_ref, scr_ref, sem, slot, start_row, rows):
    """Async-copy descriptor for one stream tile: rows
    ``start_row : start_row + rows`` of ``src_ref`` (ANY-space) into slot
    ``slot`` of the (2, rows, ...) ping/pong scratch, tracked by the
    (already slot-indexed) DMA semaphore ``sem``.  ``.start()`` on the
    descriptor kicks the DMA; an identically-constructed descriptor's
    ``.wait()`` blocks on its completion."""
    return pltpu.make_async_copy(
        src_ref.at[pl.ds(start_row, rows)],
        scr_ref.at[slot],
        sem,
    )


def _db_kernel(*refs, l, seg_count, c_blk, num_cb, quantized):
    """Double-buffered resident kernel body: grid (W,), the color-block
    reduction runs as an in-kernel fori_loop whose ping/pong scratch
    holds two schedule block triples — the DMA of triple ``i+1`` overlaps
    the gather/multiply/route of triple ``i``.  The f32 additions happen
    in the same order as the single-buffered kernel's revisited output
    tile, so the result is bitwise identical."""
    scale_ref = refs[0] if quantized else None
    (m_ref, col_ref, src_ref, xs_ref, y_ref,
     m_scr, col_scr, src_scr, sems, xr_scr) = refs[quantized:]
    w = pl.program_id(0)

    def copies(slot, blk):
        start = (w * num_cb + blk) * c_blk
        return (
            stream_copy(m_ref, m_scr, sems.at[slot, 0], slot, start, c_blk),
            stream_copy(col_ref, col_scr, sems.at[slot, 1], slot, start,
                        c_blk),
            stream_copy(src_ref, src_scr, sems.at[slot, 2], slot, start,
                        c_blk),
        )

    for c in copies(0, 0):
        c.start()
    _reverse_x(xs_ref, xr_scr)

    def body(i, acc):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < num_cb)
        def _prefetch():
            for c in copies(1 - slot, i + 1):
                c.start()

        for c in copies(slot, i):
            c.wait()
        scale = None if scale_ref is None else scale_ref[w * num_cb + i]
        gs = _gather_resident(col_scr[slot], xs_ref, xr_scr, l=l,
                              seg_count=seg_count)
        return acc + route_rows(
            _dequant(m_scr[slot], scale), gs, src_scr[slot], l=l
        )

    y_ref[0] = jax.lax.fori_loop(
        0, num_cb, body, jnp.zeros(y_ref.shape[1:], jnp.float32)
    )


@functools.lru_cache(maxsize=256)
def make_gust_spmv_db(
    num_windows: int,
    c_pad: int,
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
    """Double-buffered twin of :func:`make_gust_spmv`: same call
    signature and bitwise-identical output, but the schedule stream
    (m/col/src) is fetched by manual async copies into a two-slot
    ping/pong scratch so the DMA of color block ``i+1`` overlaps the
    math of block ``i``, and the whole per-window reduction runs in one
    grid step (grid ``(W,)`` instead of ``(W, num_cb)``).

    The scratch dtypes must match the operands, so the builder takes the
    stream's ``value_dtype``/``index_dtype`` names (the geometry memo
    includes them)."""
    if c_pad % c_blk:
        raise ValueError("c_pad must be a multiple of c_blk")
    num_cb = c_pad // c_blk
    bp = _batch_pad(b)
    vdt, idt = jnp.dtype(value_dtype), jnp.dtype(index_dtype)
    rows = _resident_x_rows(seg_count)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=int(quantized),
        grid=(num_windows,),
        in_specs=[
            any_spec, any_spec, any_spec,
            pl.BlockSpec((b, rows, l), lambda w, *_: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bp, l), lambda w, *_: (w, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, c_blk, l), vdt),
            pltpu.VMEM((2, c_blk, l), idt),
            pltpu.VMEM((2, c_blk, l), idt),
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.VMEM((b, rows, l), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _db_kernel, l=l, seg_count=seg_count, c_blk=c_blk, num_cb=num_cb,
        quantized=quantized,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_windows, bp, l), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_spmv_padded_resident_db",
    )


def _local_db_block(seg_ref, col_ref, xs_ref, xt_scr, sems, t, *, l, s_blk):
    """Shared double-buffered segment-local gather of stream block ``t``:
    ping/pong its S_blk x tiles (``seg_ref[t*s_blk + s]`` steers each
    copy), folding each into the per-cycle gather carry — the same
    selects, in the same order, as the single-buffered kernel's gather
    scratch.  The column decode is hoisted out of the tile loop (one
    decode per block instead of one per tile).  Returns the gathered
    tuple."""

    def copy(slot, s):
        return stream_copy(
            xs_ref, xt_scr, sems.at[slot], slot, seg_ref[t * s_blk + s], 1
        )

    copy(0, 0).start()
    seg, flip = _decode_cols(col_ref[...], l=l)
    c_blk, bp = seg.shape[0], xt_scr.shape[2]

    def body(s, gs):
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < s_blk)
        def _prefetch():
            copy(1 - slot, s + 1).start()

        copy(slot, s).wait()
        return _gather_tile(gs, seg, flip, s, xt_scr[slot][0])

    return jax.lax.fori_loop(0, s_blk, body, _zeros_gs(c_blk, bp, l))


def _local_db_kernel(*refs, l, s_blk, num_cb, quantized):
    seg_ref = refs[0]
    scale_ref = refs[1] if quantized else None
    m_ref, col_ref, src_ref, xs_ref, y_ref, xt_scr, sems = refs[1 + quantized:]
    w, cb = pl.program_id(0), pl.program_id(1)
    t = w * num_cb + cb
    gs = _local_db_block(seg_ref, col_ref, xs_ref, xt_scr, sems, t,
                        l=l, s_blk=s_blk)
    scale = None if scale_ref is None else scale_ref[t]
    _local_flush(m_ref, src_ref, gs, y_ref, cb == 0, l=l, scale=scale)


@functools.lru_cache(maxsize=256)
def make_gust_spmv_local_db(
    num_windows: int,
    c_pad: int,
    l: int,
    s_blk: int,
    b: int,
    *,
    c_blk: int = 8,
    interpret: Optional[bool] = None,
    quantized: bool = False,
):
    """Double-buffered twin of :func:`make_gust_spmv_local`: same call
    signature and bitwise-identical output.  The schedule blocks stay
    pipeline-managed (one (c_blk, l) triple per grid step), x lives in
    ANY-space memory, and the block's ``S_blk`` referenced tiles are
    fetched by manual async copies into a two-slot ping/pong scratch —
    the fetch of tile ``s+1`` overlaps the gather of tile ``s``, and the
    ``S_blk`` inner grid dimension collapses into the kernel (grid
    ``(W, num_cb)`` instead of ``(W, num_cb, S_blk)``), which also hoists
    the column decode and the flush's scratch round-trip out of the tile
    loop."""
    if c_pad % c_blk:
        raise ValueError("c_pad must be a multiple of c_blk")
    num_cb = c_pad // c_blk
    bp = _batch_pad(b)
    grid = (num_windows, num_cb)

    sched_spec = pl.BlockSpec(
        (c_blk, l), lambda w, cb, seg, *_: (w * num_cb + cb, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + int(quantized),
        grid=grid,
        in_specs=[sched_spec, sched_spec, sched_spec,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, bp, l), lambda w, cb, seg, *_: (w, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, bp, l), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _local_db_kernel, l=l, s_blk=s_blk, num_cb=num_cb, quantized=quantized
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_windows, bp, l), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="gust_spmv_padded_local_db",
    )
