"""Canonical ragged→packed conversion for the GUST scheduled format.

This module is the single home of the packed scheduled format: every
execution path (pure-jnp oracle, Pallas kernel, distributed row-window
split, LM serving) consumes :class:`PackedSchedule` built here.  The
conversion is fully vectorized — one scatter by ``window_starts``-derived
global indices instead of a Python loop over windows — so packing is
O(nnz) numpy work even for schedules with 10⁵ windows.

Scheduled format lifecycle
--------------------------

The front door for all of this is the plan/execute API
(:mod:`repro.core.plan`): ``repro.plan(matrix, PlanConfig(...))`` runs
steps 1-2 once (through the cache of step 4) and returns a
:class:`~repro.core.plan.GustPlan` whose ``.spmv``/``.spmm``/``.shard``
run step 3 any number of times — the paper's schedule-once/execute-many
contract as a type.  The steps themselves:

1. **Schedule (ragged).**  ``core.scheduler.schedule`` edge-colors the
   bipartite window graphs and emits a :class:`~repro.core.formats.
   GustSchedule`: three ``(C_total, l)`` arrays plus the per-window color
   prefix ``window_starts``.  Window ``w`` owns the global cycle rows
   ``window_starts[w]:window_starts[w+1]`` — a *ragged* layout (windows
   have different color counts).  Computed once per matrix; reused for
   every vector (paper §3.3/§5.3 amortization).

2. **Pack (fixed-shape).**  Two fixed-shape layouts share the padding
   invariants below:

   * :func:`pack_schedule` (*padded*) pads every window to a common
     ``C_pad`` (max window colors rounded up to ``c_blk``) and reshapes
     to ``(W * C_pad, l)`` blocks — a JAX pytree of plain arrays that can
     be jit-ed over, sharded, donated, stacked across layers, and
     described by ``ShapeDtypeStruct`` (:func:`packed_spec`) without
     running the scheduler.
   * :func:`pack_ragged` (*ragged block stream*) keeps only each window's
     actual ``max(ceil(C_w / c_blk), 1)`` cycle blocks, flattened into one
     ``(T_blk * c_blk, l)`` stream, plus scalar metadata derived from
     ``window_starts``: ``block_window`` (window id of each block,
     ``(T_blk,)``) and ``block_starts`` (per-window block prefix,
     ``(W + 1,)``).  On skewed matrices — where ``max_w C_w`` far exceeds
     the mean — this streams only real work instead of ``W * C_pad``
     mostly-zero rows.  :func:`pack_auto` picks between the two by the
     measured waste ratio ``(W * C_pad) / (T_blk * c_blk)``.

   Packed-format invariants (padding slots, BOTH layouts — in the ragged
   stream they apply to each window's final partial block and to the one
   all-padding block an empty window keeps so its accumulator still
   initializes/dumps):
     * ``m_blk``  is ``0``      — padding contributes nothing to any sum;
     * ``col_blk`` holds the slot's own lane index — the vector gather
       stays in-bounds and preserves the straight-lane structure the
       fused kernel's gather relies on (``col % l ∈ {lane, l-1-lane}``);
     * ``row_blk`` is ``0``     — safe because the value is 0.
   Crossbar leaf.  Both layouts carry ``src_blk``, shaped and
   typed like ``row_blk``: the inverse of each cycle row's routing,
   ``src_blk[r, a]`` = the lane ``j`` with ``row_blk[r, j] == a`` and
   ``m_blk[r, j] != 0``, else ``-1`` (:func:`_source_lanes`).  Padding
   rows are all ``-1``; a lane whose stored value is 0 (a padding slot, a
   zero value, an int8 value that quantized to 0) is never a source,
   which is exact because it contributes 0.  Collision-freedom of the
   edge coloring leaves at most one nonzero lane per adder and cycle, so
   the kernels route each cycle with in-register lane gathers by this
   table (``kernels.gust_spmv.route_rows``); ``row_blk`` stays for the
   XLA backend, the sharded segment sum, the oracles and the verifier.
   Ragged-stream metadata contract: blocks of one window are contiguous
   (``block_window`` is sorted), window ``w`` owns stream blocks
   ``block_starts[w]:block_starts[w+1]``, every window owns at least one
   block, and stream rows of block ``t`` are ``t*c_blk:(t+1)*c_blk``.
   Any transformation of either layout (``repad_to``,
   ``repad_to_blocks``, layer stacking, window padding for the
   distributed split) must preserve all of the above.

   Gather-locality leaves (PR 5).  Both layouts additionally carry a
   *segment-local* gather table so the kernels can stream only the x
   tiles a block actually references instead of holding all of x
   resident in VMEM:
     * ``seg_blk`` ``(T_blk, S_blk)`` int32 — the distinct column
       segments (``col // l``) referenced by each ``(c_blk, l)`` stream
       block, sorted ascending, padded to the per-schedule fixed
       ``S_blk`` with segment 0 (always in-bounds);
     * ``col_loc`` — ``col_blk`` remapped to block-local segment ids:
       ``col_loc = local_seg * l + (col_blk % l)`` where ``local_seg``
       is the column's position in its block's ``seg_blk`` row.  The
       lane structure is preserved (``col_loc % l == col_blk % l``) and
       padding slots still hold the slot's own lane (segment 0 sorts
       first, so lane-valued padding columns map to local slot 0).
   ``S_blk`` is a static aux field; the tables are a pure function of
   ``(col_blk, l, c_blk)`` (:func:`_local_gather_tables`), which is how
   ``repad_to`` / ``repad_to_blocks`` stay consistent: they recompute
   the tables on the grown stream (bit-identical on the unchanged
   blocks) and never shrink ``S_blk``.  :func:`resolve_gather` is the
   one ``gather="auto"`` decision point: the segment-local path wins
   when ``S_blk / seg_count`` is below the locality ratio.

3. **Execute.**  ``kernels.ops.execute_spmm`` (Pallas or XLA, padded
   *and* ragged, resident or segment-local gather — the latter streams
   only each block's ``S_blk`` referenced x tiles via the pack-time
   ``seg_blk`` table instead of holding all of x in VMEM) streams the
   packed blocks; every entry point reaches it
   through :meth:`GustPlan.spmm`/:meth:`GustPlan.spmv` — including
   sharded execution (:meth:`GustPlan.shard`: k parallel length-l GUSTs
   over window ranges balanced by block count) and
   ``serving.gust_serve.decode_step_gust``.  Serving stacks per-layer
   plans with :meth:`GustPlan.stack` (equalizing stream length via
   :meth:`PackedSchedule.repad_to` / :meth:`RaggedSchedule.
   repad_to_blocks`); the leaves/meta codec (:func:`packed_leaves` /
   :func:`packed_meta` / :func:`packed_from_leaves`, and the ragged
   twins) backs :meth:`GustPlan.to_spec`/``from_spec`` — the one wire
   format shared by ``gustify`` and the multi-pod dry-run specs.

4. **Cache.**  :class:`ScheduleCache` (module-level ``default_cache``
   that :func:`repro.core.plan.plan` schedules and packs through) keys
   results on matrix *content*, so serving/benchmark paths that
   re-derive the same pruned matrix pay for scheduling exactly once.
   The cache is a bounded LRU (``maxsize``, evictions counted); for
   amortization *across processes* the same content keys feed
   :class:`repro.core.plan_store.PlanStore` — ``plan(..., store=...)``
   reads a previously packed artifact straight off disk (write-behind
   happens when a fresh plan first materializes its pack), so a server
   fleet warm-starts without rescheduling or repacking at all.  For
   drifting sparsity within a process, :func:`splice_ragged_blocks`
   re-packs only the windows an incremental reschedule dirtied and
   copies every clean window's blocks bitwise from the old stream.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
from collections import OrderedDict
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .formats import COOMatrix, GustSchedule

__all__ = [
    "PackedSchedule",
    "RaggedSchedule",
    "pack_blocks",
    "pack_schedule",
    "pack_ragged",
    "pack_auto",
    "DEFAULT_WASTE_THRESHOLD",
    "DEFAULT_LOCALITY_RATIO",
    "DEFAULT_LOCAL_MIN_SEGS",
    "resolve_layout",
    "resolve_gather",
    "ragged_waste_ratio",
    "packed_spec",
    "ragged_spec",
    "window_ids",
    "packed_leaves",
    "packed_meta",
    "packed_from_leaves",
    "ragged_leaves",
    "ragged_meta",
    "ragged_from_leaves",
    "stacked_leaf_specs",
    "splice_ragged_blocks",
    "ScheduleCache",
    "DEFAULT_SCHEDULE_CACHE_SIZE",
    "schedule_packed",
    "default_cache",
    "clear_cache",
    "DEFAULT_TUNE_IMPROVEMENT",
    "resolve_tuning",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedSchedule:
    """Fixed-shape GUST scheduled format (pytree).

    Arrays (leaves):
      m_blk:   (W * C_pad, l) values; 0.0 in padding slots.
      col_blk: (W * C_pad, l) int32 original column index; padding slots
               hold the slot's own lane (in-bounds, straight layout).
      row_blk: (W * C_pad, l) int32 adder index; 0 in padding slots.
      row_perm:(W * l,) int32 — original row of each scheduled row position
               (identity-extended past m).
      src_blk: (W * C_pad, l) index dtype — source lane of each adder per
               cycle row (the inverse of ``row_blk`` over the nonzero
               slots), -1 where no nonzero product reaches the adder.
      seg_blk: (T_blk, S_blk) int32 — per-(c_blk, l)-block distinct column
               segments (sorted; padded with segment 0).  T_blk =
               W * C_pad / c_blk.
      col_loc: (W * C_pad, l) col_blk remapped to block-local segment ids
               (``local_seg * l + col % l``; index dtype preserved).
      scale_blk: (T_blk,) f32 per-block dequantization scales, or ``None``
               on unquantized packs.  Present exactly when ``m_blk`` is
               int8: the stored value of slot ``(r, j)`` is
               ``m[r, j] = q[r, j] * scale_blk[r // c_blk]`` with dequant
               fused into the kernel accumulate in f32.  Padding slots
               quantize to exactly 0 (scale of an all-zero block is 1.0),
               so the zero-contribution invariant survives quantization.

    Static (aux):
      l, num_windows, c_pad, shape=(m, n), fusable (lane structure verified
      for the fused in-kernel gather), c_blk (the block height the gather
      tables were built for), s_blk, identity_perm (row_perm is the
      identity — the executor skips the output scatter).
    """

    m_blk: jnp.ndarray
    col_blk: jnp.ndarray
    row_blk: jnp.ndarray
    row_perm: jnp.ndarray
    src_blk: jnp.ndarray
    seg_blk: jnp.ndarray
    col_loc: jnp.ndarray
    l: int
    num_windows: int
    c_pad: int
    shape: Tuple[int, int]
    fusable: bool
    c_blk: int
    s_blk: int
    identity_perm: bool
    scale_blk: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        leaves = (self.m_blk, self.col_blk, self.row_blk, self.row_perm,
                  self.src_blk, self.seg_blk, self.col_loc, self.scale_blk)
        aux = (self.l, self.num_windows, self.c_pad, self.shape, self.fusable,
               self.c_blk, self.s_blk, self.identity_perm)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        *arr, scale = leaves
        return cls(*arr, *aux, scale_blk=scale)

    @property
    def seg_count(self) -> int:
        return -(-self.shape[1] // self.l)

    @property
    def quantized(self) -> bool:
        return self.scale_blk is not None

    @property
    def stream_bytes(self) -> int:
        """HBM bytes of the scheduled stream (value + col + row leaves at
        their actual dtypes — an int8 value plane is a quarter of the f32
        one) plus the per-block scales when quantized."""
        extra = (self.scale_blk,) if self.scale_blk is not None else ()
        return sum(
            int(a.size) * jnp.dtype(a.dtype).itemsize
            for a in (self.m_blk, self.col_blk, self.row_blk) + extra
        )

    def repad_to(self, c_pad: int) -> "PackedSchedule":
        """Grow the per-window color padding to ``c_pad`` slots.

        Preserves every leaf dtype (a compact int16 stream stays int16)
        and the packed-format invariants: new value slots are 0, new
        column slots gather the slot's own lane, new row slots are 0 and
        new source lanes -1.
        Used to equalize C_pad across stacked layers in serving.
        """
        if c_pad == self.c_pad:
            return self
        if c_pad < self.c_pad:
            raise ValueError(
                f"cannot shrink c_pad {self.c_pad} -> {c_pad} (real colors "
                "may live in the dropped slots)"
            )
        W, l, extra = self.num_windows, self.l, c_pad - self.c_pad

        def grow(a, pad_row):
            a3 = jnp.asarray(a).reshape(W, self.c_pad, l)
            pad = jnp.broadcast_to(
                jnp.asarray(pad_row, a3.dtype)[None, None, :], (W, extra, l)
            )
            return jnp.concatenate([a3, pad], axis=1).reshape(W * c_pad, l)

        col_grown = grow(self.col_blk, np.arange(l, dtype=np.int32))
        # gather tables are a pure function of (col, l, c_blk): recomputing
        # on the grown stream is bit-identical on the unchanged blocks, and
        # S_blk never shrinks (all-lane padding rows reference only seg 0)
        seg_blk, col_loc, s_blk = _local_gather_tables(
            np.asarray(col_grown), l, self.c_blk, s_min=self.s_blk
        )
        scale = self.scale_blk
        if scale is not None:
            # scales are per-(c_blk, l) block: the grown padding must land
            # on whole new blocks for the old blocks' scales to stay put
            if c_pad % self.c_blk or self.c_pad % self.c_blk:
                raise ValueError(
                    f"quantized repad_to requires c_pad multiples of c_blk="
                    f"{self.c_blk}, got {self.c_pad} -> {c_pad}"
                )
            old_bpw = self.c_pad // self.c_blk
            new_bpw = c_pad // self.c_blk
            s2 = jnp.asarray(scale).reshape(W, old_bpw)
            pad = jnp.ones((W, new_bpw - old_bpw), s2.dtype)  # all-zero blocks
            scale = jnp.concatenate([s2, pad], axis=1).reshape(-1)
        return PackedSchedule(
            m_blk=grow(self.m_blk, np.zeros(l, np.float32)),
            col_blk=col_grown,
            row_blk=grow(self.row_blk, np.zeros(l, np.int32)),
            row_perm=self.row_perm,
            src_blk=grow(self.src_blk, np.full(l, -1, np.int32)),
            seg_blk=jnp.asarray(seg_blk),
            col_loc=jnp.asarray(col_loc, self.col_loc.dtype),
            l=l,
            num_windows=W,
            c_pad=c_pad,
            shape=self.shape,
            fusable=self.fusable,
            c_blk=self.c_blk,
            s_blk=s_blk,
            identity_perm=self.identity_perm,
            scale_blk=scale,
        )

    def repad_seg_to(self, s_blk: int) -> "PackedSchedule":
        """Widen the per-block segment table to ``s_blk`` slots (padding
        with segment 0, which no ``col_loc`` entry references).  Used to
        equalize ``S_blk`` across stacked serving layers."""
        return _repad_seg(self, s_blk)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RaggedSchedule:
    """Ragged color-block stream of the GUST scheduled format (pytree).

    Unlike :class:`PackedSchedule` (every window padded to the global
    ``C_pad``), the stream holds only each window's actual
    ``max(ceil(C_w / c_blk), 1)`` cycle blocks, so skewed matrices never
    execute the dead padding cycles of their light windows.

    Arrays (leaves):
      m_blk:        (T_blk * c_blk, l) values; 0.0 in padding slots (the
                    final partial block of each window + the single
                    all-padding block of an empty window).
      col_blk:      (T_blk * c_blk, l) int original column index; padding
                    slots hold the slot's own lane.
      row_blk:      (T_blk * c_blk, l) int adder index; 0 in padding slots.
      row_perm:     (W * l,) int32 — original row of each scheduled row
                    position (identity-extended past m).
      src_blk:      (T_blk * c_blk, l) index dtype — source lane of each
                    adder per cycle row, -1 where none (as
                    :class:`PackedSchedule`).
      seg_blk:      (T_blk, S_blk) int32 — per-block distinct column
                    segments (sorted; padded with segment 0).
      col_loc:      (T_blk * c_blk, l) col_blk remapped to block-local
                    segment ids (index dtype preserved).
      block_window: (T_blk,) int32 — window id of each stream block
                    (sorted; blocks of one window are contiguous).
      block_starts: (W + 1,) int32 — per-window block prefix: window ``w``
                    owns stream blocks ``block_starts[w]:block_starts[w+1]``
                    (always at least one).
      scale_blk:    (T_blk,) f32 per-block dequantization scales, or
                    ``None`` on unquantized packs (present exactly when
                    ``m_blk`` is int8; padding quantizes to 0 — same
                    contract as :class:`PackedSchedule`).

    Static (aux): l, num_windows, c_blk, num_blocks (= T_blk), shape,
    fusable, s_blk, identity_perm.
    """

    m_blk: jnp.ndarray
    col_blk: jnp.ndarray
    row_blk: jnp.ndarray
    row_perm: jnp.ndarray
    src_blk: jnp.ndarray
    seg_blk: jnp.ndarray
    col_loc: jnp.ndarray
    block_window: jnp.ndarray
    block_starts: jnp.ndarray
    l: int
    num_windows: int
    c_blk: int
    num_blocks: int
    shape: Tuple[int, int]
    fusable: bool
    s_blk: int
    identity_perm: bool
    scale_blk: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        leaves = (self.m_blk, self.col_blk, self.row_blk, self.row_perm,
                  self.src_blk, self.seg_blk, self.col_loc,
                  self.block_window, self.block_starts, self.scale_blk)
        aux = (self.l, self.num_windows, self.c_blk, self.num_blocks,
               self.shape, self.fusable, self.s_blk, self.identity_perm)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        *arr, scale = leaves
        return cls(*arr, *aux, scale_blk=scale)

    @property
    def seg_count(self) -> int:
        return -(-self.shape[1] // self.l)

    @property
    def quantized(self) -> bool:
        return self.scale_blk is not None

    @property
    def streamed_slots(self) -> int:
        """(cycle, lane) slots the execution path actually streams."""
        return self.num_blocks * self.c_blk * self.l

    @property
    def stream_bytes(self) -> int:
        """HBM bytes of the scheduled stream (value + col + row leaves at
        their actual dtypes — a compact bf16/int16 stream is ~half the
        f32/i32 one, an int8 value plane a quarter) plus the scalar block
        metadata and the per-block scales when quantized."""
        extra = (self.scale_blk,) if self.scale_blk is not None else ()
        return sum(
            int(a.size) * jnp.dtype(a.dtype).itemsize
            for a in (self.m_blk, self.col_blk, self.row_blk,
                      self.block_window, self.block_starts) + extra
        )

    def repad_to_blocks(self, num_blocks: int) -> "RaggedSchedule":
        """Grow the stream to ``num_blocks`` blocks with all-padding
        trailing blocks (attributed to the last window, whose accumulator
        they extend by zero).  Preserves every leaf dtype and the padding
        invariants; used to equalize stream lengths across stacked
        serving layers."""
        if num_blocks == self.num_blocks:
            return self
        if num_blocks < self.num_blocks:
            raise ValueError(
                f"cannot shrink num_blocks {self.num_blocks} -> {num_blocks}"
                " (real cycles may live in the dropped blocks)"
            )
        l, extra = self.l, num_blocks - self.num_blocks
        rows = extra * self.c_blk
        lane = jnp.arange(l, dtype=self.col_blk.dtype)

        def grow(a, pad_row):
            pad = jnp.broadcast_to(
                jnp.asarray(pad_row, jnp.asarray(a).dtype)[None, :], (rows, l)
            )
            return jnp.concatenate([jnp.asarray(a), pad], axis=0)

        last_w = max(self.num_windows - 1, 0)
        bw = jnp.concatenate([
            jnp.asarray(self.block_window),
            jnp.full((extra,), last_w, self.block_window.dtype),
        ])
        bs = jnp.asarray(self.block_starts).at[-1].set(num_blocks)
        col_grown = grow(self.col_blk, lane)
        # recompute the gather tables on the grown stream (pure function of
        # the column content — bit-identical on the unchanged blocks; the
        # appended all-lane blocks reference only segment 0)
        seg_blk, col_loc, s_blk = _local_gather_tables(
            np.asarray(col_grown), l, self.c_blk, s_min=self.s_blk
        )
        scale = self.scale_blk
        if scale is not None:
            # appended blocks are all padding (value 0): scale 1.0
            scale = jnp.concatenate(
                [jnp.asarray(scale), jnp.ones((extra,), jnp.asarray(scale).dtype)]
            )
        return RaggedSchedule(
            m_blk=grow(self.m_blk, np.zeros(l, np.float32)),
            col_blk=col_grown,
            row_blk=grow(self.row_blk, np.zeros(l, np.int32)),
            row_perm=self.row_perm,
            src_blk=grow(self.src_blk, np.full(l, -1, np.int32)),
            seg_blk=jnp.asarray(seg_blk),
            col_loc=jnp.asarray(col_loc, self.col_loc.dtype),
            block_window=bw,
            block_starts=bs,
            l=l,
            num_windows=self.num_windows,
            c_blk=self.c_blk,
            num_blocks=num_blocks,
            shape=self.shape,
            fusable=self.fusable,
            s_blk=s_blk,
            identity_perm=self.identity_perm,
            scale_blk=scale,
        )

    def repad_seg_to(self, s_blk: int) -> "RaggedSchedule":
        """Widen the per-block segment table to ``s_blk`` slots (padding
        with segment 0).  The ragged twin of
        :meth:`PackedSchedule.repad_seg_to`."""
        return _repad_seg(self, s_blk)


def _repad_seg(packed, s_blk: int):
    """Shared ``repad_seg_to``: pad ``seg_blk`` columns with segment 0 —
    no ``col_loc`` entry maps to the new slots, so the gathered-but-unused
    tiles contribute nothing (the local kernels mask by local id)."""
    if s_blk == packed.s_blk:
        return packed
    if s_blk < packed.s_blk:
        raise ValueError(
            f"cannot shrink s_blk {packed.s_blk} -> {s_blk} (real segment "
            "ids may live in the dropped table slots)"
        )
    seg = jnp.asarray(packed.seg_blk)
    seg = jnp.concatenate(
        [seg, jnp.zeros((seg.shape[0], s_blk - packed.s_blk), seg.dtype)],
        axis=1,
    )
    return dataclasses.replace(packed, seg_blk=seg, s_blk=s_blk)


def window_ids(sched: GustSchedule) -> np.ndarray:
    """Window id of each global schedule cycle, shape (max(C_total, 1),)."""
    wid = np.zeros(max(sched.total_colors, 1), dtype=np.int32)
    ids = np.repeat(
        np.arange(sched.num_windows, dtype=np.int32), sched.colors_per_window
    )
    wid[: ids.shape[0]] = ids
    return wid


def pack_blocks(
    sched: GustSchedule, c_blk: int = 8
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int, bool]:
    """Vectorized core of the ragged→packed conversion (host numpy).

    Returns ``(m_b, c_b, r_b, c_pad, fusable)`` with the three blocks of
    shape ``(W * c_pad, l)``.  Each real cycle row scatters to global
    destination ``window * C_pad + local_cycle`` in one fancy-indexed
    assignment — O(nnz) instead of a Python loop over windows.
    """
    l, W = sched.l, sched.num_windows
    ws = np.asarray(sched.window_starts)
    cpw = np.diff(ws)
    c_max = int(cpw.max()) if W else 1
    c_pad = max(-(-c_max // c_blk) * c_blk, c_blk)
    c_total = int(ws[-1]) if W else 0

    lane = np.arange(l, dtype=np.int32)
    # One backing allocation for all three blocks (f32 and i32 share the
    # itemsize, so the value plane is a reinterpreting view) — noticeably
    # cheaper than three separate page-faulted buffers at large W.
    buf = np.zeros((3, W * c_pad, l), dtype=np.int32)
    m_b = buf[0].view(np.float32)
    r_b = buf[1]
    c_b = buf[2]
    c_b[:] = lane  # padding slots gather v[lane] (packed-format invariant)
    if c_total:
        wid = np.repeat(np.arange(W, dtype=np.int64), cpw)
        dest = wid * c_pad + (np.arange(c_total, dtype=np.int64) - ws[wid])
        m_b[dest] = sched.m_sch[:c_total]
        r_b[dest] = sched.row_sch[:c_total]
        c_b[dest] = sched.col_sch[:c_total]

    return m_b, c_b, r_b, c_pad, _fusable(sched)


def _fusable(sched: GustSchedule) -> bool:
    """Verify the lane structure the fused gather relies on: every slot's
    column offset is its lane or the reversed lane.  Checking the ragged
    source is equivalent to checking either packed layout (padding slots
    are lane-valued by construction) and touches fewer elements."""
    l = sched.l
    lane = np.arange(l, dtype=np.int32)
    src = sched.col_sch
    off = (src & (l - 1)) if (l & (l - 1)) == 0 else (src % l)
    return bool(np.all((off == lane[None, :]) | (off == (l - 1 - lane)[None, :])))


def _quantize_stream(
    m_b: np.ndarray, c_blk: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Per-block symmetric int8 quantization of a packed value stream.

    For each ``(c_blk, l)`` block: ``scale = absmax / 127`` (1.0 for
    all-zero blocks, so padding blocks stay well-defined) and
    ``q = clip(rint(v / scale), -127, 127)`` int8.  Exact zeros — every
    padding slot — quantize to exactly 0 regardless of the block scale,
    which is what preserves the packed-format zero-contribution
    invariant.  The dequant semantics the kernels and oracles share
    bit-exactly: ``v̂ = float32(q) * scale`` (both sides perform this one
    f32 multiply, so kernel and oracle agree bitwise).

    Returns ``(q (rows, l) int8, scale (rows // c_blk,) f32)``.
    """
    m_b = np.ascontiguousarray(m_b, np.float32)
    rows, l = m_b.shape
    if rows % c_blk:
        raise ValueError(f"stream rows {rows} not a multiple of c_blk {c_blk}")
    blocks = m_b.reshape(rows // c_blk, c_blk * l)
    absmax = np.abs(blocks).max(axis=1)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(
        np.rint(blocks / scale[:, None].astype(np.float32)), -127, 127
    ).astype(np.int8)
    return q.reshape(rows, l), scale


def _is_int8(value_dtype) -> bool:
    return jnp.dtype(value_dtype) == jnp.dtype(jnp.int8)


def _stored_values(m_b: np.ndarray, c_blk: int, value_dtype):
    """The value plane as the artifact stores it (host numpy, so the
    source lanes are read off the stored values): int8 codes plus their
    per-block scales on a quantized stream, else ``m_b`` in
    ``value_dtype``.  Returns ``(values, scale or None, value_dtype)``."""
    if _is_int8(value_dtype):
        q, scale = _quantize_stream(m_b, c_blk)
        return q, jnp.asarray(scale), jnp.int8
    return m_b.astype(jnp.dtype(value_dtype), copy=False), None, value_dtype


def _local_gather_tables(
    col: np.ndarray, l: int, c_blk: int, s_min: int = 1
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Segment-local gather tables of a packed column stream.

    For each ``(c_blk, l)`` block of ``col`` (shape ``(rows, l)``), the
    distinct column segments (``col // l``) it references, sorted
    ascending, padded with segment 0 to the common width
    ``S_blk = max(max distinct per block, s_min)`` — plus the columns
    remapped to block-local segment ids:
    ``col_loc = local_seg * l + col % l``.

    A pure function of ``(col, l, c_blk)``: recomputing on a grown stream
    reproduces the original blocks' tables bitwise, which is what makes
    ``repad_to`` / ``repad_to_blocks`` safe.  Lane-valued padding columns
    live in segment 0, which sorts first, so padding slots always map to
    local slot 0 and ``col_loc`` padding rows equal the lane index.
    Returns ``(seg_blk (T, S_blk) int32, col_loc (rows, l) int32, S_blk)``.
    """
    col = np.asarray(col, np.int64)
    rows = col.shape[0]
    if rows % c_blk:  # virtually pad to a block multiple with lane rows
        lane_rows = np.broadcast_to(
            np.arange(l, dtype=np.int64), (c_blk - rows % c_blk, l)
        )
        col = np.concatenate([col, lane_rows], axis=0)
    t_blk = col.shape[0] // c_blk
    segs = (col // l).reshape(t_blk, c_blk * l)
    order = np.argsort(segs, axis=1, kind="stable")
    srt = np.take_along_axis(segs, order, axis=1)
    first = np.ones_like(srt, dtype=bool)
    if srt.shape[1] > 1:
        first[:, 1:] = srt[:, 1:] != srt[:, :-1]
    loc_sorted = np.cumsum(first, axis=1) - 1  # local id per sorted slot
    loc = np.empty_like(loc_sorted)
    np.put_along_axis(loc, order, loc_sorted, axis=1)
    counts = first.sum(axis=1)
    s_blk = int(max(counts.max() if t_blk else 1, s_min, 1))
    seg_blk = np.zeros((t_blk, s_blk), np.int32)
    r_idx = np.nonzero(first)[0]
    seg_blk[r_idx, loc_sorted[first]] = srt[first]
    col_loc = (
        loc.reshape(col.shape[0], l) * l + (col - (col // l) * l)
    ).astype(np.int32)[:rows]
    return seg_blk, col_loc, s_blk


def _source_lanes(m_blk, row_blk) -> np.ndarray:
    """The crossbar's inverse of a packed stream (host numpy):
    ``src[r, a]`` is the lane ``j`` with ``row_blk[r, j] == a`` and
    ``m_blk[r, j] != 0``, else ``-1``.  ``m_blk`` is the stored value
    leaf (int8 codes on a quantized stream), so a lane whose product is 0
    by its value is never a source.  Edge coloring leaves at most one
    nonzero lane per adder and cycle row, so the scatter below writes
    each entry at most once.  Leading axes (stacked layers) are kept;
    returns int32 of ``row_blk``'s shape."""
    row = np.asarray(row_blk)
    l = row.shape[-1]
    src = np.full(row.size, -1, np.int32)
    nz = np.flatnonzero(np.asarray(m_blk).reshape(-1) != 0)
    lane = (nz % l).astype(np.int32)
    src[nz - lane + row.reshape(-1)[nz]] = lane
    return src.reshape(row.shape)


def _extended_row_perm(sched: GustSchedule) -> np.ndarray:
    """row_perm identity-extended to the full W*l scheduled row positions
    (shared by both fixed-shape layouts)."""
    row_perm = np.arange(sched.num_windows * sched.l, dtype=np.int32)
    row_perm[: sched.row_perm.shape[0]] = sched.row_perm
    return row_perm


def pack_schedule(
    sched: GustSchedule, c_blk: int = 8, value_dtype=jnp.float32,
    index_dtype=jnp.int32,
) -> PackedSchedule:
    """Pad the ragged per-window schedule to (W, C_pad, l) blocks.

    C_pad = max window colors, rounded up to a multiple of ``c_blk``.  The
    padding cost is real on hardware too (lanes idle while the heaviest
    window drains) and is already counted by the cycle model through Eq. 1.
    """
    l, W = sched.l, sched.num_windows
    m, n = sched.shape
    m_b, c_b, r_b, c_pad, fusable = pack_blocks(sched, c_blk)
    row_perm = _extended_row_perm(sched)
    seg_blk, col_loc, s_blk = _local_gather_tables(c_b, l, c_blk)
    m_b, scale, value_dtype = _stored_values(m_b, c_blk, value_dtype)

    return PackedSchedule(
        m_blk=jnp.asarray(m_b, value_dtype),
        col_blk=jnp.asarray(c_b, index_dtype),
        row_blk=jnp.asarray(r_b, index_dtype),
        row_perm=jnp.asarray(row_perm),
        src_blk=jnp.asarray(_source_lanes(m_b, r_b), index_dtype),
        seg_blk=jnp.asarray(seg_blk),
        col_loc=jnp.asarray(col_loc, index_dtype),
        l=l,
        num_windows=W,
        c_pad=c_pad,
        shape=(m, n),
        fusable=fusable,
        c_blk=c_blk,
        s_blk=s_blk,
        identity_perm=bool(
            np.array_equal(row_perm, np.arange(W * l, dtype=np.int32))
        ),
        scale_blk=scale,
    )


def _ragged_block_layout(
    sched: GustSchedule, c_blk: int
) -> Tuple[np.ndarray, np.ndarray, int]:
    """(blocks_per_window, block_starts, num_blocks) of the ragged stream.

    Every window keeps ``ceil(C_w / c_blk)`` blocks, floored at one so
    empty windows still own a block (their accumulator tile must
    initialize and dump once — the hardware's minimum one dump per
    window)."""
    cpw = np.diff(np.asarray(sched.window_starts))
    bpw = np.maximum(-(-cpw // c_blk), 1).astype(np.int64)
    block_starts = np.zeros(sched.num_windows + 1, dtype=np.int64)
    np.cumsum(bpw, out=block_starts[1:])
    return bpw, block_starts, int(block_starts[-1])


def ragged_waste_ratio(sched: GustSchedule, c_blk: int = 8) -> float:
    """Padding waste of the padded layout relative to the ragged stream:
    ``(W * C_pad) / (T_blk * c_blk)``.  1.0 means every window already has
    the max color count (padding streams nothing extra); >= ~2 means the
    padded path spends most of its stream on dead cycles."""
    l, W = sched.l, sched.num_windows
    cpw = np.diff(np.asarray(sched.window_starts))
    c_max = int(cpw.max()) if W else 1
    c_pad = max(-(-c_max // c_blk) * c_blk, c_blk)
    _, _, t_blk = _ragged_block_layout(sched, c_blk)
    return (W * c_pad) / float(max(t_blk * c_blk, 1))


def pack_ragged(
    sched: GustSchedule, c_blk: int = 8, value_dtype=jnp.float32,
    index_dtype=jnp.int32,
) -> RaggedSchedule:
    """Flatten the ragged per-window schedule into a (T_blk * c_blk, l)
    block stream holding only real cycle blocks (plus each window's final
    partial-block padding, which keeps the packed-format invariants).

    One fancy-indexed scatter by ``window_starts``-derived destinations —
    O(nnz) host numpy, same as :func:`pack_blocks` — plus O(W) scalar
    metadata (``block_window``, ``block_starts``)."""
    l, W = sched.l, sched.num_windows
    m, n = sched.shape
    ws = np.asarray(sched.window_starts)
    cpw = np.diff(ws)
    c_total = int(ws[-1]) if W else 0
    bpw, block_starts, t_blk = _ragged_block_layout(sched, c_blk)

    lane = np.arange(l, dtype=np.int32)
    # Same one-backing-allocation trick as pack_blocks (f32/i32 share the
    # itemsize, so the value plane is a reinterpreting view).
    buf = np.zeros((3, t_blk * c_blk, l), dtype=np.int32)
    m_b = buf[0].view(np.float32)
    r_b = buf[1]
    c_b = buf[2]
    c_b[:] = lane  # padding slots gather v[lane] (packed-format invariant)
    if c_total:
        wid = np.repeat(np.arange(W, dtype=np.int64), cpw)
        dest = block_starts[wid] * c_blk + (
            np.arange(c_total, dtype=np.int64) - ws[wid]
        )
        m_b[dest] = sched.m_sch[:c_total]
        r_b[dest] = sched.row_sch[:c_total]
        c_b[dest] = sched.col_sch[:c_total]

    block_window = np.repeat(np.arange(W, dtype=np.int32), bpw)
    row_perm = _extended_row_perm(sched)
    seg_blk, col_loc, s_blk = _local_gather_tables(c_b, l, c_blk)
    m_b, scale, value_dtype = _stored_values(m_b, c_blk, value_dtype)

    return RaggedSchedule(
        m_blk=jnp.asarray(m_b, value_dtype),
        col_blk=jnp.asarray(c_b, index_dtype),
        row_blk=jnp.asarray(r_b, index_dtype),
        row_perm=jnp.asarray(row_perm),
        src_blk=jnp.asarray(_source_lanes(m_b, r_b), index_dtype),
        seg_blk=jnp.asarray(seg_blk),
        col_loc=jnp.asarray(col_loc, index_dtype),
        block_window=jnp.asarray(block_window),
        block_starts=jnp.asarray(block_starts, jnp.int32),
        l=l,
        num_windows=W,
        c_blk=c_blk,
        num_blocks=t_blk,
        shape=(m, n),
        fusable=_fusable(sched),
        s_blk=s_blk,
        identity_perm=bool(
            np.array_equal(row_perm, np.arange(W * l, dtype=np.int32))
        ),
        scale_blk=scale,
    )


def splice_ragged_blocks(
    old: RaggedSchedule,
    sched: GustSchedule,
    dirty: Sequence[int],
    *,
    value_dtype=jnp.float32,
    index_dtype=jnp.int32,
) -> RaggedSchedule:
    """Incremental ragged repack: windows listed in ``dirty`` are packed
    fresh (via a compact dirty-only sub-schedule), every other window's
    stream blocks — and per-block int8 scales — are copied bitwise from
    ``old``.  The result is **bit-identical** to
    ``pack_ragged(sched, old.c_blk, ...)`` because stream blocks are
    window-local, quantization scales are block-local, and the gather
    tables are a pure function of the spliced column stream
    (:func:`_local_gather_tables` recomputed globally).

    ``old`` must be an un-repadded pack of a schedule that agrees with
    ``sched`` on every clean window (the :func:`~repro.core.scheduler.
    incremental_schedule` contract) and on geometry/dtypes — violations
    raise rather than silently corrupting the stream."""
    l, W, cb = sched.l, sched.num_windows, old.c_blk
    if old.l != l or old.num_windows != W or tuple(old.shape) != tuple(sched.shape):
        raise ValueError("splice: schedule/artifact geometry mismatch")
    quant = _is_int8(value_dtype)
    if quant != old.quantized:
        raise ValueError("splice: quantization mismatch with the old artifact")
    if jnp.dtype(index_dtype) != jnp.dtype(old.col_blk.dtype):
        raise ValueError("splice: index dtype mismatch with the old artifact")
    if not quant and jnp.dtype(value_dtype) != jnp.dtype(old.m_blk.dtype):
        raise ValueError("splice: value dtype mismatch with the old artifact")

    from .scheduler import _ranges

    dirty = np.asarray(dirty, dtype=np.int64)
    dirty_mask = np.zeros(W, dtype=bool)
    dirty_mask[dirty] = True
    clean = np.nonzero(~dirty_mask)[0]

    bpw_new, bs_new, t_new = _ragged_block_layout(sched, cb)
    bs_old = np.asarray(old.block_starts, np.int64)
    bpw_old = np.diff(bs_old)
    if clean.size and not np.array_equal(bpw_old[clean], bpw_new[clean]):
        raise ValueError("splice: clean windows changed block counts")

    m_old = np.asarray(old.m_blk)
    c_old = np.asarray(old.col_blk)
    r_old = np.asarray(old.row_blk)
    s_old = np.asarray(old.src_blk)
    m_new = np.zeros((t_new * cb, l), dtype=m_old.dtype)
    c_new = np.empty((t_new * cb, l), dtype=c_old.dtype)
    c_new[:] = np.arange(l, dtype=c_old.dtype)  # padding invariant: col==lane
    r_new = np.zeros((t_new * cb, l), dtype=r_old.dtype)
    s_new = np.full((t_new * cb, l), -1, dtype=s_old.dtype)
    scale_new = np.ones((t_new,), np.float32) if quant else None

    if clean.size:
        src = _ranges(bs_old[clean] * cb, bpw_old[clean] * cb)
        dst = _ranges(bs_new[clean] * cb, bpw_new[clean] * cb)
        m_new[dst] = m_old[src]
        c_new[dst] = c_old[src]
        r_new[dst] = r_old[src]
        s_new[dst] = s_old[src]
        if quant:
            sb = _ranges(bs_old[clean], bpw_old[clean])
            db = _ranges(bs_new[clean], bpw_new[clean])
            scale_new[db] = np.asarray(old.scale_blk, np.float32)[sb]

    if dirty.size:
        # Pack only the dirty windows: lift their schedule rows into a
        # compact sub-schedule (sub window i == dirty[i]) and pack_ragged
        # it — per-window block content depends only on that window's
        # rows, so the sub-pack's blocks equal the fresh global pack's.
        ws = np.asarray(sched.window_starts)
        cpw = np.diff(ws)
        sub_cpw = cpw[dirty]
        sub_ws = np.zeros(dirty.size + 1, dtype=np.int64)
        np.cumsum(sub_cpw, out=sub_ws[1:])
        rows_src = _ranges(ws[dirty], sub_cpw)
        sub_c = int(sub_ws[-1])
        rows = max(sub_c, 1)
        sub_m = np.zeros((rows, l), dtype=np.asarray(sched.m_sch).dtype)
        sub_r = np.zeros((rows, l), dtype=np.int32)
        sub_col = np.tile(np.arange(l, dtype=np.int32), (rows, 1))
        sub_valid = np.zeros((rows, l), dtype=bool)
        if sub_c:
            sub_m[:sub_c] = np.asarray(sched.m_sch)[rows_src]
            sub_r[:sub_c] = np.asarray(sched.row_sch)[rows_src]
            sub_col[:sub_c] = np.asarray(sched.col_sch)[rows_src]
            sub_valid[:sub_c] = np.asarray(sched.valid)[rows_src]
        sub_sched = GustSchedule(
            l=l,
            shape=(int(dirty.size) * l, sched.shape[1]),
            nnz=int(sub_valid.sum()),
            m_sch=sub_m,
            row_sch=sub_r,
            col_sch=sub_col,
            window_starts=sub_ws,
            row_perm=np.arange(int(dirty.size) * l, dtype=np.int64),
            valid=sub_valid,
        )
        sub = pack_ragged(
            sub_sched, cb, value_dtype=value_dtype, index_dtype=index_dtype
        )
        # sub windows appear in dirty order, so the sub stream maps onto
        # the dirty destinations row-for-row
        dst = _ranges(bs_new[dirty] * cb, bpw_new[dirty] * cb)
        m_new[dst] = np.asarray(sub.m_blk)
        c_new[dst] = np.asarray(sub.col_blk)
        r_new[dst] = np.asarray(sub.row_blk)
        s_new[dst] = np.asarray(sub.src_blk)
        if quant:
            db = _ranges(bs_new[dirty], bpw_new[dirty])
            scale_new[db] = np.asarray(sub.scale_blk, np.float32)

    seg_blk, col_loc, s_blk = _local_gather_tables(c_new, l, cb)
    row_perm = _extended_row_perm(sched)
    return RaggedSchedule(
        m_blk=jnp.asarray(m_new, jnp.int8 if quant else value_dtype),
        col_blk=jnp.asarray(c_new, index_dtype),
        row_blk=jnp.asarray(r_new, index_dtype),
        row_perm=jnp.asarray(row_perm),
        src_blk=jnp.asarray(s_new, index_dtype),
        seg_blk=jnp.asarray(seg_blk),
        col_loc=jnp.asarray(col_loc, index_dtype),
        block_window=jnp.asarray(np.repeat(np.arange(W, dtype=np.int32), bpw_new)),
        block_starts=jnp.asarray(bs_new, jnp.int32),
        l=l,
        num_windows=W,
        c_blk=cb,
        num_blocks=t_new,
        shape=sched.shape,
        fusable=_fusable(sched),
        s_blk=s_blk,
        identity_perm=bool(
            np.array_equal(row_perm, np.arange(W * l, dtype=np.int32))
        ),
        scale_blk=jnp.asarray(scale_new) if quant else None,
    )


#: Padded-stream waste (``W * C_pad`` over ``T_blk * c_blk``) above which
#: the ragged layout is chosen — consumed only through
#: :func:`resolve_layout`, the one waste-threshold decision point.
DEFAULT_WASTE_THRESHOLD = 2.0


def resolve_layout(
    sched: GustSchedule, c_blk: int = 8, waste_threshold: float = None
) -> str:
    """The one layout='auto' decision point: ``"ragged"`` when the padded
    layout would stream ``>= waste_threshold`` times more (cycle, lane)
    slots than the ragged stream (skewed matrices), else ``"padded"``
    (near-uniform windows, where the simpler 2-D-grid padded kernel
    wins).  ``waste_threshold=None`` means :data:`DEFAULT_WASTE_THRESHOLD`.
    Every auto caller — :func:`pack_auto`, :meth:`ScheduleCache.auto_for`,
    ``GustPlan.layout`` — delegates here."""
    if waste_threshold is None:
        waste_threshold = DEFAULT_WASTE_THRESHOLD
    return (
        "ragged"
        if ragged_waste_ratio(sched, c_blk) >= waste_threshold
        else "padded"
    )


#: ``S_blk / seg_count`` ratio below which ``gather="auto"`` picks the
#: segment-local path — consumed only through :func:`resolve_gather`, the
#: one gather-mode decision point (the locality twin of
#: :data:`DEFAULT_WASTE_THRESHOLD`).
DEFAULT_LOCALITY_RATIO = 0.5

#: Minimum segment count before ``gather="auto"`` considers the local
#: path at all: below this width the resident contraction is small enough
#: that the local mode's extra per-block grid steps (S_blk tile loads,
#: scratch init/flush) dominate the FLOP saving — measured crossover in
#: ``BENCH_gather.json`` (0.64x at 32 segments, >=1.6x from 128 up).
DEFAULT_LOCAL_MIN_SEGS = 128


def resolve_gather(
    s_blk: int, seg_count: int, locality_ratio: float = None,
    min_segs: int = None,
) -> str:
    """The one ``gather="auto"`` decision point: ``"local"`` when the
    matrix is wide enough for tile streaming to pay for its grid-step
    overhead (``seg_count >= min_segs``) AND the per-block segment
    working set is small relative to the width (``S_blk <=
    locality_ratio * seg_count`` — the regime where streaming only the
    referenced x tiles beats holding all of x resident in VMEM and
    contracting over every segment); else ``"resident"``.  ``None``
    thresholds mean :data:`DEFAULT_LOCALITY_RATIO` /
    :data:`DEFAULT_LOCAL_MIN_SEGS`.  Every auto caller —
    ``kernels.ops.execute_spmm``, ``GustPlan`` — delegates here."""
    if locality_ratio is None:
        locality_ratio = DEFAULT_LOCALITY_RATIO
    if min_segs is None:
        min_segs = DEFAULT_LOCAL_MIN_SEGS
    if seg_count < max(min_segs, 2):
        return "resident"
    return "local" if s_blk <= locality_ratio * seg_count else "resident"


#: A measured tune winner must beat the static-default baseline by this
#: wall-clock factor to displace it — consumed only through
#: :func:`resolve_tuning`, the one measured-tuning decision point (the
#: measured twin of :data:`DEFAULT_WASTE_THRESHOLD` /
#: :data:`DEFAULT_LOCALITY_RATIO`).  The margin absorbs timer noise so
#: ``GustPlan.tune`` is never slower than the static defaults.
DEFAULT_TUNE_IMPROVEMENT = 1.05


def resolve_tuning(
    measurements: Dict, baseline, min_improvement: float = None,
):
    """The one measured-tuning decision point: return the key of the
    fastest candidate in ``measurements`` (a ``{candidate_key: seconds}``
    dict), unless it fails to beat ``baseline``'s own measurement by
    ``min_improvement`` — then the baseline key is returned unchanged.
    Guarantees a tuned plan is never slower than the static
    :func:`resolve_layout`/:func:`resolve_gather` defaults (to timer
    noise), because the baseline is always itself a measured candidate.
    ``None`` means :data:`DEFAULT_TUNE_IMPROVEMENT`.  Every measured-tune
    caller — ``GustPlan.tune`` — delegates here."""
    if min_improvement is None:
        min_improvement = DEFAULT_TUNE_IMPROVEMENT
    if baseline not in measurements:
        raise ValueError(
            f"baseline {baseline!r} missing from measurements "
            f"({sorted(map(repr, measurements))})"
        )
    if not all(t > 0 for t in measurements.values()):
        raise ValueError("measurements must be positive wall-clock seconds")
    best = min(measurements, key=measurements.get)
    if measurements[baseline] / measurements[best] >= min_improvement:
        return best
    return baseline


def pack_auto(
    sched: GustSchedule, c_blk: int = 8, *, waste_threshold: float = None,
    value_dtype=jnp.float32, index_dtype=jnp.int32,
):
    """Pick the execution layout by measured padding waste
    (:func:`resolve_layout`) and materialize only the chosen one."""
    fn = (
        pack_ragged
        if resolve_layout(sched, c_blk, waste_threshold) == "ragged"
        else pack_schedule
    )
    return fn(sched, c_blk, value_dtype=value_dtype, index_dtype=index_dtype)


def _default_spec_s_blk(n: int, l: int, c_blk: int) -> int:
    """Worst-case table width for shape-only specs: a block of c_blk*l
    slots can reference at most that many distinct segments, capped at the
    matrix's segment count."""
    return max(min(-(-n // l), c_blk * l), 1)


def packed_spec(
    m: int,
    n: int,
    l: int,
    c_pad: int,
    value_dtype=jnp.float32,
    index_dtype=jnp.int32,
    c_blk: int = 8,
    s_blk: int = None,
) -> PackedSchedule:
    """ShapeDtypeStruct stand-in for a PackedSchedule — used by the dry-run
    (no allocation).  ``c_pad`` is typically sized from the Eq. 9 bound:
    ``expected_colors_bound(n, density, l)`` rounded up.  ``s_blk=None``
    sizes the gather table at the worst case (no locality assumed)."""
    W = max(-(-m // l), 1)
    if s_blk is None:
        s_blk = _default_spec_s_blk(n, l, c_blk)
    t_blk = -(-(W * c_pad) // c_blk)
    sds = jax.ShapeDtypeStruct
    return PackedSchedule(
        m_blk=sds((W * c_pad, l), value_dtype),
        col_blk=sds((W * c_pad, l), index_dtype),
        row_blk=sds((W * c_pad, l), index_dtype),
        row_perm=sds((W * l,), jnp.int32),
        src_blk=sds((W * c_pad, l), index_dtype),
        seg_blk=sds((t_blk, s_blk), jnp.int32),
        col_loc=sds((W * c_pad, l), index_dtype),
        l=l,
        num_windows=W,
        c_pad=c_pad,
        shape=(m, n),
        fusable=True,
        c_blk=c_blk,
        s_blk=s_blk,
        identity_perm=False,
        scale_blk=sds((t_blk,), jnp.float32) if _is_int8(value_dtype) else None,
    )


def ragged_spec(
    m: int,
    n: int,
    l: int,
    num_blocks: int,
    c_blk: int = 8,
    value_dtype=jnp.float32,
    index_dtype=jnp.int32,
    s_blk: int = None,
) -> RaggedSchedule:
    """ShapeDtypeStruct stand-in for a RaggedSchedule — the ragged twin of
    :func:`packed_spec` for dry-runs.  ``num_blocks`` is typically sized
    from the Eq. 9 bound: ``W * ceil(expected_colors_bound / c_blk)``."""
    W = max(-(-m // l), 1)
    if s_blk is None:
        s_blk = _default_spec_s_blk(n, l, c_blk)
    sds = jax.ShapeDtypeStruct
    return RaggedSchedule(
        m_blk=sds((num_blocks * c_blk, l), value_dtype),
        col_blk=sds((num_blocks * c_blk, l), index_dtype),
        row_blk=sds((num_blocks * c_blk, l), index_dtype),
        row_perm=sds((W * l,), jnp.int32),
        src_blk=sds((num_blocks * c_blk, l), index_dtype),
        seg_blk=sds((num_blocks, s_blk), jnp.int32),
        col_loc=sds((num_blocks * c_blk, l), index_dtype),
        block_window=sds((num_blocks,), jnp.int32),
        block_starts=sds((W + 1,), jnp.int32),
        l=l,
        num_windows=W,
        c_blk=c_blk,
        num_blocks=num_blocks,
        shape=(m, n),
        fusable=True,
        s_blk=s_blk,
        identity_perm=False,
        scale_blk=(
            sds((num_blocks,), jnp.float32) if _is_int8(value_dtype) else None
        ),
    )


# ---------------------------------------------------------------------------
# Leaves/meta codec — the one wire format for serving stacks and dry-runs.
# ---------------------------------------------------------------------------


def packed_leaves(p: PackedSchedule) -> Dict:
    """Array leaves of a packed schedule as a plain dict (jit-able pytree).

    The ``scale_blk`` key is present exactly when the pack is quantized —
    meta tuples stay unchanged, so old serialized stacks round-trip and
    quantization is inferred from the value leaf's dtype."""
    leaves = {
        "m_blk": p.m_blk,
        "col_blk": p.col_blk,
        "row_blk": p.row_blk,
        "row_perm": p.row_perm,
        "src_blk": p.src_blk,
        "seg_blk": p.seg_blk,
        "col_loc": p.col_loc,
    }
    if p.scale_blk is not None:
        leaves["scale_blk"] = p.scale_blk
    return leaves


def packed_meta(p: PackedSchedule) -> Tuple:
    """Static (non-array) part: ``(l, num_windows, c_pad, shape, fusable,
    c_blk, s_blk, identity_perm)``."""
    return (p.l, p.num_windows, p.c_pad, p.shape, p.fusable, p.c_blk,
            p.s_blk, p.identity_perm)


def _leaf_sources(leaves: Dict):
    """The ``src_blk`` leaf, derived from ``m_blk``/``row_blk`` by
    :func:`_source_lanes` when the leaves were written without it (a
    store entry or serving stack from before the leaf existed); a shape
    spec derives a spec of ``row_blk``'s shape and dtype."""
    if "src_blk" in leaves:
        return leaves["src_blk"]
    row = leaves["row_blk"]
    if isinstance(row, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(row.shape, row.dtype)
    return jnp.asarray(_source_lanes(leaves["m_blk"], row), row.dtype)


def packed_from_leaves(leaves: Dict, meta: Tuple) -> PackedSchedule:
    """Inverse of the codec: rebuild a PackedSchedule from leaves + meta
    (leaves written without ``src_blk`` derive it, :func:`_leaf_sources`)."""
    l, w, c_pad, shape, fusable, c_blk, s_blk, identity_perm = meta
    return PackedSchedule(
        m_blk=leaves["m_blk"],
        col_blk=leaves["col_blk"],
        row_blk=leaves["row_blk"],
        row_perm=leaves["row_perm"],
        src_blk=_leaf_sources(leaves),
        seg_blk=leaves["seg_blk"],
        col_loc=leaves["col_loc"],
        l=l, num_windows=w, c_pad=c_pad, shape=shape, fusable=fusable,
        c_blk=c_blk, s_blk=s_blk, identity_perm=identity_perm,
        scale_blk=leaves.get("scale_blk"),
    )


def ragged_leaves(r: RaggedSchedule) -> Dict:
    """Array leaves of a ragged stream as a plain dict (jit-able pytree).
    ``scale_blk`` present exactly when quantized (see
    :func:`packed_leaves`)."""
    leaves = {
        "m_blk": r.m_blk,
        "col_blk": r.col_blk,
        "row_blk": r.row_blk,
        "row_perm": r.row_perm,
        "src_blk": r.src_blk,
        "seg_blk": r.seg_blk,
        "col_loc": r.col_loc,
        "block_window": r.block_window,
        "block_starts": r.block_starts,
    }
    if r.scale_blk is not None:
        leaves["scale_blk"] = r.scale_blk
    return leaves


def ragged_meta(r: RaggedSchedule) -> Tuple:
    """Static part: ``("ragged", l, num_windows, c_blk, num_blocks, shape,
    fusable, s_blk, identity_perm)``.  The leading tag disambiguates from
    :func:`packed_meta` tuples in serialized serving stacks."""
    return ("ragged", r.l, r.num_windows, r.c_blk, r.num_blocks, r.shape,
            r.fusable, r.s_blk, r.identity_perm)


def ragged_from_leaves(leaves: Dict, meta: Tuple) -> RaggedSchedule:
    """Inverse of the ragged codec (``src_blk`` derived when missing, as
    :func:`packed_from_leaves`)."""
    tag, l, w, c_blk, t_blk, shape, fusable, s_blk, identity_perm = meta
    if tag != "ragged":
        raise ValueError(f"not a ragged meta tuple: {meta!r}")
    return RaggedSchedule(
        m_blk=leaves["m_blk"],
        col_blk=leaves["col_blk"],
        row_blk=leaves["row_blk"],
        row_perm=leaves["row_perm"],
        src_blk=_leaf_sources(leaves),
        seg_blk=leaves["seg_blk"],
        col_loc=leaves["col_loc"],
        block_window=leaves["block_window"],
        block_starts=leaves["block_starts"],
        l=l, num_windows=w, c_blk=c_blk, num_blocks=t_blk, shape=shape,
        fusable=fusable, s_blk=s_blk, identity_perm=identity_perm,
        scale_blk=leaves.get("scale_blk"),
    )


def stacked_leaf_specs(proto, reps: int) -> Dict:
    """ShapeDtypeStruct leaves of ``reps`` layer packs stacked on axis 0.

    Works for packed and ragged prototypes, real-array or spec (only
    .shape/.dtype are read) — this is how ``dryrun_specs`` sizes the
    serving stack without running the scheduler."""
    leaves = (
        ragged_leaves(proto)
        if isinstance(proto, RaggedSchedule)
        else packed_leaves(proto)
    )
    return {
        k: jax.ShapeDtypeStruct((reps, *v.shape), v.dtype)
        for k, v in leaves.items()
    }


# ---------------------------------------------------------------------------
# Content-keyed schedule cache.
# ---------------------------------------------------------------------------


class ScheduleCache:
    """LRU cache of ``schedule(...)`` / ``pack_schedule(...)`` results,
    keyed by matrix *content* (sha1 of shape + COO triples) and the
    scheduling/packing parameters.

    The paper's amortization argument (§5.3) assumes the schedule is
    computed once per matrix; this cache enforces it across independent
    call sites (serving gustify, GustLinear, benchmarks) that re-derive
    the same pruned matrix.

    ``maxsize`` must cover a whole model conversion for the reuse to
    materialize: gustify inserts ``reps * len(mats)`` schedule entries
    plus as many packed entries (2 * 32 * 3 = 192 for a 32-layer stack),
    so the default (:data:`DEFAULT_SCHEDULE_CACHE_SIZE`, overridable via
    ``REPRO_SCHEDULE_CACHE_SIZE``) is sized above that.  The bound is a
    hard LRU: long-lived servers planning an unbounded matrix stream top
    out at ``maxsize`` live entries, with drops counted in ``evictions``
    (surfaced by :meth:`stats` next to hits/misses).  Entries hold device
    arrays — tens of MB each at LLM scale — for the process lifetime;
    call :func:`clear_cache` after a one-shot conversion to release
    them."""

    def __init__(self, maxsize: Optional[int] = None):
        if maxsize is None:
            env = os.environ.get("REPRO_SCHEDULE_CACHE_SIZE", "").strip()
            maxsize = int(env) if env else DEFAULT_SCHEDULE_CACHE_SIZE
        if maxsize < 1:
            raise ValueError(f"ScheduleCache maxsize must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        self._store: "OrderedDict[Tuple, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @staticmethod
    def matrix_key(coo: COOMatrix) -> str:
        h = hashlib.sha1()
        # canonicalize: a shape rebuilt from numpy scalars (e.g. an npz
        # round trip) must hash like the original python-int tuple
        h.update(repr(tuple(int(s) for s in coo.shape)).encode())
        for a in (coo.rows, coo.cols, coo.vals):
            arr = np.ascontiguousarray(a)
            h.update(str(arr.dtype).encode())
            h.update(arr.tobytes())
        return h.hexdigest()

    def _get(self, key: Tuple, build):
        if key in self._store:
            self.hits += 1
            self._store.move_to_end(key)
            return self._store[key]
        self.misses += 1
        val = build()
        self._store[key] = val
        while len(self._store) > self.maxsize:
            self._store.popitem(last=False)
            self.evictions += 1
        return val

    def _schedule_for_key(self, mk: str, coo: COOMatrix, l: int,
                          load_balance: bool, method: str,
                          workers: Optional[int] = None) -> GustSchedule:
        from .scheduler import schedule as _schedule

        # ``workers`` is deliberately NOT part of the key: the schedule is
        # bit-identical for every worker count (chunking invariant).
        key = ("sched", mk, l, load_balance, method)
        return self._get(
            key,
            lambda: _schedule(
                coo, l, load_balance=load_balance, method=method,
                workers=workers,
            ),
        )

    def schedule(
        self, coo: COOMatrix, l: int, *, load_balance: bool = True,
        method: str = "fast", workers: Optional[int] = None,
    ) -> GustSchedule:
        return self._schedule_for_key(
            self.matrix_key(coo), coo, l, load_balance, method, workers
        )

    def packed(
        self, coo: COOMatrix, l: int, *, load_balance: bool = True,
        method: str = "fast", c_blk: int = 8, value_dtype=jnp.float32,
        index_dtype=jnp.int32,
    ) -> Tuple[GustSchedule, PackedSchedule]:
        mk = self.matrix_key(coo)  # O(nnz) hash — computed once per call
        sched = self._schedule_for_key(mk, coo, l, load_balance, method)
        key = (
            "packed", mk, l, load_balance, method, c_blk,
            jnp.dtype(value_dtype).name, jnp.dtype(index_dtype).name,
        )
        packed = self._get(
            key,
            lambda: pack_schedule(
                sched, c_blk=c_blk, value_dtype=value_dtype,
                index_dtype=index_dtype,
            ),
        )
        return sched, packed

    def ragged_packed(
        self, coo: COOMatrix, l: int, *, load_balance: bool = True,
        method: str = "fast", c_blk: int = 8, value_dtype=jnp.float32,
        index_dtype=jnp.int32,
    ) -> Tuple[GustSchedule, "RaggedSchedule"]:
        """Ragged twin of :meth:`packed`: schedule + ragged block stream,
        both served from the matrix-content-keyed store."""
        mk = self.matrix_key(coo)
        sched = self._schedule_for_key(mk, coo, l, load_balance, method)
        key = (
            "ragged", mk, l, load_balance, method, c_blk,
            jnp.dtype(value_dtype).name, jnp.dtype(index_dtype).name,
        )
        ragged = self._get(
            key,
            lambda: pack_ragged(
                sched, c_blk=c_blk, value_dtype=value_dtype,
                index_dtype=index_dtype,
            ),
        )
        return sched, ragged

    @staticmethod
    def schedule_key(sched: GustSchedule) -> str:
        """Content key of an already-built schedule — used by call sites
        that receive a ``GustSchedule`` rather than the source matrix
        (``distributed_spmv``, ``gust_spmm_auto``)."""
        h = hashlib.sha1()
        h.update(repr((sched.l, sched.shape, sched.nnz)).encode())
        for a in (sched.m_sch, sched.row_sch, sched.col_sch,
                  sched.window_starts, sched.row_perm):
            arr = np.ascontiguousarray(a)
            h.update(str(arr.dtype).encode())
            h.update(arr.tobytes())
        return h.hexdigest()

    def pack_for(
        self, sched: GustSchedule, *, c_blk: int = 8,
        value_dtype=jnp.float32, index_dtype=jnp.int32,
    ) -> PackedSchedule:
        """Memoized :func:`pack_schedule` keyed on schedule content —
        repeated executions of the same schedule (every ``distributed_spmv``
        call, serving re-exports) pack exactly once."""
        key = ("pack_for", self.schedule_key(sched), c_blk,
               jnp.dtype(value_dtype).name, jnp.dtype(index_dtype).name)
        return self._get(
            key,
            lambda: pack_schedule(
                sched, c_blk=c_blk, value_dtype=value_dtype,
                index_dtype=index_dtype,
            ),
        )

    def ragged_for(
        self, sched: GustSchedule, *, c_blk: int = 8,
        value_dtype=jnp.float32, index_dtype=jnp.int32,
    ) -> RaggedSchedule:
        """Memoized :func:`pack_ragged` keyed on schedule content."""
        key = ("ragged_for", self.schedule_key(sched), c_blk,
               jnp.dtype(value_dtype).name, jnp.dtype(index_dtype).name)
        return self._get(
            key,
            lambda: pack_ragged(
                sched, c_blk=c_blk, value_dtype=value_dtype,
                index_dtype=index_dtype,
            ),
        )

    def auto_for(
        self, sched: GustSchedule, *, c_blk: int = 8,
        waste_threshold: float = None, value_dtype=jnp.float32,
        index_dtype=jnp.int32,
    ):
        """Cached twin of :func:`pack_auto`: the :func:`resolve_layout`
        decision, delegated to :meth:`ragged_for` / :meth:`pack_for` so
        the chosen layout is memoized on schedule content."""
        route = (
            self.ragged_for
            if resolve_layout(sched, c_blk, waste_threshold) == "ragged"
            else self.pack_for
        )
        return route(
            sched, c_blk=c_blk, value_dtype=value_dtype,
            index_dtype=index_dtype,
        )

    def memo(self, key: Tuple, build):
        """Generic LRU memoization for artifacts *derived from* cached
        entries (e.g. the distributed device-major shard layout, or a
        ``GustPlan.tune`` result).  ``key`` must lead with a tag distinct
        from the built-in routes."""
        return self._get(key, build)

    def stats(self) -> Dict[str, int]:
        """Hit/miss/eviction/entry counters — surfaced on
        ``GustPlan.cost()`` so benchmarks and serving logs can report
        schedule-reuse rates and capacity pressure."""
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "entries": len(self._store),
        }

    def clear(self):
        self._store.clear()
        self.hits = self.misses = self.evictions = 0


#: Default LRU capacity of :class:`ScheduleCache` — generous enough for a
#: whole multi-layer model conversion; override per-process with the
#: ``REPRO_SCHEDULE_CACHE_SIZE`` env var or per-cache with ``maxsize=``.
DEFAULT_SCHEDULE_CACHE_SIZE = 256

default_cache = ScheduleCache()


def clear_cache() -> None:
    """Drop every cached schedule/packed entry of the module-level cache
    (and the ``spmm_scheduled`` shim's identity-keyed plan memo).

    Cached entries hold device arrays (tens of MB per LLM-scale matrix, up
    to ``maxsize`` of them) for the process lifetime; call this after a
    one-shot conversion (e.g. ``gustify`` at weight-load time) if the
    memory matters more than re-schedule speed."""
    default_cache.clear()
    # late import via importlib: spmv imports this module, and the package
    # namespace shadows the submodule with the spmv *function*
    import importlib

    importlib.import_module(__package__ + ".spmv")._SHIM_PLANS.clear()


def schedule_packed(
    coo: COOMatrix, l: int, *, load_balance: bool = True, method: str = "fast",
    c_blk: int = 8, value_dtype=jnp.float32, index_dtype=jnp.int32,
    cache: Optional[ScheduleCache] = default_cache,
) -> Tuple[GustSchedule, PackedSchedule]:
    """schedule + pack in one call, served from ``cache`` (content-keyed;
    pass ``cache=None`` to bypass)."""
    if cache is None:
        from .scheduler import schedule as _schedule

        sched = _schedule(coo, l, load_balance=load_balance, method=method)
        return sched, pack_schedule(
            sched, c_blk=c_blk, value_dtype=value_dtype, index_dtype=index_dtype
        )
    return cache.packed(
        coo, l, load_balance=load_balance, method=method, c_blk=c_blk,
        value_dtype=value_dtype, index_dtype=index_dtype,
    )
