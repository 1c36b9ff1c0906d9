"""Pure-jnp oracles for every Pallas kernel (same packed-block semantics).

These mirror the kernels' contracts exactly — same inputs, same outputs —
with no Pallas, no BlockSpecs, no one-hot tricks: direct gathers and
scatter-adds.  Every kernel test sweeps shapes/dtypes and asserts
``assert_allclose(kernel(...), ref(...))``.

The segment-local twins (``*_local_ref``) replace the direct gather
``x[col]`` with the two-step segment-table gather the local kernels run
— x tiles selected by ``seg_blk``, then a block-local index — and share
every instruction downstream, so local-vs-resident bit-identity is an
oracle-level property too (the gathered values are equal bitwise: the
table maps each local id back to the slot's original global column).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "gust_spmv_ref",
    "gust_spmv_ragged_ref",
    "gust_spmv_local_ref",
    "gust_spmv_ragged_local_ref",
    "gather_fill_ref",
    "gather_fill_local_ref",
    "dequant_ref",
    "gust_spgemm_ref",
]


def dequant_ref(
    m_blocks: jnp.ndarray,  # (T*c_blk, l) int8 quantized values
    scale_blk: jnp.ndarray,  # (T,) f32 per-block scales
    *,
    c_blk: int,
) -> jnp.ndarray:
    """The one definition of int8 dequant semantics, shared bit-exactly by
    kernels and oracles: ``v̂ = float32(q) * scale`` — a single f32
    multiply by the slot's block scale, nothing else (no rounding, no
    intermediate cast).  The kernels perform the same multiply on their
    (c_blk, l) value tile before the accumulate, so kernel and oracle
    dequantized values are bitwise equal.  Padding slots store q == 0 and
    dequantize to exactly 0.0, preserving the zero-contribution
    invariant."""
    scale = jnp.repeat(scale_blk.astype(jnp.float32), c_blk)  # (T*c_blk,)
    return m_blocks.astype(jnp.float32) * scale[:, None]


def gather_fill_ref(
    col_blocks: jnp.ndarray,  # (T, l) int32 original column indices
    x_padded: jnp.ndarray,  # (S*l, B) zero-padded vector
) -> jnp.ndarray:
    """Oracle for the Buffer Filler: plain gather ``x[col]``, (T, l, B)."""
    return jnp.take(x_padded.astype(jnp.float32), col_blocks.astype(jnp.int32), axis=0)


def gather_fill_local_ref(
    col_loc: jnp.ndarray,  # (T*c_blk, l) block-local column indices
    seg_blk: jnp.ndarray,  # (T, S_blk) int32 per-block segment table
    x_padded: jnp.ndarray,  # (S*l, B) zero-padded vector
    *,
    l: int,
    c_blk: int,
) -> jnp.ndarray:
    """Oracle for the segment-local Buffer Filler: gather each block's
    ``S_blk`` x tiles by the segment table, then index them block-locally
    — ``x[seg_blk[t, col_loc // l] * l + col_loc % l]``.  Bit-identical
    to :func:`gather_fill_ref` on the same stream because the table maps
    every local id back to the slot's original column."""
    seg_blk = seg_blk.astype(jnp.int32)
    t_blk, s_blk = seg_blk.shape
    b = x_padded.shape[1]
    tiles = x_padded.astype(jnp.float32).reshape(-1, l, b)[seg_blk]
    # tiles: (T, S_blk, l, B) -> local address space (T, S_blk*l, B)
    tiles = tiles.reshape(t_blk, s_blk * l, b)
    rows = col_loc.shape[0]
    blk = jnp.arange(rows, dtype=jnp.int32) // c_blk
    return tiles[blk[:, None], col_loc.astype(jnp.int32), :]  # (rows, l, B)


def gust_spgemm_ref(
    m_blocks: jnp.ndarray,  # (T*c_blk, l) A values (0 in padding)
    col_blocks: jnp.ndarray,  # (T*c_blk, l) int32 ORIGINAL A columns (B row ids)
    row_blocks: jnp.ndarray,  # (T*c_blk, l) int32 adder index
    window: jnp.ndarray,  # (T*c_blk,) int32 window id of each stream row
    b_vals: jnp.ndarray,  # (R, k_max) condensed B row values (0 in padding)
    b_cols: jnp.ndarray,  # (R, k_max) int32 condensed B row columns (0 in padding)
    *,
    num_windows: int,
    l: int,
    n_out: int,
) -> jnp.ndarray:
    """Oracle for the SpGEMM kernel: sparse×sparse through A's color-block
    stream as an outer-product schedule over B's condensed rows.

    Each scheduled slot ``(a = A[i, j], row, col=j)`` gathers B's condensed
    row ``j`` — its ``k_max`` padded ``(value, column)`` pairs — multiplies
    the values by ``a``, and merges every partial product into the dense
    per-window row accumulator at ``(window*l + row, b_col)``.  Padding A
    slots carry ``a == 0`` and padding B entries carry ``value == 0``, so
    both contribute exactly zero (the packed-format zero-contribution
    invariant extends to the product).  Returns ``(W, l, n_out)`` f32 —
    the same per-window accumulator shape as the SpMV oracles with the
    vector batch replaced by B's output columns."""
    col = col_blocks.astype(jnp.int32)
    bv = jnp.take(b_vals.astype(jnp.float32), col, axis=0)  # (T, l, k_max)
    bc = jnp.take(b_cols.astype(jnp.int32), col, axis=0)  # (T, l, k_max)
    partial = m_blocks.astype(jnp.float32)[:, :, None] * bv  # (T, l, k_max)
    adder = window.astype(jnp.int32)[:, None] * l + row_blocks.astype(
        jnp.int32
    )  # (T, l)
    idx = adder[:, :, None] * n_out + bc  # (T, l, k_max)
    y = jax.ops.segment_sum(
        partial.reshape(-1),
        idx.reshape(-1),
        num_segments=num_windows * l * n_out,
    )
    return y.reshape(num_windows, l, n_out)


def _route_rows_onehot(m_blk, gs, row_blk, *, l):
    """The crossbar as a one-hot matmul, the oracle of the kernels'
    lane-gather ``route_rows``: per cycle row ``c`` of a (C_blk, l) block,
    ``m[c] * g[c]`` times ``OneHot(row[c])`` at ``HIGHEST`` precision,
    sending lane ``j`` to adder ``row[c, j]``, summed over the cycle rows
    in order.  ``gs`` is the per-cycle tuple of gathered (B_pad, l)
    arrays; returns the block's (B_pad, l) f32 contribution."""
    row = row_blk.astype(jnp.int32)
    adder = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    acc = None
    for c, g in enumerate(gs):
        p = m_blk[c:c + 1] * g  # (B_pad, l)
        onehot = (adder == row[c:c + 1]).astype(jnp.float32)  # (l_out, l)
        y = jax.lax.dot_general(
            p, onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        acc = y if acc is None else acc + y
    return acc


def _window_accumulate(
    m_blocks: jnp.ndarray,  # (T, l) values (0 in padding)
    v_sch: jnp.ndarray,  # (T, l, B) gathered vector stream
    row_blocks: jnp.ndarray,  # (T, l) int32 adder index
    window: jnp.ndarray,  # (T,) int32 window id of each stream row
    *,
    num_windows: int,
    l: int,
) -> jnp.ndarray:
    """Shared multiply + scatter-add of every oracle: identical
    instructions downstream of the gather keep the resident/local oracle
    pair bit-identical by construction."""
    partial = m_blocks.astype(jnp.float32)[:, :, None] * v_sch
    adder = window[:, None] * l + row_blocks.astype(jnp.int32)  # (T, l)
    b = v_sch.shape[-1]
    y = jax.ops.segment_sum(
        partial.reshape(-1, b),
        adder.reshape(-1),
        num_segments=num_windows * l,
    )
    return y.reshape(num_windows, l, b)


def _padded_windows(total: int, num_windows: int) -> jnp.ndarray:
    c_pad = total // num_windows
    return jnp.arange(total, dtype=jnp.int32) // c_pad


def gust_spmv_ref(
    m_blocks: jnp.ndarray,  # (W*C_pad, l) values (0 in padding)
    col_blocks: jnp.ndarray,  # (W*C_pad, l) int32
    row_blocks: jnp.ndarray,  # (W*C_pad, l) int32 adder index
    x_padded: jnp.ndarray,  # (S*l, B)
    *,
    num_windows: int,
    l: int,
    scale_blk: jnp.ndarray = None,  # (T_blk,) f32 when the stream is int8
    c_blk: int = 8,
) -> jnp.ndarray:
    """Oracle for the flagship kernel: gather, multiply, scatter-add into
    per-window accumulators.  ``scale_blk`` dequantizes an int8 stream
    first (:func:`dequant_ref`).  Returns (W, l, B) f32."""
    if scale_blk is not None:
        m_blocks = dequant_ref(m_blocks, scale_blk, c_blk=c_blk)
    v_sch = gather_fill_ref(col_blocks, x_padded)  # (T, l, B)
    window = _padded_windows(m_blocks.shape[0], num_windows)
    return _window_accumulate(
        m_blocks, v_sch, row_blocks, window, num_windows=num_windows, l=l
    )


def gust_spmv_local_ref(
    m_blocks: jnp.ndarray,  # (W*C_pad, l) values (0 in padding)
    col_loc: jnp.ndarray,  # (W*C_pad, l) block-local columns
    row_blocks: jnp.ndarray,  # (W*C_pad, l) int32 adder index
    seg_blk: jnp.ndarray,  # (T_blk, S_blk) segment table
    x_padded: jnp.ndarray,  # (S*l, B)
    *,
    num_windows: int,
    l: int,
    c_blk: int,
    scale_blk: jnp.ndarray = None,  # (T_blk,) f32 when the stream is int8
) -> jnp.ndarray:
    """Segment-local oracle for the padded layout (gather via the
    pack-time table; same accumulate).  Returns (W, l, B) f32."""
    if scale_blk is not None:
        m_blocks = dequant_ref(m_blocks, scale_blk, c_blk=c_blk)
    v_sch = gather_fill_local_ref(col_loc, seg_blk, x_padded, l=l, c_blk=c_blk)
    window = _padded_windows(m_blocks.shape[0], num_windows)
    return _window_accumulate(
        m_blocks, v_sch, row_blocks, window, num_windows=num_windows, l=l
    )


def gust_spmv_ragged_ref(
    m_blocks: jnp.ndarray,  # (T_blk*c_blk, l) values (0 in padding)
    col_blocks: jnp.ndarray,  # (T_blk*c_blk, l) int32
    row_blocks: jnp.ndarray,  # (T_blk*c_blk, l) int32 adder index
    block_window: jnp.ndarray,  # (T_blk,) int32 window id of each block
    x_padded: jnp.ndarray,  # (S*l, B)
    *,
    num_windows: int,
    l: int,
    c_blk: int,
    scale_blk: jnp.ndarray = None,  # (T_blk,) f32 when the stream is int8
) -> jnp.ndarray:
    """Oracle for the ragged scalar-prefetch kernel: same gather/multiply,
    with the window of each stream row read from ``block_window`` instead
    of a fixed ``C_pad`` stride.  Returns (W, l, B) f32."""
    if scale_blk is not None:
        m_blocks = dequant_ref(m_blocks, scale_blk, c_blk=c_blk)
    v_sch = gather_fill_ref(col_blocks, x_padded)  # (T, l, B)
    window = jnp.repeat(block_window.astype(jnp.int32), c_blk)  # (T,)
    return _window_accumulate(
        m_blocks, v_sch, row_blocks, window, num_windows=num_windows, l=l
    )


def gust_spmv_ragged_local_ref(
    m_blocks: jnp.ndarray,  # (T_blk*c_blk, l) values (0 in padding)
    col_loc: jnp.ndarray,  # (T_blk*c_blk, l) block-local columns
    row_blocks: jnp.ndarray,  # (T_blk*c_blk, l) int32 adder index
    seg_blk: jnp.ndarray,  # (T_blk, S_blk) segment table
    block_window: jnp.ndarray,  # (T_blk,) int32 window id of each block
    x_padded: jnp.ndarray,  # (S*l, B)
    *,
    num_windows: int,
    l: int,
    c_blk: int,
    scale_blk: jnp.ndarray = None,  # (T_blk,) f32 when the stream is int8
) -> jnp.ndarray:
    """Segment-local oracle for the ragged stream.  Returns (W, l, B)."""
    if scale_blk is not None:
        m_blocks = dequant_ref(m_blocks, scale_blk, c_blk=c_blk)
    v_sch = gather_fill_local_ref(col_loc, seg_blk, x_padded, l=l, c_blk=c_blk)
    window = jnp.repeat(block_window.astype(jnp.int32), c_blk)
    return _window_accumulate(
        m_blocks, v_sch, row_blocks, window, num_windows=num_windows, l=l
    )
