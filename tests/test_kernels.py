"""Pallas kernel sweeps: shapes × dtypes × batch vs the pure-jnp oracle
(kernels/ref.py) and the dense ground truth.  Kernels run interpret=True
on CPU (the kernel body executes in Python) — the TPU BlockSpec tiling is
exercised structurally."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.formats import coo_from_dense
from repro.core.scheduler import schedule
from repro.kernels.gather_fill import make_gather_fill
from repro.kernels.gust_spmv import _resident_x_rows
from repro.kernels.ops import gust_spmm, pack_schedule
from repro.kernels.ref import gather_fill_ref, gust_spmv_ref


def random_dense(rng, m, n, density):
    return ((rng.random((m, n)) < density) * rng.standard_normal((m, n))).astype(
        np.float32
    )


SHAPE_SWEEP = [
    # (m, n, l, B, density)
    (8, 8, 4, 1, 0.3),
    (16, 64, 8, 1, 0.1),
    (64, 48, 16, 4, 0.2),
    (100, 130, 32, 8, 0.05),  # non-divisible m, n
    (33, 7, 8, 2, 0.5),  # n < l
    (256, 256, 32, 3, 0.02),
]


@pytest.mark.parametrize("m,n,l,b,density", SHAPE_SWEEP)
@pytest.mark.parametrize("lb", [False, True])
def test_gust_spmv_kernel_sweep(m, n, l, b, density, lb):
    rng = np.random.default_rng(m * 1000 + n)
    dense = random_dense(rng, m, n, density)
    x = rng.standard_normal((n, b)).astype(np.float32)
    ref = dense @ x
    sched = schedule(coo_from_dense(dense), l, load_balance=lb)
    packed = pack_schedule(sched)
    assert packed.fusable, "scheduler output must satisfy the lane structure"
    y_kernel = np.asarray(gust_spmm(packed, jnp.asarray(x), use_kernel=True))
    y_xla = np.asarray(gust_spmm(packed, jnp.asarray(x), use_kernel=False))
    np.testing.assert_allclose(y_kernel, ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(y_xla, ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(y_kernel, y_xla, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gust_spmv_dtypes(dtype):
    rng = np.random.default_rng(5)
    dense = random_dense(rng, 64, 96, 0.2)
    x = rng.standard_normal((96, 4)).astype(np.float32)
    sched = schedule(coo_from_dense(dense), 16)
    packed = pack_schedule(sched, value_dtype=dtype)
    y = np.asarray(gust_spmm(packed, jnp.asarray(x, dtype))).astype(np.float32)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    ref = dense @ x
    err = np.abs(y - ref).max() / np.abs(ref).max()
    assert err < tol, err


@pytest.mark.parametrize("c_blk", [4, 8, 16])
def test_gust_spmv_block_shapes(c_blk):
    """BlockSpec color-block sweep — different VMEM tile heights must give
    identical results."""
    rng = np.random.default_rng(9)
    dense = random_dense(rng, 48, 64, 0.15)
    x = rng.standard_normal((64, 2)).astype(np.float32)
    sched = schedule(coo_from_dense(dense), 8)
    packed = pack_schedule(sched, c_blk=c_blk)
    y = np.asarray(gust_spmm(packed, jnp.asarray(x), c_blk=c_blk))
    np.testing.assert_allclose(y, dense @ x, rtol=1e-4, atol=1e-4)


def test_kernel_vs_ref_on_packed_blocks():
    """Kernel output == ref.py oracle on the same packed blocks (exact
    same semantics, including padding slots)."""
    rng = np.random.default_rng(11)
    dense = random_dense(rng, 40, 56, 0.25)
    sched = schedule(coo_from_dense(dense), 8)
    packed = pack_schedule(sched)
    x = rng.standard_normal((56, 3)).astype(np.float32)
    seg = packed.seg_count
    xp = jnp.pad(jnp.asarray(x), ((0, seg * 8 - 56), (0, 0)))
    y_ref = np.asarray(
        gust_spmv_ref(
            packed.m_blk, packed.col_blk, packed.row_blk, xp,
            num_windows=packed.num_windows, l=packed.l,
        )
    )
    from repro.kernels.gust_spmv import make_gust_spmv

    # resident layout: (batch, segment padded to groups of 8, lane)
    rows = _resident_x_rows(seg)
    xs = jnp.pad(xp, ((0, (rows - seg) * 8), (0, 0))).T.reshape(3, rows, 8)
    fn = make_gust_spmv(packed.num_windows, packed.c_pad, 8, seg, 3)
    y_k = np.asarray(fn(packed.m_blk, packed.col_blk, packed.src_blk, xs))
    y_k = y_k[:, :3, :].transpose(0, 2, 1)
    np.testing.assert_allclose(y_k, y_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("l,seg,b", [(8, 4, 1), (16, 3, 4), (32, 8, 2)])
def test_gather_fill_kernel(l, seg, b):
    rng = np.random.default_rng(l)
    n = seg * l
    total = 16
    x = rng.standard_normal((n, b)).astype(np.float32)
    # build col indices honouring the lane structure (off == lane or
    # l-1-lane), like the scheduler emits
    lanes = np.tile(np.arange(l), (total, 1))
    segs = rng.integers(0, seg, (total, l))
    flip = rng.integers(0, 2, (total, l)).astype(bool)
    offs = np.where(flip, l - 1 - lanes, lanes)
    cols = (segs * l + offs).astype(np.int32)
    fn = make_gather_fill(total, l, seg, b)
    # resident layout: (batch, segment padded to groups of 8, lane)
    rows = _resident_x_rows(seg)
    xs = jnp.pad(jnp.asarray(x), ((0, (rows - seg) * l), (0, 0)))
    xs = xs.T.reshape(b, rows, l)
    out = np.asarray(fn(jnp.asarray(cols), xs))[:, :b, :].transpose(0, 2, 1)
    ref = np.asarray(gather_fill_ref(jnp.asarray(cols), jnp.asarray(x)))
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Resident walk: eight segments a step, bitwise against the oracle.
# ---------------------------------------------------------------------------

WALK_L = 8
RESIDENT_KERNELS = ["padded-single", "padded-double", "ragged-single",
                    "ragged-double", "gather_fill"]


def _walk_case(seg_count, b, quantized):
    """A load-balanced schedule whose columns span ``seg_count`` segments
    (the last one partial), and an x, on which every order of summation
    gives the same f32 result, so kernel and oracle agree bitwise: small
    integer values and distinct integer x, or, for the int8 stream (whose
    scales are not integers), one nonzero per row."""
    l = WALK_L
    n = seg_count * l - (2 if seg_count > 1 else 0)
    m = 24
    rng = np.random.default_rng(100 * seg_count + b)
    if quantized:
        dense = np.zeros((m, n), np.float32)
        dense[np.arange(m), rng.integers(0, n, m)] = rng.standard_normal(m)
        x = rng.standard_normal((n, b)).astype(np.float32)
    else:
        vals = rng.integers(-4, 5, (m, n)) * (rng.random((m, n)) < 0.4)
        dense = vals.astype(np.float32)
        x = (rng.permutation(n * b) - n * b // 2).reshape(n, b)
        x = x.astype(np.float32)
    sched = schedule(coo_from_dense(dense), l, load_balance=True)
    return dense, sched, x


@pytest.mark.parametrize("kernel", RESIDENT_KERNELS)
@pytest.mark.parametrize("b", [1, 3, 8])
@pytest.mark.parametrize("seg_count", [1, 7, 8, 9, 23])
def test_resident_walk_bitwise_vs_ref(seg_count, b, kernel):
    """Every caller of the resident walk equals ``kernels/ref.py`` bit for
    bit: segment counts that fill whole groups of eight, leave a partial
    last group or a single segment, at batches below, at and off a
    sublane group, with flipped lanes and padding slots in the stream."""
    from repro.core.packing import pack_ragged
    from repro.kernels.ops import execute_spmm

    dense, sched, x = _walk_case(seg_count, b, quantized=False)
    packed = pack_schedule(sched)
    assert packed.seg_count == seg_count
    lane = np.arange(WALK_L)[None, :]
    col, m = np.asarray(packed.col_blk), np.asarray(packed.m_blk)
    # the scheduler flips every other segment by rank, so a single
    # segment is never flipped; gather_fill flips its own below
    flipped = (col % WALK_L != lane) & (m != 0)
    assert flipped.any() or seg_count == 1, "no flipped lane"
    assert (m == 0).any(), "no padding slot"
    xj = jnp.asarray(x)
    if kernel == "gather_fill":
        total = col.shape[0]
        flip = np.random.default_rng(seg_count).random(col.shape) < 0.5
        col = col // WALK_L * WALK_L + np.where(flip, WALK_L - 1 - lane, lane)
        rows = _resident_x_rows(seg_count)
        xs = jnp.pad(xj, ((0, rows * WALK_L - x.shape[0]), (0, 0)))
        xs = xs.T.reshape(b, rows, WALK_L)
        fn = make_gather_fill(total, WALK_L, seg_count, b)
        out = np.asarray(fn(jnp.asarray(col), xs))[:, :b, :]
        xp = jnp.pad(xj, ((0, seg_count * WALK_L - x.shape[0]), (0, 0)))
        ref = np.asarray(gather_fill_ref(jnp.asarray(col), xp))
        assert np.array_equal(out.transpose(0, 2, 1), ref)
        return
    layout, pipeline = kernel.split("-")
    art = packed if layout == "padded" else pack_ragged(sched)
    y = execute_spmm(art, xj, use_kernel=True, gather="resident",
                     pipeline=pipeline)
    y_ref = execute_spmm(art, xj, use_kernel=False, gather="resident")
    assert np.array_equal(np.asarray(y), np.asarray(y_ref))
    assert np.array_equal(np.asarray(y_ref), dense @ x)


@pytest.mark.parametrize("kernel", RESIDENT_KERNELS[:4])
def test_resident_walk_bitwise_vs_ref_int8(kernel):
    """The int8 stream (per-block scales) goes through the same walk."""
    from repro.core.packing import pack_ragged
    from repro.kernels.ops import execute_spmm

    _, sched, x = _walk_case(9, 3, quantized=True)
    layout, pipeline = kernel.split("-")
    pack = pack_schedule if layout == "padded" else pack_ragged
    art = pack(sched, value_dtype=jnp.int8)
    assert art.scale_blk is not None
    xj = jnp.asarray(x)
    y = execute_spmm(art, xj, use_kernel=True, gather="resident",
                     pipeline=pipeline)
    y_ref = execute_spmm(art, xj, use_kernel=False, gather="resident")
    assert np.array_equal(np.asarray(y), np.asarray(y_ref))


# ---------------------------------------------------------------------------
# Crossbar: the lane-gather route against the one-hot route it replaced.
# ---------------------------------------------------------------------------

ROUTE_CASES = ["mixed", "empty_cycles", "full_cycles", "all_padding",
               "zero_value", "int8"]


def _route_block(l, case, rng, c_blk=8):
    """One (c_blk, l) block of a collision-free stream: per cycle row a
    random partial permutation of lanes onto adders; padding lanes hold
    value 0 and adder 0 as packing writes them.  Values span 20 decades
    either side of 1 (the gathered x 15, so no product leaves the normal
    f32 range).  Returns ``(stored values, dequantized f32 values,
    row)``."""
    m = np.zeros((c_blk, l), np.float32)
    row = np.zeros((c_blk, l), np.int32)
    for c in range(c_blk):
        if case == "all_padding" or (case == "empty_cycles" and c % 2):
            continue
        perm = rng.permutation(l)
        real = (np.ones(l, bool) if case == "full_cycles" or c == 0
                else rng.random(l) < 0.6)
        row[c, real] = perm[real]
        mag = 10.0 ** rng.uniform(-20, 20, l)
        m[c, real] = (np.sign(rng.standard_normal(l)) * mag)[real]
    if case == "zero_value":
        # a real slot whose value is 0, on a real adder (not adder 0)
        j = int(np.flatnonzero(row[0] != 0)[0])
        m[0, j] = 0.0
    if case == "int8":
        q = np.clip(np.rint(rng.standard_normal((c_blk, l)) * 60), -127, 127)
        q = np.where(m != 0, q, 0).astype(np.int8)
        q[0, 0] = 0 if row[0, 0] else q[0, 0]  # a value quantized to 0
        return q, q.astype(np.float32) * np.float32(0.0173), row
    return m, m, row


@pytest.mark.parametrize("case", ROUTE_CASES)
@pytest.mark.parametrize("b_pad", [8, 64])
@pytest.mark.parametrize("l", [64, 128, 256, 512])
def test_route_rows_equals_onehot_route(l, b_pad, case):
    """``route_rows`` (a lane gather by the pack-time source-lane table)
    equals the one-hot HIGHEST-precision matmul route bit for bit, up to
    the sign of zero, at every lane width the gather splits differently
    (one piece below 128 lanes, 1, 2 and 4 vreg chunks) and at one and
    eight sublane tiles of batch."""
    from repro.core.packing import _source_lanes
    from repro.kernels.gust_spmv import route_rows
    from repro.kernels.ref import _route_rows_onehot

    rng = np.random.default_rng(l * 100 + b_pad + ROUTE_CASES.index(case))
    stored, m, row = _route_block(l, case, rng)
    src = _source_lanes(stored, row)
    real = stored != 0
    if case == "all_padding":
        assert (src == -1).all()
    else:
        assert (src >= 0).any() and (src == -1).any() or case == "full_cycles"
    gs = tuple(
        jnp.asarray(rng.standard_normal((b_pad, l)).astype(np.float32)
                    * 10.0 ** rng.uniform(-15, 15, (b_pad, l)))
        for _ in range(m.shape[0])
    )
    y = np.asarray(route_rows(jnp.asarray(m), gs, jnp.asarray(src), l=l))
    y_ref = np.asarray(_route_rows_onehot(jnp.asarray(m), gs,
                                          jnp.asarray(row), l=l))
    assert y.shape == (b_pad, l)
    assert np.array_equal(y, y_ref)
    assert real.any() == bool(np.any(y != 0))
