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

    # kernel layout: (segment, batch padded to 8 sublanes, lane)
    xs = jnp.pad(xp, ((0, 0), (0, 5))).reshape(seg, 8, 8).transpose(0, 2, 1)
    fn = make_gust_spmv(packed.num_windows, packed.c_pad, 8, seg, 3)
    y_k = np.asarray(fn(packed.m_blk, packed.col_blk, packed.row_blk, xs))
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
    # kernel layout: (segment, batch padded to 8 sublanes, lane)
    xs = jnp.pad(jnp.asarray(x), ((0, 0), (0, 8 - b)))
    xs = xs.reshape(seg, l, 8).transpose(0, 2, 1)
    out = np.asarray(fn(jnp.asarray(cols), xs))[:, :b, :].transpose(0, 2, 1)
    ref = np.asarray(gather_fill_ref(jnp.asarray(cols), jnp.asarray(x)))
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)
