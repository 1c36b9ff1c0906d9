"""Ragged color-block streaming: equivalence + format invariants.

The ragged stream (``core/packing.pack_ragged``) must execute *exactly*
the same math as the padded layout while streaming only real blocks:

  * property test (hypothesis, random + power-law degree matrices, all
    three colorers): ``gust_spmm`` output is **bit-identical** between
    the padded and ragged paths — kernel vs kernel and oracle vs oracle
    (kernel vs oracle stays allclose: the one-hot routing matmul reduces
    in a different order than segment-sum);
  * block-metadata contract: contiguous sorted ``block_window``, per-
    window prefix ``block_starts``, >= 1 block per window, padding slots
    keep the packed-format invariants in each window's final partial
    block;
  * ``pack_auto`` picks by the measured waste ratio; ``gust_spmm_auto``
    routes through the content-keyed cache; kernel builders are memoized
    on geometry.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.formats import coo_from_dense
from repro.core.packing import (
    PackedSchedule,
    RaggedSchedule,
    ScheduleCache,
    pack_auto,
    pack_ragged,
    pack_schedule,
    ragged_from_leaves,
    ragged_leaves,
    ragged_meta,
    ragged_waste_ratio,
)
from repro.core.scheduler import schedule
from repro.core.spmv import spmm_ragged
from repro.kernels.ops import gust_spmm, gust_spmm_auto


def random_dense(rng, m, n, density):
    return ((rng.random((m, n)) < density) * rng.standard_normal((m, n))).astype(
        np.float32
    )


def power_law_dense(rng, m, n, base_density=0.03, heavy_rows=4,
                    heavy_density=0.6):
    """Skewed (power-law-degree surrogate): a few dense rows on a sparse
    background — max window colors far above the mean, the regime where
    the padded layout streams mostly dead cycles."""
    dense = random_dense(rng, m, n, base_density)
    k = min(heavy_rows, m)
    rows = rng.choice(m, k, replace=False)
    dense[rows] = (rng.random((k, n)) < heavy_density) * rng.standard_normal(
        (k, n)
    )
    return dense.astype(np.float32)


def all_paths(sched, x, c_blk=8):
    """y from all four execution paths on one schedule."""
    p = pack_schedule(sched, c_blk)
    r = pack_ragged(sched, c_blk)
    xs = jnp.asarray(x)
    return {
        "pad_kernel": np.asarray(gust_spmm(p, xs, use_kernel=True, c_blk=c_blk)),
        "rag_kernel": np.asarray(gust_spmm(r, xs, use_kernel=True)),
        "pad_xla": np.asarray(gust_spmm(p, xs, use_kernel=False, c_blk=c_blk)),
        "rag_xla": np.asarray(gust_spmm(r, xs, use_kernel=False)),
    }, p, r


def assert_equivalent(ys, ref):
    assert np.array_equal(ys["pad_kernel"], ys["rag_kernel"]), \
        "padded vs ragged kernel not bit-identical"
    assert np.array_equal(ys["pad_xla"], ys["rag_xla"]), \
        "padded vs ragged oracle not bit-identical"
    for k, y in ys.items():
        np.testing.assert_allclose(y, ref, rtol=2e-4, atol=2e-4, err_msg=k)


# ---------------------------------------------------------------------------
# equivalence sweeps
# ---------------------------------------------------------------------------


SHAPE_SWEEP = [
    # (m, n, l, B, density)
    (16, 64, 8, 1, 0.1),
    (64, 48, 16, 4, 0.2),
    (100, 130, 32, 8, 0.05),  # non-divisible m, n
    (33, 7, 8, 2, 0.5),  # n < l
]


@pytest.mark.parametrize("m,n,l,b,density", SHAPE_SWEEP)
@pytest.mark.parametrize("lb", [False, True])
def test_ragged_vs_padded_sweep(m, n, l, b, density, lb):
    rng = np.random.default_rng(m * 1000 + n)
    dense = random_dense(rng, m, n, density)
    x = rng.standard_normal((n, b)).astype(np.float32)
    sched = schedule(coo_from_dense(dense), l, load_balance=lb)
    ys, _, r = all_paths(sched, x)
    assert r.fusable
    assert_equivalent(ys, dense @ x)


def test_ragged_power_law_streams_fewer_blocks():
    """On the skewed surrogate the ragged stream must be >= 2x smaller
    while remaining bit-identical (the ISSUE 2 acceptance shape)."""
    rng = np.random.default_rng(0)
    dense = power_law_dense(rng, 128, 128, heavy_rows=6)
    x = rng.standard_normal((128, 3)).astype(np.float32)
    sched = schedule(coo_from_dense(dense), 8)
    cpw = np.diff(sched.window_starts)
    assert cpw.max() / max(cpw.mean(), 1e-9) >= 4, "surrogate not skewed"
    ys, p, r = all_paths(sched, x)
    assert_equivalent(ys, dense @ x)
    assert p.m_blk.shape[0] >= 2 * r.m_blk.shape[0], (
        p.m_blk.shape, r.m_blk.shape
    )
    assert ragged_waste_ratio(sched) >= 2.0


@pytest.mark.parametrize("lb", [False, True])
def test_ragged_empty_windows_and_empty_matrix(lb):
    rng = np.random.default_rng(7)
    dense = np.zeros((32, 40), np.float32)
    for row in list(range(0, 8)) + list(range(16, 24)):
        cols = rng.choice(40, 5, replace=False)
        dense[row, cols] = rng.standard_normal(5)
    for d in (dense, np.zeros((24, 16), np.float32)):
        sched = schedule(coo_from_dense(d), 8, load_balance=lb)
        x = rng.standard_normal((d.shape[1], 2)).astype(np.float32)
        ys, _, r = all_paths(sched, x)
        assert_equivalent(ys, d @ x)
        # empty windows still own exactly one (all-padding) block
        assert np.all(np.diff(np.asarray(r.block_starts)) >= 1)


@pytest.mark.parametrize("value_dtype,index_dtype",
                         [(jnp.float32, jnp.int32), (jnp.bfloat16, jnp.int16)])
def test_ragged_dtype_variants(value_dtype, index_dtype):
    rng = np.random.default_rng(3)
    dense = random_dense(rng, 48, 64, 0.2)
    x = rng.standard_normal((64, 2)).astype(np.float32)
    sched = schedule(coo_from_dense(dense), 16)
    r = pack_ragged(sched, value_dtype=value_dtype, index_dtype=index_dtype)
    assert r.m_blk.dtype == jnp.dtype(value_dtype)
    assert r.col_blk.dtype == jnp.dtype(index_dtype)
    p = pack_schedule(sched, value_dtype=value_dtype, index_dtype=index_dtype)
    for uk in (False, True):
        yr = np.asarray(gust_spmm(r, jnp.asarray(x), use_kernel=uk))
        yp = np.asarray(gust_spmm(p, jnp.asarray(x), use_kernel=uk))
        assert np.array_equal(yr, yp)


# ---------------------------------------------------------------------------
# format invariants + metadata contract
# ---------------------------------------------------------------------------


def test_ragged_block_metadata_contract():
    rng = np.random.default_rng(1)
    dense = power_law_dense(rng, 64, 64)
    sched = schedule(coo_from_dense(dense), 8)
    r = pack_ragged(sched, c_blk=8)
    bs = np.asarray(r.block_starts)
    bw = np.asarray(r.block_window)
    cpw = np.diff(sched.window_starts)
    # prefix structure, >= 1 block per window, counts match ceil(C_w/c_blk)
    assert bs[0] == 0 and bs[-1] == r.num_blocks
    bpw = np.diff(bs)
    assert np.all(bpw == np.maximum(-(-cpw // r.c_blk), 1))
    # block_window is the expansion of the prefix (sorted, contiguous)
    assert np.array_equal(bw, np.repeat(np.arange(r.num_windows), bpw))
    # padding slots in each window's final partial block keep the packed-
    # format invariants: value 0, col == own lane, row 0
    m_s = np.asarray(r.m_blk)
    c_s = np.asarray(r.col_blk)
    r_s = np.asarray(r.row_blk)
    lane = np.arange(r.l, dtype=np.int32)
    for w in range(r.num_windows):
        pad_lo = int(bs[w]) * r.c_blk + int(cpw[w])
        pad_hi = int(bs[w + 1]) * r.c_blk
        assert np.all(m_s[pad_lo:pad_hi] == 0.0)
        assert np.all(c_s[pad_lo:pad_hi] == lane)
        assert np.all(r_s[pad_lo:pad_hi] == 0)


def test_repad_to_blocks_invariants_and_numerics():
    rng = np.random.default_rng(11)
    dense = random_dense(rng, 40, 56, 0.25)
    x = rng.standard_normal((56, 3)).astype(np.float32)
    sched = schedule(coo_from_dense(dense), 8)
    r = pack_ragged(sched)
    g = r.repad_to_blocks(r.num_blocks + 4)
    assert g.num_blocks == r.num_blocks + 4
    rows0 = r.num_blocks * r.c_blk
    assert np.all(np.asarray(g.m_blk)[rows0:] == 0.0)
    assert np.all(np.asarray(g.col_blk)[rows0:] == np.arange(g.l))
    assert np.all(np.asarray(g.row_blk)[rows0:] == 0)
    assert np.asarray(g.block_starts)[-1] == g.num_blocks
    # trailing blocks attribute to the last window; stream stays sorted
    assert np.all(np.diff(np.asarray(g.block_window)) >= 0)
    for uk in (False, True):
        ya = np.asarray(gust_spmm(r, jnp.asarray(x), use_kernel=uk))
        yb = np.asarray(gust_spmm(g, jnp.asarray(x), use_kernel=uk))
        assert np.array_equal(ya, yb)
    assert r.repad_to_blocks(r.num_blocks) is r
    with pytest.raises(ValueError):
        r.repad_to_blocks(r.num_blocks - 1)


def test_ragged_compact_repad_preserves_dtypes():
    rng = np.random.default_rng(2)
    sched = schedule(coo_from_dense(random_dense(rng, 48, 64, 0.2)), 16)
    r = pack_ragged(sched, value_dtype=jnp.bfloat16, index_dtype=jnp.int16)
    g = r.repad_to_blocks(r.num_blocks + 2)
    assert g.m_blk.dtype == jnp.bfloat16
    assert g.col_blk.dtype == jnp.int16 and g.row_blk.dtype == jnp.int16


def test_ragged_codec_round_trip():
    rng = np.random.default_rng(6)
    sched = schedule(coo_from_dense(random_dense(rng, 30, 44, 0.15)), 8)
    r = pack_ragged(sched)
    q = ragged_from_leaves(ragged_leaves(r), ragged_meta(r))
    assert isinstance(q, RaggedSchedule)
    assert ragged_meta(q) == ragged_meta(r)
    for k, v in ragged_leaves(r).items():
        assert np.array_equal(np.asarray(getattr(q, k)), np.asarray(v))
    with pytest.raises(ValueError):
        ragged_from_leaves(ragged_leaves(r), ("padded",) + ragged_meta(r)[1:])


def test_spmm_ragged_matches_dense():
    rng = np.random.default_rng(4)
    dense = power_law_dense(rng, 64, 48)
    x = rng.standard_normal((48, 5)).astype(np.float32)
    sched = schedule(coo_from_dense(dense), 8)
    y = np.asarray(spmm_ragged(pack_ragged(sched), jnp.asarray(x)))
    np.testing.assert_allclose(y, dense @ x, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# auto-select + caching
# ---------------------------------------------------------------------------


def test_pack_auto_selects_by_waste():
    rng = np.random.default_rng(5)
    skewed = power_law_dense(rng, 128, 128, heavy_rows=6)
    s_skew = schedule(coo_from_dense(skewed), 8)
    assert isinstance(pack_auto(s_skew), RaggedSchedule)
    # near-uniform windows -> negligible waste -> padded layout
    uniform = random_dense(rng, 64, 64, 0.3)
    s_uni = schedule(coo_from_dense(uniform), 8)
    assert ragged_waste_ratio(s_uni) < 2.0
    assert isinstance(pack_auto(s_uni), PackedSchedule)
    # threshold is respected
    assert isinstance(
        pack_auto(s_skew, waste_threshold=1e9), PackedSchedule
    )


def test_gust_spmm_auto_routes_through_cache():
    rng = np.random.default_rng(8)
    dense = power_law_dense(rng, 64, 64)
    x = rng.standard_normal((64, 2)).astype(np.float32)
    sched = schedule(coo_from_dense(dense), 8)
    cache = ScheduleCache()
    y1 = np.asarray(gust_spmm_auto(sched, jnp.asarray(x), use_kernel=False,
                                   cache=cache))
    assert cache.misses == 1 and cache.hits == 0
    y2 = np.asarray(gust_spmm_auto(sched, jnp.asarray(x), use_kernel=False,
                                   cache=cache))
    assert cache.hits == 1
    assert np.array_equal(y1, y2)
    np.testing.assert_allclose(y1, dense @ x, rtol=1e-4, atol=1e-4)
    # bypass works
    y3 = np.asarray(gust_spmm_auto(sched, jnp.asarray(x), use_kernel=False,
                                   cache=None))
    assert np.array_equal(y1, y3)


def test_schedule_cache_pack_for_ragged_for():
    rng = np.random.default_rng(9)
    sched = schedule(coo_from_dense(random_dense(rng, 32, 32, 0.2)), 8)
    cache = ScheduleCache()
    p1 = cache.pack_for(sched, c_blk=1)
    p2 = cache.pack_for(sched, c_blk=1)
    assert p1 is p2
    r1 = cache.ragged_for(sched, c_blk=1)
    r2 = cache.ragged_for(sched, c_blk=1)
    assert r1 is r2 and r1 is not p1
    assert cache.ragged_for(sched, c_blk=8) is not r1
    # auto_for delegates to the memoized routes (one decision, same object)
    skewed = schedule(coo_from_dense(power_law_dense(rng, 128, 128)), 8)
    a1 = cache.auto_for(skewed)
    assert isinstance(a1, RaggedSchedule)
    assert cache.auto_for(skewed) is a1
    assert cache.auto_for(skewed) is cache.ragged_for(skewed, c_blk=8)
    assert isinstance(cache.auto_for(sched), PackedSchedule)


def test_dryrun_specs_ragged_layout():
    """A ragged config must dry-run the ragged program: spec leaves carry
    the block metadata and the meta tuple is tagged, so decode_step_gust
    lowers the scalar-prefetch-shaped path (the padded/ragged layouts
    lower different programs — validating one does not cover the other)."""
    import jax

    from repro.configs.base import get_arch
    from repro.models.model_zoo import build_model
    from repro.serving.gust_serve import GustServeConfig, dryrun_specs

    lm = build_model(get_arch("yi_6b").reduced())
    cfg = GustServeConfig(density=0.1, gust_length=16, ragged=True)
    specs = dryrun_specs(lm, cfg)
    for entry in specs["mats"].values():
        assert entry["meta"][0] == "ragged"
        leaves = entry["leaves"]
        assert "block_window" in leaves and "block_starts" in leaves
        assert "seg_blk" in leaves and "col_loc" in leaves
        (tag, l, w, c_blk, t_blk, shape, fusable, s_blk,
         identity_perm) = entry["meta"]
        assert leaves["m_blk"].shape == (lm.stack.reps, t_blk * c_blk, l)
        assert leaves["block_starts"].shape == (lm.stack.reps, w + 1)
        # spec round-trips through the codec into a RaggedSchedule
        proto = ragged_from_leaves(
            {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
             for k, v in leaves.items()},
            entry["meta"],
        )
        assert isinstance(proto, RaggedSchedule)


def test_kernel_builders_memoized():
    from repro.kernels.gather_fill import make_gather_fill
    from repro.kernels.gust_spmv import make_gust_spmv
    from repro.kernels.gust_spmv_ragged import make_gust_spmv_ragged

    assert make_gust_spmv(4, 16, 8, 2, 3) is make_gust_spmv(4, 16, 8, 2, 3)
    assert make_gust_spmv(4, 16, 8, 2, 3) is not make_gust_spmv(4, 16, 8, 2, 4)
    assert make_gust_spmv_ragged(6, 3, 8, 2, 1) is make_gust_spmv_ragged(
        6, 3, 8, 2, 1
    )
    assert make_gather_fill(16, 8, 2, 1) is make_gather_fill(16, 8, 2, 1)


# ---------------------------------------------------------------------------
# serving: ragged layer stacking
# ---------------------------------------------------------------------------


def test_serving_ragged_stack_matches_padded():
    import jax

    from repro.configs.base import get_arch
    from repro.models.model_zoo import build_model
    from repro.serving.gust_serve import (
        GustServeConfig,
        decode_step_gust,
        gustify,
    )

    cfg = get_arch("yi_6b").reduced()
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    caches = lm.init_caches(2, 64, jnp.float32)
    toks = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (2, 1))
    _, caches = lm.prefill(params, {"tokens": toks}, caches, dtype=jnp.float32)
    tok = jnp.full((2, 1), 3, jnp.int32)

    gp = GustServeConfig(density=0.3, gust_length=16, ragged=False)
    gr = GustServeConfig(density=0.3, gust_length=16, ragged=True)
    gust_p = gustify(lm, params, gp)
    gust_r = gustify(lm, params, gr)
    for name in gust_p["mats"]:
        st_p, st_r = gust_p["stats"][name], gust_r["stats"][name]
        # ragged stacks never stream more slots, and utilization only rises
        assert st_r["streamed_slots"] <= st_p["streamed_slots"]
        assert st_r["stream_utilization"] >= st_p["stream_utilization"] - 1e-9
        assert gust_r["mats"][name]["meta"][0] == "ragged"
    lp, _ = decode_step_gust(lm, params, gust_p, caches, tok, jnp.int32(8),
                             cfg=gp, dtype=jnp.float32)
    lr, _ = decode_step_gust(lm, params, gust_r, caches, tok, jnp.int32(8),
                             cfg=gr, dtype=jnp.float32)
    assert np.array_equal(np.asarray(lp), np.asarray(lr))


# ---------------------------------------------------------------------------
# distributed: block-balanced sharding
# ---------------------------------------------------------------------------


def test_distributed_spmv_block_balanced_skewed():
    from conftest import run_spmd_subprocess

    run_spmd_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core.formats import coo_from_dense
from repro.core.scheduler import schedule
from repro.core.spmv import distributed_spmv
from repro.core.packing import default_cache
rng = np.random.default_rng(0)
dense = ((rng.random((96, 64)) < 0.05) * rng.standard_normal((96, 64))).astype(np.float32)
rows = rng.choice(96, 5, replace=False)
dense[rows] = (rng.random((5, 64)) < 0.7) * rng.standard_normal((5, 64))
v = rng.standard_normal(64).astype(np.float32)
mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
sched = schedule(coo_from_dense(dense), 8)
y = np.asarray(distributed_spmv(sched, jnp.asarray(v), mesh, axis="data"))
np.testing.assert_allclose(y, dense @ v, rtol=1e-4, atol=1e-4)
# second call hits the content-keyed cache instead of re-packing
h0 = default_cache.hits
np.asarray(distributed_spmv(sched, jnp.asarray(v), mesh, axis="data"))
assert default_cache.hits == h0 + 1
# fewer windows than devices still works
d2 = ((rng.random((8, 16)) < 0.4) * rng.standard_normal((8, 16))).astype(np.float32)
v2 = rng.standard_normal(16).astype(np.float32)
y2 = np.asarray(distributed_spmv(schedule(coo_from_dense(d2), 8), jnp.asarray(v2), mesh))
np.testing.assert_allclose(y2, d2 @ v2, rtol=1e-4, atol=1e-4)
print("ok")
""")
