"""Packing-layer tests: the vectorized ragged→packed conversion in
``core/packing.py`` must be *bit-identical* to the original per-window
Python loop (kept here as the reference), across colorers, empty windows,
non-divisible shapes, and load balancing; plus ``repad_to`` invariants,
the leaves/meta codec round-trip, and the content-keyed ScheduleCache."""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from conftest import REPO

sys.path.insert(0, os.path.join(REPO, "benchmarks"))
from pack_bench import pack_loop_old  # the seed per-window-loop packer

from repro.core.formats import COOMatrix, coo_from_dense
from repro.core.packing import (
    PackedSchedule,
    ScheduleCache,
    pack_schedule,
    packed_from_leaves,
    packed_leaves,
    packed_meta,
    packed_spec,
    schedule_packed,
    stacked_leaf_specs,
    window_ids,
)
from repro.core.scheduler import schedule
from repro.kernels.ops import gust_spmm


def random_dense(rng, m, n, density):
    return ((rng.random((m, n)) < density) * rng.standard_normal((m, n))).astype(
        np.float32
    )


def pack_loop_reference(sched, c_blk=8, value_dtype=jnp.float32,
                        index_dtype=jnp.int32):
    """Equivalence oracle: the seed per-window loop (shared with
    benchmarks/pack_bench.py) plus the dtype/row_perm finishing of the
    seed ``pack_schedule``."""
    l, W = sched.l, sched.num_windows
    m_b, r_b, c_b, fusable = pack_loop_old(sched, c_blk)
    c_pad = m_b.shape[1]

    row_perm = np.arange(W * l, dtype=np.int32)
    row_perm[: sched.row_perm.shape[0]] = sched.row_perm
    return {
        "m_blk": np.asarray(jnp.asarray(m_b.reshape(W * c_pad, l), value_dtype)),
        "col_blk": c_b.reshape(W * c_pad, l).astype(
            np.dtype(jnp.dtype(index_dtype).name)),
        "row_blk": r_b.reshape(W * c_pad, l).astype(
            np.dtype(jnp.dtype(index_dtype).name)),
        "row_perm": row_perm,
        "c_pad": c_pad,
        "fusable": fusable,
    }


def empty_window_matrix():
    """4 windows at l=8; the 2nd and 4th windows hold no nonzeros."""
    rng = np.random.default_rng(7)
    dense = np.zeros((32, 40), np.float32)
    for r in list(range(0, 8)) + list(range(16, 24)):
        cols = rng.choice(40, 5, replace=False)
        dense[r, cols] = rng.standard_normal(5)
    return dense


EQUIV_CASES = [
    (16, 64, 8, 0.1),
    (64, 48, 16, 0.2),
    (100, 130, 32, 0.05),  # m % l != 0, n % l != 0
    (33, 7, 8, 0.5),  # n < l
    (57, 57, 16, 0.3),
]


@pytest.mark.parametrize("method", ["paper", "fast", "exact"])
@pytest.mark.parametrize("lb", [False, True])
@pytest.mark.parametrize("m,n,l,density", EQUIV_CASES)
def test_vectorized_pack_bit_identical(method, lb, m, n, l, density):
    rng = np.random.default_rng(m * 7919 + n)
    dense = random_dense(rng, m, n, density)
    sched = schedule(coo_from_dense(dense), l, load_balance=lb, method=method)
    ref = pack_loop_reference(sched)
    p = pack_schedule(sched)
    assert p.c_pad == ref["c_pad"] and p.fusable == ref["fusable"]
    assert np.array_equal(np.asarray(p.m_blk), ref["m_blk"])
    assert np.array_equal(np.asarray(p.col_blk), ref["col_blk"])
    assert np.array_equal(np.asarray(p.row_blk), ref["row_blk"])
    assert np.array_equal(np.asarray(p.row_perm), ref["row_perm"])


@pytest.mark.parametrize("lb", [False, True])
def test_vectorized_pack_empty_windows_and_empty_matrix(lb):
    for dense in (empty_window_matrix(), np.zeros((24, 16), np.float32)):
        sched = schedule(coo_from_dense(dense), 8, load_balance=lb)
        ref = pack_loop_reference(sched)
        p = pack_schedule(sched)
        for k in ("m_blk", "col_blk", "row_blk", "row_perm"):
            assert np.array_equal(np.asarray(getattr(p, k)), ref[k]), k
        assert p.c_pad == ref["c_pad"]


@pytest.mark.parametrize("value_dtype,index_dtype",
                         [(jnp.float32, jnp.int32), (jnp.bfloat16, jnp.int16)])
def test_vectorized_pack_dtype_variants(value_dtype, index_dtype):
    rng = np.random.default_rng(3)
    dense = random_dense(rng, 48, 64, 0.2)
    sched = schedule(coo_from_dense(dense), 16)
    ref = pack_loop_reference(sched, value_dtype=value_dtype,
                              index_dtype=index_dtype)
    p = pack_schedule(sched, value_dtype=value_dtype, index_dtype=index_dtype)
    assert p.m_blk.dtype == jnp.dtype(value_dtype)
    assert p.col_blk.dtype == jnp.dtype(index_dtype)
    assert np.array_equal(np.asarray(p.m_blk, np.float32),
                          ref["m_blk"].astype(np.float32))
    assert np.array_equal(np.asarray(p.col_blk), ref["col_blk"])


def test_window_ids_vectorized():
    rng = np.random.default_rng(5)
    for dense in (random_dense(rng, 50, 60, 0.1), empty_window_matrix(),
                  np.zeros((12, 12), np.float32)):
        sched = schedule(coo_from_dense(dense), 8, load_balance=False)
        wid_ref = np.zeros(max(sched.total_colors, 1), np.int32)
        ws = sched.window_starts
        for w in range(sched.num_windows):
            wid_ref[ws[w]: ws[w + 1]] = w
        assert np.array_equal(window_ids(sched), wid_ref)


# ---------------------------------------------------------------------------
# repad_to
# ---------------------------------------------------------------------------


def test_repad_to_invariants_and_numerics():
    rng = np.random.default_rng(11)
    dense = random_dense(rng, 40, 56, 0.25)
    x = rng.standard_normal((56, 3)).astype(np.float32)
    sched = schedule(coo_from_dense(dense), 8)
    p = pack_schedule(sched)
    g = p.repad_to(p.c_pad + 16)
    assert g.c_pad == p.c_pad + 16 and g.fusable == p.fusable
    # invariants in the new slots: values 0, cols == lane, rows 0
    W, l = g.num_windows, g.l
    m3 = np.asarray(g.m_blk).reshape(W, g.c_pad, l)
    c3 = np.asarray(g.col_blk).reshape(W, g.c_pad, l)
    r3 = np.asarray(g.row_blk).reshape(W, g.c_pad, l)
    assert np.all(m3[:, p.c_pad:] == 0.0)
    assert np.all(c3[:, p.c_pad:] == np.arange(l, dtype=np.int32))
    assert np.all(r3[:, p.c_pad:] == 0)
    # identical SpMM result, both execution paths
    for uk in (False, True):
        ya = np.asarray(gust_spmm(p, jnp.asarray(x), use_kernel=uk))
        yb = np.asarray(gust_spmm(g, jnp.asarray(x), use_kernel=uk))
        np.testing.assert_allclose(ya, yb, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(ya, dense @ x, rtol=1e-4, atol=1e-4)


def test_repad_to_preserves_compact_dtypes():
    """Regression: the old serving repad closure silently promoted the
    compact int16/bf16 stream to int32/float32 when layers had unequal
    C_pad; repad_to must keep leaf dtypes."""
    rng = np.random.default_rng(2)
    sched = schedule(coo_from_dense(random_dense(rng, 48, 64, 0.2)), 16)
    p = pack_schedule(sched, value_dtype=jnp.bfloat16, index_dtype=jnp.int16)
    g = p.repad_to(p.c_pad + 8)
    assert g.m_blk.dtype == jnp.bfloat16
    assert g.col_blk.dtype == jnp.int16 and g.row_blk.dtype == jnp.int16


def test_repad_to_noop_and_shrink_guard():
    rng = np.random.default_rng(4)
    sched = schedule(coo_from_dense(random_dense(rng, 16, 16, 0.3)), 8)
    p = pack_schedule(sched)
    assert p.repad_to(p.c_pad) is p
    with pytest.raises(ValueError):
        p.repad_to(p.c_pad - 1)


# ---------------------------------------------------------------------------
# leaves/meta codec
# ---------------------------------------------------------------------------


def test_codec_round_trip_and_spec_stacking():
    rng = np.random.default_rng(6)
    sched = schedule(coo_from_dense(random_dense(rng, 30, 44, 0.15)), 8)
    p = pack_schedule(sched)
    q = packed_from_leaves(packed_leaves(p), packed_meta(p))
    assert isinstance(q, PackedSchedule)
    assert packed_meta(q) == packed_meta(p)
    for k, v in packed_leaves(p).items():
        assert np.array_equal(np.asarray(getattr(q, k)), np.asarray(v))
    # spec prototypes stack with a leading reps axis, dtypes preserved
    proto = packed_spec(30, 44, 8, p.c_pad, value_dtype=jnp.bfloat16,
                        index_dtype=jnp.int16)
    stacked = stacked_leaf_specs(proto, reps=3)
    assert stacked["m_blk"].shape == (3, *proto.m_blk.shape)
    assert stacked["m_blk"].dtype == jnp.bfloat16
    assert stacked["col_blk"].dtype == jnp.int16
    assert stacked["row_perm"].dtype == jnp.int32


# ---------------------------------------------------------------------------
# ScheduleCache
# ---------------------------------------------------------------------------


def test_schedule_cache_content_keyed():
    rng = np.random.default_rng(9)
    dense = random_dense(rng, 32, 32, 0.2)
    cache = ScheduleCache()
    s1, p1 = schedule_packed(coo_from_dense(dense), 8, cache=cache)
    # same content, fresh COO objects -> cache hit, same objects back
    s2, p2 = schedule_packed(coo_from_dense(dense.copy()), 8, cache=cache)
    assert s1 is s2 and p1 is p2
    assert cache.hits >= 2  # schedule + packed
    # different packing dtype -> schedule reused, pack recomputed
    _, p3 = schedule_packed(coo_from_dense(dense), 8, cache=cache,
                            value_dtype=jnp.bfloat16, index_dtype=jnp.int16)
    assert p3 is not p1 and p3.m_blk.dtype == jnp.bfloat16
    # different content -> miss
    dense2 = dense.copy()
    dense2[0, 0] += 1.0
    s4, _ = schedule_packed(coo_from_dense(dense2), 8, cache=cache)
    assert s4 is not s1
    # different scheduling params -> miss
    s5, _ = schedule_packed(coo_from_dense(dense), 8, cache=cache,
                            load_balance=False)
    assert s5 is not s1


def test_schedule_cache_eviction_and_bypass():
    rng = np.random.default_rng(10)
    cache = ScheduleCache(maxsize=2)
    mats = [random_dense(rng, 16, 16, 0.3) for _ in range(3)]
    for d in mats:
        cache.schedule(coo_from_dense(d), 8)
    assert len(cache._store) <= 2  # oldest evicted
    # cache=None bypasses entirely
    d = mats[0]
    sa, pa = schedule_packed(coo_from_dense(d), 8, cache=None)
    sb, pb = schedule_packed(coo_from_dense(d), 8, cache=None)
    assert sa is not sb
    assert np.array_equal(np.asarray(pa.m_blk), np.asarray(pb.m_blk))


# ---------------------------------------------------------------------------
# src_blk: the crossbar's source-lane table
# ---------------------------------------------------------------------------


def assert_source_lanes(art):
    """``src_blk`` inverts the routing over the nonzero slots: where it
    names a lane, that lane holds a nonzero value routed to that adder,
    and every nonzero lane is named by its adder (so no two nonzero lanes
    of a cycle share an adder); rows with no nonzero value are all -1."""
    m = np.asarray(art.m_blk).astype(np.float32)
    row = np.asarray(art.row_blk, np.int64)
    src = np.asarray(art.src_blk, np.int64)
    assert src.shape == row.shape and art.src_blk.dtype == art.row_blk.dtype
    r, a = np.nonzero(src >= 0)
    assert np.array_equal(row[r, src[r, a]], a)
    assert np.all(m[r, src[r, a]] != 0)
    rn, jn = np.nonzero(m != 0)
    assert np.array_equal(src[rn, row[rn, jn]], jn)
    assert int((src >= 0).sum()) == rn.size
    empty = ~(m != 0).any(axis=1)
    assert np.all(src[empty] == -1)
    return empty


def _src_case_artifacts(layout, transform):
    """(artifact, mask of the rows the transform grew, or None) for one
    packing path."""
    from repro.core.packing import (
        pack_ragged,
        ragged_from_leaves,
        ragged_leaves,
        ragged_meta,
        splice_ragged_blocks,
    )
    from repro.core.plan import GustPlan

    rng = np.random.default_rng(21)
    dense = random_dense(rng, 40, 56, 0.25)
    dense[3, 5] = 0.0
    sched = schedule(coo_from_dense(dense), 8, load_balance=False)
    pack = pack_ragged if layout == "ragged" else pack_schedule
    kw = {"int8": dict(value_dtype=jnp.int8, index_dtype=jnp.int16),
          "compact": dict(value_dtype=jnp.bfloat16, index_dtype=jnp.int16),
          }.get(transform, {})
    p = pack(sched, **kw)
    if transform == "repad":
        if layout == "ragged":
            p = p.repad_to_blocks(p.num_blocks + 3)
            grown = np.arange(p.num_blocks * 8) >= (p.num_blocks - 3) * 8
        else:
            old = p.c_pad
            p = p.repad_to(old + 16)
            grown = np.arange(p.num_windows * p.c_pad) % p.c_pad >= old
        return p, grown
    if transform == "stack":
        other = pack(schedule(coo_from_dense(random_dense(rng, 40, 56, 0.6)),
                              8, load_balance=False))
        st = GustPlan.stack([p, other])
        p = GustPlan.from_spec({
            "leaves": {k: v[0] for k, v in st["leaves"].items()},
            "meta": st["meta"]}).artifact
    elif transform in ("codec", "spec"):
        spec = GustPlan.from_artifact(p).to_spec()
        if transform == "spec":
            p = GustPlan.from_spec(spec).artifact
        elif layout == "ragged":
            p = ragged_from_leaves(ragged_leaves(p), ragged_meta(p))
        else:
            p = packed_from_leaves(packed_leaves(p), packed_meta(p))
    elif transform == "splice":
        dense2 = dense.copy()
        dense2[8:16][dense2[8:16] != 0] *= 2.0
        dense2[9, 1] = 1.5
        sched2 = schedule(coo_from_dense(dense2), 8, load_balance=False)
        p = splice_ragged_blocks(p, sched2, [1])
        fresh = pack_ragged(sched2)
        assert np.array_equal(np.asarray(p.src_blk),
                              np.asarray(fresh.src_blk))
    return p, None


SRC_CASES = [
    (layout, transform)
    for layout in ("padded", "ragged")
    for transform in ("pack", "int8", "compact", "repad", "stack", "codec",
                      "spec")
] + [("ragged", "splice")]


@pytest.mark.parametrize("layout,transform", SRC_CASES)
def test_source_lane_table_invariants(layout, transform):
    """``src_blk`` holds its invariants as packed, quantized, compact,
    grown by ``repad_to``/``repad_to_blocks`` (the new rows all -1),
    stacked with a heavier layer, through the leaves/meta codec and
    ``to_spec``/``from_spec``, and spliced (equal to a fresh pack)."""
    art, grown = _src_case_artifacts(layout, transform)
    empty = assert_source_lanes(art)
    if grown is not None:
        assert grown.any() and np.all(empty[grown])
        assert np.all(np.asarray(art.src_blk)[grown] == -1)


@pytest.mark.parametrize("layout", ["padded", "ragged"])
def test_artifact_without_source_lanes_derives_them(tmp_path, layout):
    """Leaves written before the ``src_blk`` leaf existed (here, a plan
    store entry stripped of it) load, derive the table, and run the
    kernel bit-identically to a fresh plan."""
    from repro.core.plan import PlanConfig, plan
    from repro.core.plan_store import PlanStore

    rng = np.random.default_rng(23)
    dense = random_dense(rng, 40, 56, 0.25)
    cfg = PlanConfig(l=8, layout=layout, backend="pallas")
    fresh = plan(dense, cfg, cache=None)
    store = PlanStore(str(tmp_path))
    spec = fresh.to_spec()
    spec["leaves"] = {k: v for k, v in spec["leaves"].items()
                      if k != "src_blk"}
    store.put(store.key(ScheduleCache.matrix_key(coo_from_dense(dense)), cfg),
              spec)
    loaded = plan(dense, cfg, cache=None, store=store)
    assert store.hits == 1 and loaded.sched is None
    assert_source_lanes(loaded.artifact)
    assert np.array_equal(np.asarray(loaded.artifact.src_blk),
                          np.asarray(fresh.artifact.src_blk))
    x = jnp.asarray(rng.standard_normal((56, 2)).astype(np.float32))
    assert np.array_equal(np.asarray(loaded.spmm(x)),
                          np.asarray(fresh.spmm(x)))
