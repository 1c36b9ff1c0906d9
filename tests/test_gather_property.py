"""Segment-local Buffer-Filler gather: equivalence + format invariants
(ISSUE 5).

The segment-local execution path (pack-time ``seg_blk`` table +
block-local ``col_loc`` columns, streamed x tiles in the kernels) must be
**bit-identical** to the resident path on both layouts — kernel vs kernel
and oracle vs oracle — and the new leaves must survive every packed-
format transformation (``repad_to`` / ``repad_to_blocks``, the
leaves/meta codec, serving stacking) with the bf16/int16 dtype rules
intact.  The hypothesis property test sweeps random and power-law
matrices; the deterministic tests pin the table contract, the
``identity_perm`` scatter-skip, the ``gather="auto"`` decision point and
the new :class:`PlanCost` fields.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.formats import coo_from_dense
from repro.core.packing import (
    PackedSchedule,
    RaggedSchedule,
    pack_ragged,
    pack_schedule,
    packed_from_leaves,
    packed_leaves,
    packed_meta,
    ragged_from_leaves,
    ragged_leaves,
    ragged_meta,
    resolve_gather,
)
from repro.core.plan import GustPlan, PlanConfig, plan
from repro.core.scheduler import schedule
from repro.kernels.ops import execute_spmm

from test_ragged import power_law_dense, random_dense


def both_gathers(art, x, use_kernel):
    """(resident, local) outputs of one artifact through the executor."""
    yr = np.asarray(
        execute_spmm(art, x, use_kernel=use_kernel, gather="resident")
    )
    yl = np.asarray(
        execute_spmm(art, x, use_kernel=use_kernel, gather="local")
    )
    return yr, yl


def assert_local_matches_resident(sched, x, dense_ref):
    xs = jnp.asarray(x)
    for art in (pack_schedule(sched), pack_ragged(sched)):
        for uk in (False, True):
            yr, yl = both_gathers(art, xs, uk)
            tag = (type(art).__name__, "kernel" if uk else "oracle")
            assert np.array_equal(yr, yl), \
                f"local gather diverged from resident: {tag}"
            np.testing.assert_allclose(
                yr, dense_ref, rtol=2e-4, atol=2e-4, err_msg=str(tag)
            )


# ---------------------------------------------------------------------------
# table contract
# ---------------------------------------------------------------------------


def _assert_table_contract(art):
    """seg_blk/col_loc describe exactly the original columns."""
    l, c_blk = art.l, art.c_blk
    col = np.asarray(art.col_blk, np.int64)
    loc = np.asarray(art.col_loc, np.int64)
    tab = np.asarray(art.seg_blk, np.int64)
    assert tab.shape == (col.shape[0] // c_blk, art.s_blk)
    blk = np.repeat(np.arange(tab.shape[0]), c_blk)
    # the table maps every local id back to the slot's global segment,
    # the lane offset is preserved, and local ids are in range
    assert np.all(tab[blk[:, None], loc // l] == col // l)
    assert np.all(loc % l == col % l)
    assert np.all((loc // l >= 0) & (loc // l < art.s_blk))
    # per-block table rows are sorted with 0-padding past the distinct set
    assert np.all(np.diff(np.sort(tab, axis=1), axis=1) >= 0)
    # every table entry is a valid segment id (padding uses segment 0)
    assert np.all((tab >= 0) & (tab < max(art.seg_count, 1)))


@pytest.mark.parametrize("lb", [False, True])
def test_segment_table_contract_both_layouts(lb):
    rng = np.random.default_rng(0)
    dense = power_law_dense(rng, 64, 96)
    sched = schedule(coo_from_dense(dense), 8, load_balance=lb)
    for art in (pack_schedule(sched), pack_ragged(sched)):
        _assert_table_contract(art)
        # identity_perm is exact: it equals the actual permutation check
        assert art.identity_perm == bool(
            np.array_equal(
                np.asarray(art.row_perm),
                np.arange(art.num_windows * art.l),
            )
        )


def test_local_tables_survive_repads():
    rng = np.random.default_rng(1)
    dense = random_dense(rng, 40, 56, 0.25)
    x = jnp.asarray(rng.standard_normal((56, 3)).astype(np.float32))
    sched = schedule(coo_from_dense(dense), 8)
    p = pack_schedule(sched)
    r = pack_ragged(sched)
    gp = p.repad_to(p.c_pad + 16)
    gr = r.repad_to_blocks(r.num_blocks + 4)
    for g in (gp, gr):
        _assert_table_contract(g)
        assert g.s_blk >= 1
    # repadded artifacts still execute bit-identically in both modes
    for art in (gp, gr):
        for uk in (False, True):
            yr, yl = both_gathers(art, x, uk)
            assert np.array_equal(yr, yl)
    # seg-table widening is repad-safe and refuses to shrink
    wide = p.repad_seg_to(p.s_blk + 3)
    assert wide.s_blk == p.s_blk + 3
    _assert_table_contract(wide)
    yr, yl = both_gathers(wide, x, True)
    assert np.array_equal(yr, yl)
    with pytest.raises(ValueError):
        wide.repad_seg_to(p.s_blk)
    assert p.repad_seg_to(p.s_blk) is p


def test_compact_dtypes_through_repads_and_codec():
    """bf16 values / int16 indices survive the new leaves' lifecycle:
    pack -> repad -> codec round-trip, on both layouts."""
    rng = np.random.default_rng(2)
    sched = schedule(coo_from_dense(random_dense(rng, 48, 64, 0.2)), 16)
    x = jnp.asarray(rng.standard_normal((64, 2)).astype(np.float32))
    p = pack_schedule(sched, value_dtype=jnp.bfloat16, index_dtype=jnp.int16)
    r = pack_ragged(sched, value_dtype=jnp.bfloat16, index_dtype=jnp.int16)
    for art, grow in ((p, lambda a: a.repad_to(a.c_pad + 8)),
                      (r, lambda a: a.repad_to_blocks(a.num_blocks + 2))):
        assert art.col_loc.dtype == jnp.int16
        assert art.seg_blk.dtype == jnp.int32  # table is always int32
        g = grow(art)
        assert g.col_loc.dtype == jnp.int16 and g.seg_blk.dtype == jnp.int32
        if isinstance(art, RaggedSchedule):
            q = ragged_from_leaves(ragged_leaves(g), ragged_meta(g))
        else:
            q = packed_from_leaves(packed_leaves(g), packed_meta(g))
        assert q.col_loc.dtype == jnp.int16 and q.s_blk == g.s_blk
        assert q.identity_perm == g.identity_perm
        for uk in (False, True):
            yr, yl = both_gathers(q, x, uk)
            assert np.array_equal(yr, yl)


# ---------------------------------------------------------------------------
# hypothesis property: local == resident, bitwise, everywhere
# ---------------------------------------------------------------------------


try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

def _property_body(args):
    m, n, density, l, b, skew, lb, compact, seed = args
    rng = np.random.default_rng(seed)
    dense = (
        power_law_dense(rng, m, n, base_density=density * 0.2)
        if skew
        else random_dense(rng, m, n, density)
    )
    x = jnp.asarray(rng.standard_normal((n, b)).astype(np.float32))
    sched = schedule(coo_from_dense(dense), l, load_balance=lb)
    vd, idd = (jnp.bfloat16, jnp.int16) if compact else (jnp.float32,
                                                         jnp.int32)
    for art in (
        pack_schedule(sched, value_dtype=vd, index_dtype=idd),
        pack_ragged(sched, value_dtype=vd, index_dtype=idd),
    ):
        _assert_table_contract(art)
        for uk in (False, True):
            yr, yl = both_gathers(art, x, uk)
            assert np.array_equal(yr, yl), (
                type(art).__name__, uk, m, n, l, lb, compact
            )


if HAVE_HYPOTHESIS:
    matrix_strategy = st.tuples(
        st.integers(2, 48),  # m
        st.integers(2, 64),  # n
        st.sampled_from([0.05, 0.2, 0.5]),
        st.sampled_from([4, 8, 16]),  # l
        st.integers(1, 4),  # B
        st.booleans(),  # power-law skew
        st.booleans(),  # load balance
        st.booleans(),  # compact dtypes
        st.integers(0, 10_000),  # seed
    )

    @settings(max_examples=25, deadline=None)
    @given(args=matrix_strategy)
    def test_local_gather_equivalence_property(args):
        _property_body(args)

else:  # keep a deterministic slice of the sweep without hypothesis

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_local_gather_equivalence_property(seed):
        rng = np.random.default_rng(seed)
        args = (
            int(rng.integers(2, 48)), int(rng.integers(2, 64)),
            [0.05, 0.2, 0.5][seed % 3], [4, 8, 16][seed % 3],
            1 + seed % 4, bool(seed % 2), bool((seed // 2) % 2),
            bool(seed % 2), seed,
        )
        _property_body(args)


# ---------------------------------------------------------------------------
# identity_perm scatter skip
# ---------------------------------------------------------------------------


def test_identity_perm_skips_scatter_bit_identically():
    rng = np.random.default_rng(3)
    dense = random_dense(rng, 48, 64, 0.2)
    x = jnp.asarray(rng.standard_normal((64, 3)).astype(np.float32))
    sched = schedule(coo_from_dense(dense), 8, load_balance=False)
    p = pack_schedule(sched)
    assert p.identity_perm, "load_balance=False pack must flag identity"
    # force the scatter path by clearing the flag; outputs must agree
    import dataclasses as dc

    forced = dc.replace(p, identity_perm=False)
    for uk in (False, True):
        y_fast = np.asarray(execute_spmm(p, x, use_kernel=uk))
        y_scatter = np.asarray(execute_spmm(forced, x, use_kernel=uk))
        assert np.array_equal(y_fast, y_scatter)
    np.testing.assert_allclose(
        np.asarray(execute_spmm(p, x)), dense @ np.asarray(x),
        rtol=2e-4, atol=2e-4,
    )


# ---------------------------------------------------------------------------
# plan surface: gather knob, auto decision, cost fields
# ---------------------------------------------------------------------------


def test_plan_gather_knob_and_auto_decision():
    with pytest.raises(ValueError):
        PlanConfig(gather="vmem")
    rng = np.random.default_rng(4)
    dense = random_dense(rng, 64, 256, 0.05)  # wide: few segs per block
    x = jnp.asarray(rng.standard_normal((256, 2)).astype(np.float32))
    outs = {}
    for mode in ("resident", "local", "auto"):
        p = plan(dense, PlanConfig(l=8, backend="jnp", gather=mode),
                 cache=None)
        outs[mode] = np.asarray(p.spmm(x))
        assert p.gather_mode in ("resident", "local")
    assert np.array_equal(outs["resident"], outs["local"])
    assert np.array_equal(outs["auto"], outs["local"])
    # the auto decision is the one resolve_gather decision point
    p = plan(dense, PlanConfig(l=8), cache=None)
    a = p.artifact
    assert p.gather_mode == resolve_gather(a.s_blk, a.seg_count)


def test_plan_cost_gather_fields():
    rng = np.random.default_rng(5)
    dense = random_dense(rng, 64, 512, 0.03)
    p = plan(dense, PlanConfig(l=8), cache=None)
    c = p.cost()
    a = p.artifact
    assert c.s_blk == a.s_blk
    assert c.locality_ratio == pytest.approx(a.s_blk / a.seg_count)
    # the FLOP ratio between the modes is exactly seg_count / S_blk
    assert c.gather_flops_resident == 4 * c.streamed_slots * a.seg_count
    assert c.gather_flops_local == 4 * c.streamed_slots * a.s_blk
    assert c.gather_flops_resident / c.gather_flops_local == pytest.approx(
        a.seg_count / a.s_blk
    )
    # resident x VMEM scales with matrix width, local with the working set
    assert c.x_vmem_bytes_resident == a.seg_count * p.l * 4
    assert c.x_vmem_bytes_local == a.s_blk * p.l * 4
    assert c.gather in ("resident", "local")
    assert c.to_dict()["s_blk"] == a.s_blk


@pytest.mark.parametrize("n", [8, 56, 64, 72, 184])
def test_plan_cost_gather_walk_steps(n):
    """The resident walk takes one step per group of eight column
    segments: 1, 7, 8, 9 and 23 segments walk in 1, 1, 1, 2 and 3."""
    rng = np.random.default_rng(n)
    p = plan(random_dense(rng, 32, n, 0.2), PlanConfig(l=8), cache=None)
    seg_count = p.artifact.seg_count
    assert seg_count == n // 8
    assert p.cost().gather_walk_steps == -(-seg_count // 8)
    assert p.cost().to_dict()["gather_walk_steps"] == -(-seg_count // 8)


def test_stack_equalizes_seg_tables_and_flags():
    """Layers with different S_blk / identity_perm must stack: tables are
    widened to the max and the shared static flags are conservative."""
    rng = np.random.default_rng(6)
    plans = [
        plan(random_dense(rng, 32, 128, d), PlanConfig(l=8, layout="padded",
                                                       backend="jnp"),
             cache=None)
        for d in (0.02, 0.4)
    ]
    arts = [p.artifact for p in plans]
    assert arts[0].s_blk != arts[1].s_blk, "fixture should differ in S_blk"
    stacked = GustPlan.stack(plans)
    s_uniform = max(a.s_blk for a in arts)
    assert stacked["leaves"]["seg_blk"].shape[-1] == s_uniform
    meta_s_blk = stacked["meta"][6]
    assert meta_s_blk == s_uniform
    # each layer's slice still executes both gather modes bit-identically
    for i, p in enumerate(plans):
        sl = {k: v[i] for k, v in stacked["leaves"].items()}
        q = GustPlan.from_spec({"leaves": sl, "meta": stacked["meta"]})
        x = jnp.asarray(rng.standard_normal((128, 2)).astype(np.float32))
        yr, yl = both_gathers(q.artifact, x, False)
        assert np.array_equal(yr, yl)
        np.testing.assert_allclose(
            np.asarray(q.spmm(x)), np.asarray(p.spmm(x)),
            rtol=1e-5, atol=1e-5,
        )


def test_wide_matrix_executes_via_local_gather():
    """A width whose resident x footprint exceeds a (scaled-down) VMEM
    budget executes through gather='local' — the end-to-end wide-matrix
    fast path.  The real 16 MB budget is exercised by
    benchmarks/gather_bench.py; here the same inequality is asserted at
    test scale."""
    rng = np.random.default_rng(7)
    m, n, l, b = 32, 4096, 8, 4
    dense = random_dense(rng, m, n, 0.01)
    x = jnp.asarray(rng.standard_normal((n, b)).astype(np.float32))
    p = plan(dense, PlanConfig(l=l, backend="pallas", gather="local"),
             cache=None)
    c = p.cost()
    budget = c.x_vmem_bytes_resident - 1  # resident would not fit
    assert c.x_vmem_bytes_local < budget < c.x_vmem_bytes_resident
    assert p.gather_mode == "local"
    y = np.asarray(p.spmm(x))
    np.testing.assert_allclose(y, dense @ np.asarray(x), rtol=2e-4,
                               atol=2e-4)


def test_resolve_gather_decision_point():
    assert resolve_gather(4, 256) == "local"
    assert resolve_gather(128, 256) == "local"  # ratio 0.5 inclusive
    assert resolve_gather(129, 256) == "resident"
    assert resolve_gather(1, 1) == "resident"
    assert resolve_gather(65, 256, locality_ratio=0.25) == "resident"
    assert resolve_gather(64, 256, locality_ratio=0.25) == "local"
    # below the width floor the resident contraction is cheap enough that
    # tile-streaming grid-step overhead dominates — auto stays resident
    assert resolve_gather(2, 8) == "resident"
    assert resolve_gather(2, 8, min_segs=8) == "local"
    assert resolve_gather(2, 8, min_segs=9) == "resident"
