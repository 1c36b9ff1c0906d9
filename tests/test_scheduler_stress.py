"""Scheduler stress suite (ISSUE 7): adversarial structures + bit-identity.

Validity / completeness / Eq. 1 for all three colorers on the structures
the satellite list calls out — empty windows, single-lane hot columns,
duplicate-heavy degree skew — plus the PR's three bit-identity contracts:

  * parallel window-chunked coloring == serial ``color_edges_fast``;
  * the O(e) ``color_edges_fast`` rewrite == the pre-PR np.unique
    reference (``_color_edges_fast_reference``);
  * ``incremental_schedule`` == a fresh ``schedule`` on the new matrix;
  * the ``color_edges_paper`` done-mask fix == the old sorted-dict loop.

With hypothesis installed the sweeps are property tests; without it a
seeded deterministic slice runs the same bodies (same policy as
``test_quant_property.py`` — CI images may lack hypothesis).
"""

import os

import numpy as np
import pytest

from repro.core.bounds import eq1_colors
from repro.core.formats import COOMatrix, coo_from_dense
from repro.core.scheduler import (
    _build_edges,
    _color_edges_fast_reference,
    _edge_index_dtype,
    color_edges_exact,
    color_edges_fast,
    color_edges_paper,
    color_windows_chunked,
    incremental_schedule,
    reset_sched_counters,
    sched_counters,
    schedule,
    window_fingerprints,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Adversarial matrix generators
# ---------------------------------------------------------------------------


def empty_window_dense(rng, m, n, l, density=0.2):
    """Random matrix with entire row bands (windows) zeroed out — the
    scheduler must keep zero-color windows and exact window offsets."""
    dense = ((rng.random((m, n)) < density)
             * rng.standard_normal((m, n))).astype(np.float32)
    num_windows = -(-m // l)
    kill = rng.random(num_windows) < 0.5
    kill[rng.integers(num_windows)] = True  # at least one empty window
    for w in np.nonzero(kill)[0]:
        dense[w * l: (w + 1) * l] = 0.0
    return dense


def hot_column_dense(rng, m, n, l, density=0.05):
    """One nearly-full column: every window funnels through a single lane,
    so per-window colors must reach that lane's degree (Eq. 1 tight on
    the lane side)."""
    dense = ((rng.random((m, n)) < density)
             * rng.standard_normal((m, n))).astype(np.float32)
    hot = int(rng.integers(n))
    dense[:, hot] = rng.standard_normal(m).astype(np.float32)
    dense[dense[:, hot] == 0.0, hot] = 1.0
    return dense


def duplicate_heavy_dense(rng, m, n, l, density=0.3):
    """Power-law row degrees with columns congruent mod l: many edges per
    (row, lane) pair — the multigraph case where per-vertex degree far
    exceeds the number of distinct neighbors."""
    dense = np.zeros((m, n), np.float32)
    lanes = rng.integers(0, l, size=max(1, l // 2))
    for i in range(m):
        deg = min(n, int(rng.pareto(1.0) * 3) + 1)
        cols = (rng.integers(0, max(1, n // l), size=deg) * l
                + rng.choice(lanes, size=deg)) % n
        dense[i, np.unique(cols)] = rng.standard_normal(
            np.unique(cols).size
        ).astype(np.float32)
    return dense


STRUCTURES = {
    "empty_windows": empty_window_dense,
    "hot_column": hot_column_dense,
    "duplicate_heavy": duplicate_heavy_dense,
}


# ---------------------------------------------------------------------------
# Invariant checkers
# ---------------------------------------------------------------------------


def assert_schedule_invariants(sched, coo, l):
    """Completeness, validity, Eq. 1 — the three contracts every colorer
    must satisfy on every structure."""
    # completeness: every nonzero exactly once, values preserved
    assert int(sched.valid.sum()) == coo.nnz
    np.testing.assert_allclose(
        np.sort(sched.m_sch[sched.valid]), np.sort(coo.vals)
    )
    cyc, lane = np.nonzero(sched.valid)
    # validity: within a cycle no adder receives two partial products
    adders = sched.row_sch[cyc, lane]
    keys = cyc.astype(np.int64) * l + adders
    assert np.unique(keys).size == keys.size, "adder collision"
    # Eq. 1 per window (empty windows must contribute exactly 0 colors)
    wid = np.searchsorted(
        sched.window_starts, np.arange(sched.valid.shape[0]), side="right"
    ) - 1
    wid = wid[cyc]
    for w in range(sched.num_windows):
        sel = wid == w
        used = int(sched.window_starts[w + 1] - sched.window_starts[w])
        if not sel.any():
            assert used == 0, "empty window must occupy zero cycles"
            continue
        row_nnz = np.bincount(adders[sel], minlength=l)
        lane_nnz = np.bincount(lane[sel], minlength=l)
        assert used >= eq1_colors(row_nnz, lane_nnz)


def assert_schedules_bitwise_equal(a, b):
    assert a.l == b.l and a.shape == b.shape and a.nnz == b.nnz
    for f in ("m_sch", "row_sch", "col_sch", "window_starts", "row_perm",
              "valid"):
        fa, fb = getattr(a, f), getattr(b, f)
        assert fa.dtype == fb.dtype, f
        assert np.array_equal(fa, fb), f


# ---------------------------------------------------------------------------
# Adversarial structures x all colorers
# ---------------------------------------------------------------------------


def _adversarial_body(structure, method, seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(8, 48))
    n = int(rng.integers(8, 64))
    l = int(rng.choice([4, 8]))
    dense = STRUCTURES[structure](rng, m, n, l)
    coo = coo_from_dense(dense)
    for lb in (False, True):
        sched = schedule(coo, l, load_balance=lb, method=method)
        assert_schedule_invariants(sched, coo, l)


if HAVE_HYPOTHESIS:

    @pytest.mark.parametrize("method", ["paper", "fast", "exact"])
    @pytest.mark.parametrize("structure", sorted(STRUCTURES))
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_adversarial_structures(structure, method, seed):
        _adversarial_body(structure, method, seed)

else:

    @pytest.mark.parametrize("method", ["paper", "fast", "exact"])
    @pytest.mark.parametrize("structure", sorted(STRUCTURES))
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_adversarial_structures(structure, method, seed):
        _adversarial_body(structure, method, seed)


# ---------------------------------------------------------------------------
# Bit-identity: O(e) fast rewrite vs np.unique reference
# ---------------------------------------------------------------------------


def _edges_for(dense, l, lb=False):
    coo = coo_from_dense(dense)
    win, row_local, lane, _, _, _ = _build_edges(coo, l, lb)
    num_windows = max(-(-dense.shape[0] // l), 1)
    return (win * l + row_local, win * l + lane, win, num_windows)


def _fast_rewrite_body(structure, seed):
    rng = np.random.default_rng(seed)
    dense = STRUCTURES[structure](rng, int(rng.integers(8, 64)),
                                  int(rng.integers(8, 80)), 8)
    row_key, lane_key, _, _ = _edges_for(dense, 8)
    got = color_edges_fast(row_key, lane_key)
    want = _color_edges_fast_reference(row_key, lane_key)
    assert np.array_equal(got, want), "O(e) rewrite diverged from reference"


if HAVE_HYPOTHESIS:

    @pytest.mark.parametrize("structure", sorted(STRUCTURES))
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_fast_rewrite_bit_identical(structure, seed):
        _fast_rewrite_body(structure, seed)

else:

    @pytest.mark.parametrize("structure", sorted(STRUCTURES))
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_fast_rewrite_bit_identical(structure, seed):
        _fast_rewrite_body(structure, seed)


# ---------------------------------------------------------------------------
# Bit-identity: parallel window-chunked coloring vs serial
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workers", [2, 3, 5])
def test_parallel_chunked_bit_identical(workers):
    rng = np.random.default_rng(workers)
    dense = empty_window_dense(rng, 96, 64, 8, density=0.15)
    row_key, lane_key, win, num_windows = _edges_for(dense, 8)
    want = color_edges_fast(row_key, lane_key)
    got = color_windows_chunked(
        row_key, lane_key, win, num_windows, 8, workers=workers
    )
    assert np.array_equal(got, want)


@pytest.mark.parametrize("lb", [False, True])
def test_parallel_schedule_bit_identical(lb):
    rng = np.random.default_rng(7)
    coo = coo_from_dense(duplicate_heavy_dense(rng, 80, 48, 8))
    serial = schedule(coo, 8, load_balance=lb, workers=1)
    par = schedule(coo, 8, load_balance=lb, workers=3)
    assert_schedules_bitwise_equal(serial, par)


def test_parallel_falls_back_serial_below_threshold():
    """workers=None (auto) stays serial under DEFAULT_PARALLEL_MIN_EDGES —
    the counter proves no worker pool span up for a tiny matrix."""
    rng = np.random.default_rng(0)
    coo = coo_from_dense(hot_column_dense(rng, 32, 32, 8))
    reset_sched_counters()
    schedule(coo, 8, load_balance=False)  # workers=None: auto threshold
    assert sched_counters["parallel_chunks"] == 0


def test_parallel_coloring_after_jax_initialized():
    """A process whose JAX backend is up (a server holding the chip, its
    runtime multithreaded) still colors in parallel above the auto
    threshold: the workers start from a forkserver instead of forking this
    process, return within the time limit, and match serial bitwise."""
    import threading

    import jax.numpy as jnp

    from repro.core.scheduler import DEFAULT_PARALLEL_MIN_EDGES

    jnp.zeros(8).block_until_ready()  # initialize the backend
    rng = np.random.default_rng(11)
    m, n, l = 16384, 4096, 256
    flat = np.unique(rng.integers(0, m * n, 2_200_000))
    coo = COOMatrix((m, n), flat // n, flat % n,
                    np.ones(flat.shape[0], np.float32))
    win, row_local, lane, _, _, _ = _build_edges(coo, l, False)
    row_key, lane_key, num_windows = win * l + row_local, win * l + lane, m // l
    assert row_key.shape[0] > DEFAULT_PARALLEL_MIN_EDGES
    want = color_edges_fast(row_key, lane_key)

    reset_sched_counters()
    out = {}
    worker = threading.Thread(
        target=lambda: out.update(got=color_windows_chunked(
            row_key, lane_key, win, num_windows, l, workers=2)),
        daemon=True,
    )
    worker.start()
    worker.join(timeout=240)
    assert not worker.is_alive(), "parallel coloring hung"
    assert sched_counters["parallel_chunks"] >= 2  # the pool really ran
    assert np.array_equal(out["got"], want)


def test_stop_workers_ends_helper_processes():
    """After a parallel coloring the forkserver and the resource tracker
    are running; ``stop_workers`` ends both (and reaps them), is harmless
    when repeated, and a later parallel coloring starts them again."""
    from multiprocessing import forkserver, resource_tracker

    from repro.core.scheduler import stop_workers

    rng = np.random.default_rng(3)
    row_key, lane_key, win, num_windows = _edges_for(
        empty_window_dense(rng, 96, 64, 8, density=0.15), 8)
    want = color_edges_fast(row_key, lane_key)
    for _ in range(2):
        reset_sched_counters()
        got = color_windows_chunked(row_key, lane_key, win, num_windows, 8,
                                    workers=2)
        assert sched_counters["parallel_chunks"] >= 2
        assert np.array_equal(got, want)
        pids = [forkserver._forkserver._forkserver_pid,
                resource_tracker._resource_tracker._pid]
        assert all(pids)
        stop_workers()
        stop_workers()
        for pid in pids:
            with pytest.raises(ProcessLookupError):
                os.kill(pid, 0)


# ---------------------------------------------------------------------------
# Bit-identity: paper colorer done-mask fix vs the old sorted-dict loop
# ---------------------------------------------------------------------------


def _paper_colorer_old(row_key, lane_key):
    """Pre-PR-7 ``color_edges_paper``: per color round, ``sorted()`` over a
    dict of remaining rows (the O(rows log rows) hotspot this PR removed).
    Kept inline here as the semantics oracle."""
    e = row_key.shape[0]
    colors = np.full(e, -1, dtype=np.int64)
    row_edges = {}
    for idx in range(e):
        row_edges.setdefault(int(row_key[idx]), []).append(idx)
    clr = 0
    while row_edges:
        matching = set()
        for rk in sorted(row_edges):
            edges = row_edges[rk]
            for pos, eidx in enumerate(edges):
                lk = int(lane_key[eidx])
                if lk not in matching:
                    colors[eidx] = clr
                    matching.add(lk)
                    edges.pop(pos)
                    break
            if not edges:
                del row_edges[rk]
        clr += 1
    return colors


def _paper_fix_body(structure, seed):
    rng = np.random.default_rng(seed)
    dense = STRUCTURES[structure](rng, int(rng.integers(8, 40)),
                                  int(rng.integers(8, 48)), 4)
    row_key, lane_key, _, _ = _edges_for(dense, 4)
    got = color_edges_paper(row_key, lane_key)
    want = _paper_colorer_old(row_key, lane_key)
    assert np.array_equal(got, want), "paper fix changed Listing 1 semantics"


if HAVE_HYPOTHESIS:

    @pytest.mark.parametrize("structure", sorted(STRUCTURES))
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_paper_fix_bit_identical(structure, seed):
        _paper_fix_body(structure, seed)

else:

    @pytest.mark.parametrize("structure", sorted(STRUCTURES))
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_paper_fix_bit_identical(structure, seed):
        _paper_fix_body(structure, seed)


# ---------------------------------------------------------------------------
# Bit-identity: incremental reschedule vs fresh schedule
# ---------------------------------------------------------------------------


def _mutate_windows(rng, dense, l, n_windows):
    """Perturb values + structure inside ``n_windows`` random windows."""
    new = dense.copy()
    num_windows = -(-dense.shape[0] // l)
    dirty = rng.choice(num_windows, size=min(n_windows, num_windows),
                       replace=False)
    for w in dirty:
        rows = slice(w * l, min((w + 1) * l, dense.shape[0]))
        band = new[rows]
        nz = np.nonzero(band)
        if nz[0].size:  # value-only change on half, structural on half
            k = nz[0].size // 2
            band[nz[0][:k], nz[1][:k]] *= 1.5
            band[nz[0][k:], nz[1][k:]] = 0.0
        band[rng.integers(band.shape[0]), rng.integers(band.shape[1])] = 3.25
        new[rows] = band
    return new, np.sort(dirty)


def _incremental_body(method, seed):
    rng = np.random.default_rng(seed)
    dense = duplicate_heavy_dense(rng, 64, 48, 8)
    coo = coo_from_dense(dense)
    old = schedule(coo, 8, load_balance=False, method=method)
    new_dense, expected_dirty = _mutate_windows(rng, dense, 8, 3)
    new_coo = coo_from_dense(new_dense)

    reset_sched_counters()
    inc, dirty, new_hashes = incremental_schedule(
        old, new_coo, old_coo=coo, method=method
    )
    fresh = schedule(new_coo, 8, load_balance=False, method=method)
    assert_schedules_bitwise_equal(inc, fresh)
    # only windows whose content actually changed are recolored
    assert set(dirty) <= set(expected_dirty)
    assert sched_counters["windows_recolored"] == dirty.size
    assert sched_counters["windows_reused"] == old.num_windows - dirty.size
    # chained delta: reuse new_hashes, no old_coo rehash needed
    third, d3 = _mutate_windows(rng, new_dense, 8, 1)
    inc2, dirty2, _ = incremental_schedule(
        inc, coo_from_dense(third), old_hashes=new_hashes, method=method
    )
    assert_schedules_bitwise_equal(inc2, schedule(
        coo_from_dense(third), 8, load_balance=False, method=method
    ))
    assert set(dirty2) <= set(d3)


if HAVE_HYPOTHESIS:

    @pytest.mark.parametrize("method", ["paper", "fast", "exact"])
    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_incremental_matches_fresh(method, seed):
        _incremental_body(method, seed)

else:

    @pytest.mark.parametrize("method", ["paper", "fast", "exact"])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_incremental_matches_fresh(method, seed):
        _incremental_body(method, seed)


def test_incremental_identical_matrix_recolors_nothing():
    rng = np.random.default_rng(11)
    coo = coo_from_dense(empty_window_dense(rng, 48, 32, 8))
    old = schedule(coo, 8, load_balance=False)
    reset_sched_counters()
    inc, dirty, _ = incremental_schedule(old, coo, old_coo=coo)
    assert dirty.size == 0
    assert sched_counters["windows_recolored"] == 0
    assert sched_counters["color_calls"] == 0, \
        "no dirty windows -> no colorer invocation at all"
    assert_schedules_bitwise_equal(inc, old)


def test_incremental_rejects_load_balanced_and_reshaped():
    rng = np.random.default_rng(3)
    dense = duplicate_heavy_dense(rng, 32, 32, 8)
    coo = coo_from_dense(dense)
    balanced = schedule(coo, 8, load_balance=True)
    if not np.array_equal(balanced.row_perm, np.arange(32)):
        with pytest.raises(ValueError, match="load_balance=False"):
            incremental_schedule(balanced, coo, old_coo=coo)
    plain = schedule(coo, 8, load_balance=False)
    small = COOMatrix((16, 32), np.zeros(0, np.int64), np.zeros(0, np.int64),
                      np.zeros(0, np.float32))
    with pytest.raises(ValueError, match="shape changed"):
        incremental_schedule(plain, small, old_coo=coo)


def test_window_fingerprints_detect_value_and_structure():
    rng = np.random.default_rng(5)
    dense = duplicate_heavy_dense(rng, 32, 32, 8)
    f0 = window_fingerprints(coo_from_dense(dense), 8)
    bumped = dense.copy()
    nz = np.nonzero(bumped)
    bumped[nz[0][0], nz[1][0]] *= 2.0  # value-only change
    f1 = window_fingerprints(coo_from_dense(bumped), 8)
    w = nz[0][0] // 8
    assert f0[w] != f1[w]
    others = np.arange(f0.shape[0]) != w
    assert np.array_equal(f0[others], f1[others])


# ---------------------------------------------------------------------------
# Index-dtype policy (satellite: halve scheduler peak memory)
# ---------------------------------------------------------------------------


def test_build_edges_int32_when_small():
    rng = np.random.default_rng(1)
    coo = coo_from_dense(hot_column_dense(rng, 40, 40, 8))
    win, row_local, lane, col, val, row_perm = _build_edges(coo, 8, False)
    for arr in (win, row_local, lane, col):
        assert arr.dtype == np.int32, arr.dtype
    assert row_perm.dtype == np.int64  # row_perm feeds jnp gathers as-is
    assert val.dtype == coo.vals.dtype
    # and the schedule built from int32 edges is identical to one built
    # from a forced-int64 path (the dtype is an implementation detail)
    sched = schedule(coo, 8, load_balance=False)
    assert_schedule_invariants(sched, coo, 8)


def test_edge_index_dtype_boundaries():
    assert _edge_index_dtype(100, 100, 1000, 8) == np.int32
    big = np.iinfo(np.int32).max
    assert _edge_index_dtype(big + 1, 100, 1000, 8) == np.int64
    assert _edge_index_dtype(100, big + 1, 1000, 8) == np.int64
    assert _edge_index_dtype(100, 100, big + 1, 8) == np.int64
    # the globalized key bound must fit too, not just m/n/nnz
    assert _edge_index_dtype(big - 4, 100, 1000, 8) == np.int64


# ---------------------------------------------------------------------------
# Degenerate inputs
# ---------------------------------------------------------------------------


def test_empty_edge_stream_all_colorers():
    empty = np.empty(0, dtype=np.int64)
    for colorer in (color_edges_fast, _color_edges_fast_reference,
                    color_edges_paper, color_edges_exact):
        out = colorer(empty, empty)
        assert out.shape == (0,)
    out = color_windows_chunked(empty, empty, empty, 4, 8, workers=4)
    assert out.shape == (0,)


def test_all_zero_matrix_schedules_and_reschedules():
    coo = COOMatrix((16, 16), np.empty(0, np.int64), np.empty(0, np.int64),
                    np.empty(0, np.float32))
    sched = schedule(coo, 4, load_balance=False, workers=2)
    assert sched.nnz == 0
    inc, dirty, _ = incremental_schedule(sched, coo, old_coo=coo)
    assert dirty.size == 0
    assert_schedules_bitwise_equal(inc, sched)
