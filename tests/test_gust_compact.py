"""Compact GUST stream (bf16 values + int16 indices — EXPERIMENTS.md
§Perf iteration 8): numerical parity with the f32/int32 stream, kernel
and XLA paths, plus the stream-size accounting."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch
from repro.core.formats import coo_from_dense
from repro.core.scheduler import schedule
from repro.kernels.ops import gust_spmm, pack_schedule
from repro.models.model_zoo import build_model
from repro.serving.gust_serve import GustServeConfig, decode_step_gust, gustify


def test_compact_pack_parity():
    rng = np.random.default_rng(0)
    dense = ((rng.random((96, 128)) < 0.2) * rng.standard_normal((96, 128))).astype(
        np.float32
    )
    x = rng.standard_normal((128, 4)).astype(np.float32)
    sched = schedule(coo_from_dense(dense), 16)
    full = pack_schedule(sched)
    compact = pack_schedule(sched, value_dtype=jnp.bfloat16, index_dtype=jnp.int16)
    assert compact.col_blk.dtype == jnp.int16 and compact.m_blk.dtype == jnp.bfloat16
    y_full = np.asarray(gust_spmm(full, jnp.asarray(x), use_kernel=False))
    for uk in (False, True):
        y_c = np.asarray(
            gust_spmm(compact, jnp.asarray(x), use_kernel=uk)
        ).astype(np.float32)
        err = np.abs(y_c - y_full).max() / (np.abs(y_full).max() + 1e-9)
        assert err < 2e-2, (uk, err)  # bf16 value rounding


def test_compact_gust_decode_close_to_full():
    cfg = get_arch("yi_6b").reduced()
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    caches = lm.init_caches(2, 64, jnp.float32)
    toks = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (2, 1))
    _, caches = lm.prefill(params, {"tokens": toks}, caches, dtype=jnp.float32)
    tok = jnp.full((2, 1), 3, jnp.int32)
    outs = {}
    for compact in (False, True):
        gcfg = GustServeConfig(density=0.5, gust_length=16, compact=compact)
        gust = gustify(lm, params, gcfg)
        lg, _ = decode_step_gust(lm, params, gust, caches, tok, jnp.int32(8),
                                 cfg=gcfg, dtype=jnp.float32)
        outs[compact] = np.asarray(lg)
        # stream bytes: compact must be exactly half (12 -> 6 B/slot)
        m_blk = gust["mats"]["0.w_down"]["leaves"]["m_blk"]
        col = gust["mats"]["0.w_down"]["leaves"]["col_blk"]
        per_slot = m_blk.dtype.itemsize + 2 * col.dtype.itemsize
        assert per_slot == (6 if compact else 12)
    err = np.abs(outs[True] - outs[False]).max() / np.abs(outs[False]).max()
    assert err < 5e-2, err


def test_int16_range_guard():
    """Compact indices require n <= int16 range; every assigned arch's MLP
    dims satisfy it."""
    from repro.configs.base import ARCH_IDS
    for aid in ARCH_IDS:
        cfg = get_arch(aid)
        if cfg.d_ff:
            assert max(cfg.d_ff, cfg.d_model) < 2 ** 15, aid
