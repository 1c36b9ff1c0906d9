"""Serving-layer tests: continuous-batching loop (per-slot prefill +
per-slot positions: concurrent mixed-length serving is bit-identical per
request to solo serving), GUST-sparse decode (identity at density 1.0,
Pallas/XLA parity), GustLinear, cache sizing."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch
from repro.core.gust_linear import GustLinear, SparsityConfig, prune_by_magnitude
from repro.models.model_zoo import build_model
from repro.serving import (
    CachePolicy,
    GustServeConfig,
    ServeConfig,
    ServeLoop,
    cache_bytes,
    cache_specs,
    make_sampler,
)
from repro.serving.gust_serve import decode_step_gust, dryrun_specs, gustify

KEY = jax.random.PRNGKey(0)


def _solo(lm, params, prompt, max_new, *, batch=4, seq_len=64, gust=None):
    """Serve one request alone on an otherwise-idle engine."""
    sc = ServeConfig(batch=batch, seq_len=seq_len, dtype="float32", gust=gust)
    loop = ServeLoop(lm, params, sc)
    rid = loop.submit(np.asarray(prompt, np.int32), max_new=max_new)
    loop.run_to_completion()
    return loop.completed[rid]


@pytest.fixture(scope="module")
def dense_lm():
    cfg = get_arch("yi_6b").reduced()
    lm = build_model(cfg)
    return lm, lm.init(KEY)


@pytest.fixture(scope="module")
def moe_lm():
    """Expert layers (2 of 4 experts held, top 2) under sliding-window
    and full YaRN attention: the reduced Mellum2."""
    cfg = get_arch("mellum2_12b_a2_5b").reduced()
    lm = build_model(cfg)
    return lm, lm.init(KEY)


def test_serve_loop_generates(dense_lm):
    lm, params = dense_lm
    loop = ServeLoop(lm, params, ServeConfig(batch=4, seq_len=64, dtype="float32"))
    rid = loop.submit(np.arange(8, dtype=np.int32), max_new=5)
    loop.run_to_completion()
    out = loop.completed[rid]
    assert len(out) == 6  # first sampled token + 5 decode steps
    assert all(0 <= t < lm.cfg.padded_vocab for t in out)


def test_serve_loop_deterministic_greedy(dense_lm):
    lm, params = dense_lm
    outs = []
    for _ in range(2):
        loop = ServeLoop(lm, params, ServeConfig(batch=2, seq_len=64, dtype="float32"))
        rid = loop.submit(np.arange(6, dtype=np.int32), max_new=4)
        loop.run_to_completion()
        outs.append(loop.completed[rid])
    assert outs[0] == outs[1]


def test_gust_decode_identity_at_full_density(dense_lm):
    lm, params = dense_lm
    gcfg = GustServeConfig(density=1.0, gust_length=16)
    gust = gustify(lm, params, gcfg)
    caches = lm.init_caches(2, 64, jnp.float32)
    toks = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (2, 1))
    _, caches = lm.prefill(params, {"tokens": toks}, caches, dtype=jnp.float32)
    tok = jnp.full((2, 1), 3, jnp.int32)
    ld, _ = lm.decode_step(params, caches, tok, jnp.int32(8), dtype=jnp.float32)
    lg, _ = decode_step_gust(lm, params, gust, caches, tok, jnp.int32(8),
                             cfg=gcfg, dtype=jnp.float32)
    err = np.abs(np.asarray(ld) - np.asarray(lg)).max() / np.abs(np.asarray(ld)).max()
    assert err < 1e-4, err
    # full density -> every scheduled slot is a real nonzero along rows
    for key in gust["mats"]:
        assert gust["stats"][key]["stream_utilization"] > 0.5


def test_gust_decode_pallas_xla_parity(dense_lm):
    lm, params = dense_lm
    gcfg_x = GustServeConfig(density=0.3, gust_length=16, use_kernel=False)
    gcfg_k = GustServeConfig(density=0.3, gust_length=16, use_kernel=True)
    gust = gustify(lm, params, gcfg_x)
    caches = lm.init_caches(2, 64, jnp.float32)
    toks = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (2, 1))
    _, caches = lm.prefill(params, {"tokens": toks}, caches, dtype=jnp.float32)
    tok = jnp.full((2, 1), 3, jnp.int32)
    lx, _ = decode_step_gust(lm, params, gust, caches, tok, jnp.int32(8),
                             cfg=gcfg_x, dtype=jnp.float32)
    lk, _ = decode_step_gust(lm, params, gust, caches, tok, jnp.int32(8),
                             cfg=gcfg_k, dtype=jnp.float32)
    err = np.abs(np.asarray(lx) - np.asarray(lk)).max() / np.abs(np.asarray(lx)).max()
    assert err < 1e-4, err


def test_gust_serve_loop_end_to_end(dense_lm):
    lm, params = dense_lm
    sc = ServeConfig(batch=2, seq_len=64, dtype="float32",
                     gust=GustServeConfig(density=0.5, gust_length=16))
    loop = ServeLoop(lm, params, sc)
    rid = loop.submit(np.arange(8, dtype=np.int32), max_new=4)
    loop.run_to_completion()
    assert len(loop.completed[rid]) == 5


def test_dryrun_specs_shapes(dense_lm):
    lm, _ = dense_lm
    gcfg = GustServeConfig(density=0.1, gust_length=16)
    specs = dryrun_specs(lm, gcfg)
    for name, entry in specs["mats"].items():
        (l, w, c_pad, shape, fusable, c_blk, s_blk,
         identity_perm) = entry["meta"]
        assert fusable and l == 16
        m_blk = entry["leaves"]["m_blk"]
        assert m_blk.shape == (lm.stack.reps, w * c_pad, l)
        assert entry["leaves"]["seg_blk"].shape[-1] == s_blk


def test_gust_linear_vs_dense():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((48, 64)).astype(np.float32)
    x = rng.standard_normal((5, 64)).astype(np.float32)
    gl = GustLinear(w, SparsityConfig(enable=True, density=1.0, gust_length=8))
    y = np.asarray(gl(jnp.asarray(x)))
    np.testing.assert_allclose(y, x @ w.T, rtol=1e-4, atol=1e-4)
    # pruned version equals dense with pruned weights
    gl2 = GustLinear(w, SparsityConfig(enable=True, density=0.25, gust_length=8))
    wp = prune_by_magnitude(w, 0.25)
    y2 = np.asarray(gl2(jnp.asarray(x)))
    np.testing.assert_allclose(y2, x @ wp.T, rtol=1e-4, atol=1e-4)
    assert gl2.nnz <= int(w.size * 0.25) + 1


def test_gust_linear_use_kernel_regression():
    """Regression: use_kernel=True used to pass the ragged GustSchedule to
    kops.gust_spmm (which requires a PackedSchedule) and crash.  Both
    execution paths must run and agree with the pruned dense product."""
    rng = np.random.default_rng(1)
    w = rng.standard_normal((48, 64)).astype(np.float32)
    x = rng.standard_normal((5, 64)).astype(np.float32)
    wp = prune_by_magnitude(w, 0.25)
    ys = {}
    for uk in (False, True):
        gl = GustLinear(w, SparsityConfig(enable=True, density=0.25,
                                          gust_length=8, use_kernel=uk))
        assert gl.packed.fusable
        ys[uk] = np.asarray(gl(jnp.asarray(x)))
        np.testing.assert_allclose(ys[uk], x @ wp.T, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ys[True], ys[False], rtol=1e-5, atol=1e-5)


def test_second_admission_mid_decode_is_isolated(dense_lm):
    """Regression (ISSUE 4 bug 1): admitting request B while request A is
    mid-decode must not touch A's KV cache.  The old full-batch prefill
    clobbered every slot with B's padded prompt; per-slot prefill writes
    only B's batch row, so A's continuation is bit-identical to solo."""
    lm, params = dense_lm
    pa = np.arange(8, dtype=np.int32)
    pb = np.arange(3, 8, dtype=np.int32)
    solo_a = _solo(lm, params, pa, max_new=8)
    solo_b = _solo(lm, params, pb, max_new=6)
    loop = ServeLoop(lm, params, ServeConfig(batch=4, seq_len=64, dtype="float32"))
    ra = loop.submit(pa, max_new=8)
    for _ in range(3):  # A is now mid-decode
        loop.step()
    rb = loop.submit(pb, max_new=6)
    loop.run_to_completion()
    assert loop.completed[ra] == solo_a
    assert loop.completed[rb] == solo_b


def test_mixed_length_concurrent_matches_solo(dense_lm, moe_lm):
    """Regression (ISSUE 4 bug 2): slots with different prompt lengths
    decode at their OWN positions.  The old step() decoded everyone at
    max(slot.pos), corrupting every shorter request.  Expert layers route
    without a capacity on the serving path, so their rows stay
    independent too."""
    prompts = [np.arange(5, dtype=np.int32),
               np.arange(11, dtype=np.int32),
               np.arange(2, 9, dtype=np.int32)]
    for lm, params in (dense_lm, moe_lm):
        solos = [_solo(lm, params, p, max_new=6) for p in prompts]
        loop = ServeLoop(lm, params,
                         ServeConfig(batch=4, seq_len=64, dtype="float32"))
        rids = [loop.submit(p, max_new=6) for p in prompts]
        loop.run_to_completion()
        for rid, solo in zip(rids, solos):
            assert loop.completed[rid] == solo


def test_gust_mixed_length_concurrent_matches_solo(dense_lm, moe_lm):
    """The GUST decode path runs through the same per-slot machinery, for
    MLP blocks and for held experts under the router alike."""
    gcfg = GustServeConfig(density=0.5, gust_length=16)
    prompts = [np.arange(4, dtype=np.int32), np.arange(9, dtype=np.int32)]
    for lm, params in (dense_lm, moe_lm):
        solos = [_solo(lm, params, p, max_new=4, batch=2, gust=gcfg)
                 for p in prompts]
        sc = ServeConfig(batch=2, seq_len=64, dtype="float32", gust=gcfg)
        loop = ServeLoop(lm, params, sc)
        rids = [loop.submit(p, max_new=4) for p in prompts]
        loop.run_to_completion()
        for rid, solo in zip(rids, solos):
            assert loop.completed[rid] == solo


def test_queue_admission_drains_stream(dense_lm):
    """Bounded admission queue: more requests than slots drain through
    step() with no manual slot management; capacity overflow load-sheds
    the newest request as a structured SHED result, not an exception."""
    lm, params = dense_lm
    sc = ServeConfig(batch=2, seq_len=64, dtype="float32", queue_capacity=6)
    loop = ServeLoop(lm, params, sc)
    rng = np.random.default_rng(0)
    rids = [loop.enqueue(rng.integers(0, lm.cfg.vocab, 3 + r).astype(np.int32),
                         max_new=3) for r in range(6)]
    shed_rid = loop.enqueue(np.arange(4, dtype=np.int32), max_new=1)
    shed = loop.results[shed_rid]
    assert shed.status.name == "SHED" and "queue full" in shed.reason
    assert loop.stats["shed"] == 1
    loop.run_to_completion()
    assert not loop.pending
    assert sorted(loop.completed) == sorted(rids)
    assert all(len(loop.completed[r]) == 4 for r in rids)
    # 6 requests on 2 slots: at least 3 waves of decode, fully occupied
    assert loop.stats["prefills"] == 6
    assert loop.occupancy > 0.9


def test_eos_retirement(dense_lm):
    """A slot retires as soon as it samples eos_id."""
    lm, params = dense_lm
    prompt = np.arange(7, dtype=np.int32)
    full = _solo(lm, params, prompt, max_new=8)
    eos = full[2]
    k = full.index(eos)  # first time greedy decode emits it
    sc = ServeConfig(batch=2, seq_len=64, dtype="float32", eos_id=int(eos))
    loop = ServeLoop(lm, params, sc)
    rid = loop.submit(prompt, max_new=8)
    loop.run_to_completion()
    assert loop.completed[rid] == full[: k + 1]


def test_sampler_max_subtracted_large_logits():
    """Regression: the host sampler did np.exp(logits / T) and produced
    inf/NaN for |logits| ~ 1e3.  The on-device sampler is max-subtracted:
    huge logits sample fine, and the argmax-dominant token wins."""
    sampler = make_sampler(1.0)
    logits = jnp.asarray([[1000.0, 0.0, -500.0],
                          [2000.0, 2000.0 - 30.0, 0.0]], jnp.float32)
    rid_step = jnp.asarray([[0, 0], [1, 5]], jnp.int32)
    for seed in range(8):
        out = np.asarray(sampler(logits, jax.random.PRNGKey(seed), rid_step))
        assert out.shape == (2,) and out.dtype == np.int32
        # p(other) ~ e^-1000 and e^-30: the dominant logit must win
        assert out[0] == 0 and out[1] == 0
    greedy = make_sampler(0.0)
    out = np.asarray(greedy(logits, jax.random.PRNGKey(0), rid_step))
    np.testing.assert_array_equal(out, [0, 0])


def test_temperature_serving_is_reproducible(dense_lm):
    """Per-(request, token) sampling keys: same seed -> same stream, and
    a request's sampled continuation doesn't depend on co-scheduling."""
    lm, params = dense_lm
    sc = ServeConfig(batch=2, seq_len=64, dtype="float32", temperature=0.8)
    outs = []
    for _ in range(2):
        loop = ServeLoop(lm, params, sc, seed=7)
        rid = loop.submit(np.arange(6, dtype=np.int32), max_new=5)
        loop.run_to_completion()
        outs.append(loop.completed[rid])
    assert outs[0] == outs[1]
    assert all(0 <= t < lm.cfg.padded_vocab for t in outs[0])


def test_cache_bytes_accounting():
    cfg = get_arch("yi_6b").reduced()
    lm = build_model(cfg)
    n = cache_bytes(lm, batch=2, seq_len=64, policy=CachePolicy(dtype="bfloat16"))
    # 3 layers(reduced) x k/v (2, 64, 2, 16) bf16 + pos
    assert n > 0
    n32 = cache_bytes(lm, batch=2, seq_len=64, policy=CachePolicy(dtype="float32"))
    assert n32 > n


def test_cache_bytes_no_int32_overflow_at_123b_scale():
    """Regression: jnp.prod(jnp.array(shape)) overflowed int32 above 2**31
    elements per leaf.  The 123B config at serving shapes crosses that;
    accounting must match an independent host-side math.prod sum."""
    lm = build_model(get_arch("mistral_large_123b"))
    batch, seq = 8, 32_768
    n = cache_bytes(lm, batch=batch, seq_len=seq)
    expect = sum(
        jnp.dtype(x.dtype).itemsize * math.prod(x.shape)
        for x in jax.tree.leaves(cache_specs(lm, batch, seq))
    )
    assert n == expect
    assert n > 2**31  # the overflow regime: old code went negative/garbage
    assert n % 2 == 0  # bf16 leaves: whole itemsize multiples
