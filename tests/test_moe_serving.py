"""Expert layers and mixed sliding/full attention served through GUST, at
a small size with Mellum2's pattern (3 sliding-window layers with a
window shorter than the sequence, then 1 full layer with YaRN; 16
experts, 4 held, top 4): prefill then decode through ``ServeLoop`` agrees
with the benchmark's plain float32 reference (``bench/lib/ref_moe.py``,
which imports nothing of the program) on logits; the held-expert shares
of a layer add up to the uncut layer; serving routes without a capacity;
YaRN's frequencies are the closed form at Mellum's parameters."""

import dataclasses
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch
from repro.core.gust_linear import prune_by_magnitude
from repro.models.model_zoo import build_model
from repro.models.moe import MoESpec, init_moe, moe_ffn
from repro.resilience.fallback import fallback_counters
from repro.serving import GustServeConfig, ServeConfig, ServeLoop
from repro.serving.gust_serve import decode_step_gust, gustify

from conftest import REPO

sys.path.insert(0, os.path.join(REPO, "bench"))
from lib import ref_moe  # noqa: E402

WINDOW = 8
DENSITY = 0.5


def _arch():
    return dataclasses.replace(
        get_arch("mellum2_12b_a2_5b").reduced(), n_layers=4, n_experts=16,
        top_k=4, experts_held=4, local_window=WINDOW)


def _ref_cfg(cfg):
    """The reference's configuration keys for the program's ArchConfig."""
    y = cfg.global_yarn
    return {
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv,
        "head_dim": cfg.head_dim, "rms_norm_eps": 1e-6,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "sliding_window": cfg.local_window,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": y.factor,
                "original_max_position_embeddings": y.original_max_position,
                "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                "attention_factor": y.attention_factor},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta}},
        "num_experts": cfg.experts_held, "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": True, "num_hidden_layers": cfg.n_layers,
        "expert_share": {"router_experts": cfg.n_experts, "first": 0},
    }


@pytest.fixture(scope="module")
def moe_lm():
    """The small model with every held expert matrix pruned to DENSITY,
    so prefill (dense) and decode (GUST plans) serve one pruned model."""
    cfg = _arch()
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(4))

    def prune(w):
        flat = np.asarray(w).reshape((-1,) + w.shape[-2:])
        return jnp.asarray(np.stack([prune_by_magnitude(m, DENSITY)
                                     for m in flat]).reshape(w.shape))

    reps = tuple(
        dict(p, moe=dict(p["moe"], **{k: prune(p["moe"][k])
                                      for k in ("w_gate", "w_up", "w_down")}))
        for p in params["stack"]["reps"])
    params = dict(params, stack={"reps": reps, "tail": []})
    return lm, params


def _gaps(lm, params, served):
    """Per served token: the reference's best logit less the served
    token's, at the position that chose it."""
    cfg = _ref_cfg(lm.cfg)
    out = []
    for prompt, toks in served:
        seq = np.concatenate([prompt, toks]).astype(np.int32)
        ref = np.asarray(ref_moe.logits(cfg, params, seq))
        lo = len(prompt) - 1
        for j, tok in enumerate(toks):
            row = ref[lo + j]
            out.append(row.max() - row[tok])
    return np.asarray(out)


def test_yarn_inv_freq_matches_closed_form():
    y = get_arch("mellum2_12b_a2_5b").global_yarn
    d, theta = 128, 5e5
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(128 * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (18, 35)
    i = np.arange(64)
    pos_freqs = theta ** (2.0 * i / d)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = (1 / (16 * pos_freqs)) * ramp + (1 / pos_freqs) * (1 - ramp)
    got = y.inv_freq(d, theta)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # below low the default frequencies, above high divided by the factor
    np.testing.assert_allclose(got[:18], 1 / pos_freqs[:18], rtol=1e-6)
    np.testing.assert_allclose(got[35:], 1 / (16 * pos_freqs[35:]), rtol=1e-6)
    np.testing.assert_allclose(
        got, ref_moe.rope_inv_freq(d, _ref_cfg(get_arch(
            "mellum2_12b_a2_5b"))["rope_parameters"]["full_attention"])[0],
        rtol=1e-6)
    # the rotation carries the attention factor on cos and sin: a vector
    # at position 0 is only scaled
    from repro.models.layers import rope

    x = jnp.ones((1, 1, 1, d), jnp.float32)
    out = rope(x, jnp.zeros((1,), jnp.int32), theta=theta, yarn=y)
    np.testing.assert_allclose(np.asarray(out), y.attention_factor, rtol=1e-6)


def test_held_expert_shares_sum_to_uncut_layer():
    spec = MoESpec(d_model=32, d_ff=64, n_experts=16, top_k=4)
    full = init_moe(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32), jnp.float32)
    uncut, _, routed = moe_ffn(full, x, spec, serving=True)
    assert routed.shape == (2, 24, 16)
    assert np.all(np.asarray(routed).sum(-1) == 4)
    total = 0.0
    for first in range(0, 16, 4):
        share = dataclasses.replace(spec, expert_first=first, n_held=4)
        p = {"router": full["router"],
             **{k: full[k][first:first + 4]
                for k in ("w_gate", "w_up", "w_down")}}
        y, _, r = moe_ffn(p, x, share, serving=True)
        np.testing.assert_array_equal(np.asarray(r),
                                      np.asarray(routed)[..., first:first + 4])
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)


def test_routing_dropless_when_every_token_picks_one_expert():
    spec = MoESpec(d_model=32, d_ff=64, n_experts=16, top_k=4)
    p = init_moe(jax.random.PRNGKey(2), spec)
    # every token's largest router logit is expert 3's
    p["router"] = p["router"].at[:, 3].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (1, 64, 32)))
    xf = x[0]
    _, _, gate, idx = spec.route(p["router"], xf)
    assert np.all(np.asarray(idx)[:, 0] == 3)
    hi = jax.lax.Precision.HIGHEST
    want = np.zeros((64, 32), np.float32)
    for t in range(64):  # each token's own top-k terms, nothing dropped
        for w, e in zip(np.asarray(gate[t]), np.asarray(idx[t])):
            h = jax.nn.silu(jnp.dot(xf[t], p["w_gate"][e], precision=hi)
                            ) * jnp.dot(xf[t], p["w_up"][e], precision=hi)
            want[t] += w * np.asarray(jnp.dot(h, p["w_down"][e], precision=hi))
    got, _, _ = moe_ffn(p, x, spec, serving=True)
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=1e-4, atol=1e-5)
    # with a capacity (64 tokens, 20 slots for expert 3) tokens are dropped
    capped, _, _ = moe_ffn(p, x, spec)
    assert np.abs(np.asarray(capped)[0] - want).max() > 1e-2


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_gust_decode_matches_reference_logits(moe_lm, use_kernel):
    """Prefill, then decode through the cache past the window, every MLP
    matvec on the held experts' GUST plans: logits at every position
    agree with the reference's full forward pass."""
    lm, params = moe_lm
    gcfg = GustServeConfig(density=DENSITY, gust_length=16,
                           use_kernel=use_kernel)
    fb0 = dict(fallback_counters)
    gust = gustify(lm, params, gcfg)
    assert len(gust["mats"]) == 12  # 4 layers x 3 matrices
    assert gust["mats"]["3.w_down"]["leaves"]["m_blk"].shape[:2] == (1, 4)
    assert np.asarray(gust["stats"]["0.w_gate"]["nnz"]).shape == (1, 4)
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (14,), 0,
                                        lm.cfg.vocab), np.int32)
    prompt, steps = 6, 8 if not use_kernel else 3
    ref = np.asarray(ref_moe.logits(_ref_cfg(lm.cfg), params, seq))
    caches = lm.init_caches(1, 32, jnp.float32)
    logits, caches = lm.prefill(params, {"tokens": jnp.asarray(seq[None, :prompt])},
                                caches, dtype=jnp.float32)
    got = [np.asarray(logits[0, -1])]
    step = jax.jit(lambda c, t, p: decode_step_gust(
        lm, params, gust, c, t, p, cfg=gcfg, dtype=jnp.float32))
    for pos in range(prompt, prompt + steps):
        logits, caches = step(caches, jnp.asarray(seq[None, pos:pos + 1]),
                              jnp.int32(pos))
        got.append(np.asarray(logits[0, 0]))
    want = ref[prompt - 1: prompt + steps]
    err = np.abs(np.stack(got) - want).max() / np.abs(want).max()
    assert err < 1e-4, err
    assert dict(fallback_counters) == fb0  # the kernel ran, no fallback


def test_serve_loop_matches_reference(moe_lm):
    """The normal serving path: ServeLoop with GUST, two requests of
    different lengths decoding past the window at once; every served
    token is the reference's first choice, and the routing counters
    count what was routed."""
    lm, params = moe_lm
    gcfg = GustServeConfig(density=DENSITY, gust_length=16)
    loop = ServeLoop(lm, params, ServeConfig(batch=4, seq_len=32,
                                             dtype="float32", gust=gcfg))
    prompts = [np.arange(3, 8, dtype=np.int32),
               np.arange(20, 31, dtype=np.int32)]
    rids = [loop.submit(p, max_new=12) for p in prompts]
    loop.run_to_completion()
    served = [(p, loop.completed[r]) for p, r in zip(prompts, rids)]
    gaps = _gaps(lm, params, served)
    assert gaps.shape == (26,) and gaps.max() < 1e-4, gaps.max()
    st = loop.stats
    tokens = np.asarray(st["moe.expert_tokens"])
    assert tokens.shape == (4, 4)
    assert tokens.sum() == st["moe.routed_pairs"] > 0
    assert st["moe.computed_cols"] == st["decode_steps"] * 4 * 4 * 4
    assert np.all(np.asarray(st["moe.expert_steps"]) <= st["decode_steps"])
    assert np.all(np.asarray(st["moe.expert_steps"]) <= tokens)
