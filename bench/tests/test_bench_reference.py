"""The benchmark's own pruning and plain reference, against the program
at a small size on the CPU."""

import numpy as np
import pytest

import benchtools
from lib import lm_weights, ref_lm

CFG = benchtools.TINY_LM


@pytest.fixture(scope="module")
def model():
    params, nnz = lm_weights.make_params(CFG, 0.25, 2**34 + 3)
    return params, nnz


def test_pruning_keeps_k_largest_and_is_a_fixed_point(model):
    from repro.core.gust_linear import prune_by_magnitude

    params, nnz = model
    mlp = params["stack"]["reps"][0]["mlp"]
    for name, counts in nnz.items():
        w = np.asarray(mlp[name])
        k = lm_weights.keep_count(w.shape[1] * w.shape[2], 0.25)
        assert counts == [k] * w.shape[0]
        for layer in w:
            assert np.count_nonzero(layer) == k
            # the program's magnitude pruning keeps the same nonzeros
            np.testing.assert_array_equal(prune_by_magnitude(layer, 0.25),
                                          layer)


def test_weights_have_the_programs_layout(model):
    import jax

    serving = benchtools.bench_module("drivers/serving.py", "bench_serving")
    from repro.models.model_zoo import build_model

    lm = build_model(serving.program_arch(CFG))
    want = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    params, _ = model
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(want)] == [
        a.shape for a in jax.tree.leaves(params)]


def test_reference_scores_the_programs_prefill(model):
    """The program's dense prefill of the pruned model puts first the
    token the reference puts first, at every position."""
    import jax
    import jax.numpy as jnp

    serving = benchtools.bench_module("drivers/serving.py", "bench_serving")
    from repro.models.model_zoo import build_model

    lm = build_model(serving.program_arch(CFG))
    params, _ = model
    toks = np.random.default_rng(1).integers(0, 256, 24).astype(np.int32)
    caches = lm.init_caches(1, 32, jnp.float32)
    served = []
    for n in range(8, 24):  # greedy next token after each prefix
        logits, _ = jax.jit(lm.prefill, static_argnames=("dtype",))(
            params, {"tokens": jnp.asarray(toks[:n])[None]}, caches,
            dtype=jnp.float32)
        served.append(int(jnp.argmax(logits[0, -1])))
    prompt = toks[:8]
    # teacher-force: the program's choice after each true prefix
    gaps = []
    for j, tok in enumerate(served):
        seq = [(toks[: 8 + j], [tok])]
        gaps.append(ref_lm.widest_gaps(CFG, params, seq, 32)["widest_logit_gap"])
    assert max(gaps) < 1e-4
    # and a wrong token reads a clear gap
    bad = ref_lm.widest_gaps(CFG, params, [(prompt, [(served[0] + 1) % 256])],
                             32)["widest_logit_gap"]
    assert bad > 1e-3


def test_gaps_count_every_served_token(model):
    """Tokens the reference puts first read 0; another token reads its
    distance below the best, in the widest gap, the mean and the count."""
    import jax.numpy as jnp

    params, _ = model
    toks = np.random.default_rng(2).integers(0, 256, 12).astype(np.int32)
    served = []
    for _ in range(6):  # the reference's own greedy tokens
        pad = np.zeros(32, np.int32)
        seq = np.concatenate([toks, served]).astype(np.int32)
        pad[: len(seq)] = seq
        out = ref_lm._forward(CFG, params, jnp.asarray(pad), jnp.float32,
                              None)
        served.append(int(np.argmax(np.asarray(out)[len(seq) - 1])))
    good = ref_lm.widest_gaps(CFG, params, [(toks, served)], 32)
    assert good["tokens"] == 6 and good["tokens_not_first"] == 0
    assert good["widest_logit_gap"] < 1e-4
    bad = list(served)
    bad[5] = (bad[5] + 1) % 256
    out = ref_lm.widest_gaps(CFG, params, [(toks, bad)], 32)
    assert out["tokens_not_first"] == 1
    assert out["widest_logit_gap"] > 1e-3
    assert abs(out["mean_logit_gap"] - out["widest_logit_gap"] / 6) < 1e-4


def test_int8_control_rounds_each_output_channel(model):
    """The control's weights: int8 steps of one scale per output channel,
    zeros kept, the embedding untouched, within half a step of the
    original."""
    params, _ = model
    q = ref_lm.int8_weights(params)
    layer, ql = params["stack"]["reps"][0], q["stack"]["reps"][0]
    w, qw = np.asarray(layer["mlp"]["w_up"]), np.asarray(ql["mlp"]["w_up"])
    step = np.abs(w).max(axis=1, keepdims=True) / 127.0
    assert np.all(np.abs(qw - w) <= step / 2 + 1e-7)
    assert np.all(qw[w == 0] == 0)
    assert len(np.unique(np.round(qw[0, :, 0] / step[0, 0, 0]))) <= 255
    np.testing.assert_array_equal(np.asarray(q["embed"]["table"]),
                                  np.asarray(params["embed"]["table"]))
    assert not np.array_equal(np.asarray(q["lm_head"]["table"]),
                              np.asarray(params["lm_head"]["table"]))
