"""The traffic generator: the same seed gives the same work, every seed
the same sizes in another order, and every size within its range."""

import json

import numpy as np
import pytest

import benchtools
from lib import traffic

MIX = json.load(open(f"{benchtools.BENCH}/traffic/chat-decode.json"))
MIX = dict(MIX, clients=4, requests_per_client=6)
SEEDS = [0, 7, 2**31 + 5, 2**40 + 123]


def flat(work):
    return [(len(p), n, p.tobytes()) for s in work["streams"] for p, n in s]


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_is_deterministic_per_seed(seed):
    a = traffic.generate(MIX, seed, 64000)
    b = traffic.generate(MIX, seed, 64000)
    assert flat(a) == flat(b)
    assert flat(a) != flat(traffic.generate(MIX, seed + 1, 64000))


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_keeps_to_its_ranges(seed):
    streams = traffic.generate(MIX, seed, 1000)["streams"]
    assert len(streams) == 4 and all(len(s) == 6 for s in streams)
    for s in streams:
        for prompt, max_new in s:
            assert 16 <= len(prompt) <= 600
            assert prompt.dtype == np.int32
            assert 0 <= prompt.min() and prompt.max() < 1000
            assert 64 <= max_new <= 1024


def test_chat_strata_match_the_published_means():
    """The mix's strata: ShareGPT's mean input (161.31 tokens) and
    output (337.99) lengths within 3%."""
    prompts = traffic.strata(MIX["prompt_tokens"], 8)
    outs = traffic.strata(MIX["output_tokens"], 8)
    assert prompts == [20, 32, 50, 78, 123, 193, 304, 478]
    assert outs == [76, 108, 152, 215, 304, 431, 609, 861]
    assert abs(np.mean(prompts) / 161.31 - 1) < 0.03
    assert abs(np.mean(outs) / 337.99 - 1) < 0.03


def test_closed_loop_blocks_hold_every_length_and_stratum():
    mix = dict(MIX, clients=8, requests_per_client=3)
    streams = traffic.generate(mix, 11, 100)["streams"]
    order = [streams[c][r] for r in range(3) for c in range(8)]
    for b in range(3):
        block = order[8 * b: 8 * b + 8]
        assert sorted(len(p) for p, _ in block) == traffic.strata(
            mix["prompt_tokens"], 8)
        assert sorted(n for _, n in block) == traffic.strata(
            mix["output_tokens"], 8)


def test_every_seed_draws_the_same_sizes():
    sizes = {tuple(sorted((len(p), n) for s in traffic.generate(
        dict(MIX, clients=8, requests_per_client=1), seed, 100)["streams"]
        for p, n in s)) for seed in SEEDS}
    lengths = {tuple(sorted(p for p, _ in s)) for s in sizes}
    assert len(lengths) == 1


@pytest.mark.parametrize("seed", SEEDS)
def test_power_iteration_start_is_deterministic(seed):
    mix = benchtools.TINY_POWER
    a = traffic.generate(mix, seed, 50)["x0"]
    assert a.shape == (50,) and a.dtype == np.float32
    np.testing.assert_array_equal(a, traffic.generate(mix, seed, 50)["x0"])
    b = traffic.generate(dict(mix, batch=3), seed, 50)["x0"]
    assert b.shape == (50, 3)


@pytest.mark.parametrize("structure", ["banded", "uniform"])
@pytest.mark.parametrize("seed", SEEDS)
def test_matrix_surrogates_are_deterministic_and_full(structure, seed):
    """Each structure draws the same matrix for a seed, with the stated
    dimension and nonzeros (where there is room for them), unique
    positions sorted by row and then column; another seed changes the
    values and not the positions, so every seed gives the same work."""
    from lib import matrices

    spec = {"dim": 2000, "nnz": 40000, "structure": structure,
            "bandwidth_frac": 0.2}
    (m, n), rows, cols, vals = matrices.surrogate(spec, seed)
    assert (m, n) == (2000, 2000) and rows.shape[0] == 40000
    key = rows.astype(np.int64) * n + cols
    assert np.all(np.diff(key) > 0)
    assert rows.min() >= 0 and cols.max() < n and vals.dtype == np.float32
    again = matrices.surrogate(spec, seed)
    np.testing.assert_array_equal(key, again[1] * n + again[2])
    np.testing.assert_array_equal(vals, again[3])
    other = matrices.surrogate(spec, seed + 1)
    np.testing.assert_array_equal(key, other[1] * n + other[2])
    assert not np.array_equal(vals, other[3])
