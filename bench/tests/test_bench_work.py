"""Operation and least-byte counts against hand counts at small shapes."""

import benchtools  # noqa: F401  (puts bench/ on the path)
from lib import work

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
TINY = {"hidden_size": 4, "intermediate_size": 6, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 2, "num_hidden_layers": 3,
        "vocab_size": 10}


def test_gust_product_counts_values_once_and_vectors_once():
    # 3x5 matrix, 7 nonzeros, 2 vectors: 2*7*2 ops; 7 values + (3+5)*2
    # vector entries of 4 bytes each
    assert work.gust_product(3, 5, 7, 2) == {"flops": 28, "bytes": 4 * (7 + 16)}


def test_least_time_takes_the_larger_bound():
    assert work.least_time(1000.0, 10.0, PEAK) == {"seconds": 10.0,
                                                   "bound": "flops"}
    assert work.least_time(100.0, 50.0, PEAK) == {"seconds": 5.0,
                                                  "bound": "bytes"}


def test_attention_parameters_by_hand():
    # q 4*2*2 + k 4*1*2 + v 4*1*2 + o 2*2*4
    assert work.attn_params(TINY) == 16 + 8 + 8 + 16


def test_token_flops_by_hand():
    # weights: 3 layers * 48 attention + 30 MLP nonzeros + head 4*10
    # attention: 4 * 2 heads * 2 dims * (position 5 + 1) * 3 layers
    assert work.lm_token_flops(TINY, 30, 5) == 2 * (144 + 30 + 40) + 288
    assert work.lm_token_flops(TINY, 30, 5, head=False) == 2 * (144 + 30) + 288


def test_prompt_flops_counts_the_head_once():
    want = sum(2 * (144 + 30) + 4 * 2 * 2 * (p + 1) * 3 for p in range(3)) + 80
    assert work.prompt_flops(TINY, 30, 3) == want
