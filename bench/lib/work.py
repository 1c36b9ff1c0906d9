"""Work counts: the operations and least bytes each cell's work needs.

Computed from the matrices' nonzeros and dimensions and from the traffic,
never from a packed stream: padding, layout, gather mode or a new kernel
change the measured time and not the count, so a share of the roofline
reads the same work whatever implements it.
"""

from __future__ import annotations

F32 = 4  # bytes of a float32 value


def gust_product(m: int, n: int, nnz: int, batch: int) -> dict:
    """One sparse product y (m, B) = M (m, n) @ x (n, B) with ``nnz``
    float32 nonzeros: 2 operations per nonzero and row of the batch, and
    at least each value once plus x and y once."""
    return {"flops": 2 * nnz * batch,
            "bytes": F32 * nnz + F32 * (m + n) * batch}


def least_time(flops: float, nbytes: float, peak: dict) -> dict:
    """The least time the chip could take: the larger of operations over
    the bf16 peak and bytes over the HBM bandwidth, and which bounds it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def attn_params(cfg: dict) -> int:
    """Parameters of one layer's q, k, v and o projections."""
    d, h, kv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return d * h * dh + 2 * d * kv * dh + h * dh * d


def lm_token_flops(cfg: dict, mlp_nnz: int, position: int,
                   head: bool = True) -> int:
    """Operations one token at ``position`` requires in the pruned model:
    2 per weight it touches (every layer's attention projections, the
    MLP matrices at their nonzeros, ``mlp_nnz`` summed over layers, and
    the head where its logits are needed), plus 4 per head dimension and
    attended position for the scores and the weighted sum, in every
    layer."""
    layers = cfg["num_hidden_layers"]
    weights = (layers * attn_params(cfg) + mlp_nnz
               + head * cfg["hidden_size"] * cfg["vocab_size"])
    attn = (4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * (position + 1) * layers)
    return 2 * weights + attn


def prompt_flops(cfg: dict, mlp_nnz: int, length: int) -> int:
    """Operations a prompt of ``length`` tokens requires (causal: token
    ``p`` attends to ``p + 1`` positions); only the last token's logits
    are needed."""
    return sum(lm_token_flops(cfg, mlp_nnz, p, head=p == length - 1)
               for p in range(length))
