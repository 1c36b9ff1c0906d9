"""mellum2-12b-a2.5b [moe] — 64 experts top-8, 3:1 sliding:full
attention, YaRN on the full layers
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

28L d_model=2304 32H (GQA kv=4) d_head=128 vocab=98304, untied head.
``layer_types`` repeats (sliding, sliding, sliding, full) with a
1024-token window; every MLP is a routed MoE (``mlp_layer_types`` all
``sparse``): 64 SwiGLU experts of width 896, 8 per token, softmax
routing renormalised over the 8, no shared expert.  Rope theta 5e5 on
every layer; the full layers add YaRN (factor 16 over 8192 original
positions, beta_fast 32, beta_slow 1, attention factor 1.27726).

One chip holds experts 0-7 of each layer: the 8-way expert-parallel
share (model-configs section 4).  The router keeps its 64 outputs and its
top-8; the experts held elsewhere are left out of this chip's result.
The multi-token-prediction head the model card names has no key in the
config and is not used to serve, so it is not built.
"""

from .base import ArchConfig, Yarn, register

CONFIG = register(ArchConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv=4,
    d_ff=896,  # moe_intermediate_size; no layer is dense
    vocab=98_304,
    pattern=("moe_local", "moe_local", "moe_local", "moe_global"),
    d_head=128,
    local_window=1024,
    rope_theta=500_000.0,
    global_yarn=Yarn(factor=16.0, original_max_position=8192, beta_fast=32.0,
                     beta_slow=1.0, attention_factor=1.2772588722239782),
    n_experts=64,
    top_k=8,
    experts_held=8,
    tie_embeddings=False,
    source="https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json",
))
