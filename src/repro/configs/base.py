"""Config system: ArchConfig / ShapeConfig dataclasses + registry.

``ArchConfig`` fully determines a model: layer pattern, attention geometry,
MoE, frontend kind.  ``reduced()`` derives the family-preserving smoke
config (same block pattern, tiny widths) used by per-arch CPU tests.
``ShapeConfig`` is one of the four assigned input shapes.

Registration is import-driven: each ``configs/<arch>.py`` module defines
``CONFIG`` and calls :func:`register`; :func:`get_arch` imports on demand
so ``--arch <id>`` works from every launcher.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Dict, Optional, Tuple

import numpy as np

__all__ = [
    "ArchConfig",
    "Yarn",
    "ShapeConfig",
    "SHAPES",
    "register",
    "get_arch",
    "list_archs",
    "ARCH_IDS",
]


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling (arXiv:2309.00071), as a HF ``rope_parameters``
    entry of ``rope_type: yarn`` states it; ``models.layers.rope`` rotates
    by :meth:`inv_freq` and scales cos and sin by ``attention_factor``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self, d: int, theta: float) -> np.ndarray:
        """(d/2,) float32 inverse frequencies for head size ``d``, as HF
        ``rope_type: yarn`` computes them.  With factor s and original
        positions L:

            pos_freqs(i) = theta ** (2i / d),  i < d/2
            extrap = 1 / pos_freqs,  interp = 1 / (s * pos_freqs)
            dim(b) = d * ln(L / (2 pi b)) / (2 ln theta)
            low = max(floor(dim(beta_fast)), 0)
            high = min(ceil(dim(beta_slow)), d - 1)
            ramp(i) = clip((i - low) / (high - low), 0, 1)
            inv_freq = interp * ramp + extrap * (1 - ramp)

        At d 128, theta 5e5, L 8192 and betas 32 / 1: low 18, high 35."""
        half = d // 2
        pos_freqs = theta ** (np.arange(half, dtype=np.float64) * 2.0 / d)

        def dim(beta):
            return d * math.log(self.original_max_position
                                / (2 * math.pi * beta)) / (2 * math.log(theta))

        low = max(math.floor(dim(self.beta_fast)), 0)
        high = min(math.ceil(dim(self.beta_slow)), d - 1)
        if high == low:
            high += 0.001
        ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
        inv = (ramp / (self.factor * pos_freqs)
               + (1.0 - ramp) / pos_freqs)
        return inv.astype(np.float32)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # ssm | hybrid | moe | dense | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    # block pattern: tuple of block-type ids, tiled to n_layers
    #   global | local | chunked | moe_global | moe_chunked | rec | mlstm | slstm
    pattern: Tuple[str, ...] = ("global",)
    d_head: int = 0  # 0 -> d_model // n_heads
    local_window: int = 0  # sliding-window size for 'local' blocks
    chunk_size: int = 0  # chunk size for 'chunked' blocks
    global_cache_cap: int = 0  # decode-cache cap for global layers (long ctx)
    rope_theta: float = 10_000.0
    global_yarn: Optional[Yarn] = None  # YaRN on 'global'/'moe_global'
    # blocks; the other blocks keep the default rope at rope_theta
    n_experts: int = 0  # the router's width: experts of the whole layer
    top_k: int = 0
    experts_held: int = 0  # experts 0..experts_held-1 held here (one
    # share of an expert-parallel layer; 0 = all n_experts)
    capacity_factor: float = 1.25
    mlp_kind: str = "swiglu"
    norm_kind: str = "rms"
    tie_embeddings: bool = True
    emb_scale: bool = False  # gemma-style sqrt(d) embedding scale
    frontend: str = "token"  # token | embed (vlm stub) | encdec (audio stub)
    n_enc_layers: int = 0  # encoder depth for encdec
    enc_seq: int = 0  # encoder (source) length for encdec shapes
    attn_block_size: int = 1024  # online-softmax KV block
    mlstm_expand: int = 2
    source: str = ""  # provenance note

    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to 256 so the embedding/logits shard over any
        mesh axis (seamless's 256206 would otherwise replicate a
        (B, S, V) f32 logits tensor on every chip).  Padding logits are
        masked to -inf in ``LM._logits``."""
        return -(-self.vocab // 256) * 256

    @property
    def is_encdec(self) -> bool:
        return self.frontend == "encdec"

    @property
    def sub_quadratic(self) -> bool:
        """True if decode memory/compute per token is bounded (can serve
        long_500k): every block is recurrent, windowed, or cap-bounded."""
        for b in self.pattern:
            if b in ("global", "moe_global") and not self.global_cache_cap:
                return False
        return True

    def reduced(self) -> "ArchConfig":
        """Family-preserving smoke config: tiny dims, same pattern."""
        pat = self.pattern
        n_layers = max(len(pat), 2 if len(pat) == 1 else len(pat))
        n_kv = min(self.n_kv, 2)
        n_heads = max(min(self.n_heads, 4) // n_kv * n_kv, n_kv)
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=n_layers + (1 if len(pat) > 1 else 0),  # force a tail
            d_model=64,
            n_heads=n_heads,
            n_kv=n_kv,
            d_head=16,
            d_ff=128 if self.d_ff else 0,
            vocab=256,
            local_window=min(self.local_window, 16) if self.local_window else 0,
            chunk_size=min(self.chunk_size, 16) if self.chunk_size else 0,
            global_cache_cap=min(self.global_cache_cap, 32)
            if self.global_cache_cap
            else 0,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            experts_held=min(self.experts_held, 2) if self.experts_held else 0,
            n_enc_layers=min(self.n_enc_layers, 2) if self.n_enc_layers else 0,
            enc_seq=min(self.enc_seq, 16) if self.enc_seq else 0,
            attn_block_size=64,
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


ARCH_IDS = (
    "xlstm_125m",
    "recurrentgemma_9b",
    "llama4_scout_17b_a16e",
    "dbrx_132b",
    "gemma3_4b",
    "phi3_mini_3_8b",
    "mistral_large_123b",
    "yi_6b",
    "llava_next_mistral_7b",
    "seamless_m4t_medium",
    "mellum2_12b_a2_5b",
)

_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[_canon(cfg.name)] = cfg
    return cfg


def _canon(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def get_arch(name: str) -> ArchConfig:
    key = _canon(name)
    if key not in _REGISTRY:
        importlib.import_module(f"repro.configs.{key}")
    return _REGISTRY[key]


def list_archs():
    return list(ARCH_IDS)
