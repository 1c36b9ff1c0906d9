"""Core neural layers (functional, pytree params, pure jnp).

Everything is written as ``init_*(key, ...) -> params`` plus a pure apply
function, so models compose into plain pytrees that pjit/GSPMD shards via
the rules in :mod:`repro.distributed.sharding`.  No framework dependency.

Conventions:
  * activations are (B, S, d) (batch, sequence, features);
  * params are f32 by default; the train loop may cast to bf16 compute via
    the ``dtype`` threading in :mod:`repro.training.train_loop`;
  * matmuls accumulate in f32 (``preferred_element_type``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "init_linear",
    "linear",
    "init_norm",
    "rms_norm",
    "layer_norm",
    "init_embedding",
    "embed",
    "unembed",
    "rope",
    "init_mlp",
    "mlp",
]


def _he(key, shape, scale_axis=0, dtype=jnp.float32):
    fan_in = shape[scale_axis]
    return jax.random.normal(key, shape, dtype) * (1.0 / jnp.sqrt(fan_in))


# ---------------------------------------------------------------------------
# Linear / norm / embedding
# ---------------------------------------------------------------------------


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False):
    p = {"w": _he(key, (d_in, d_out))}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def linear(p, x: jnp.ndarray) -> jnp.ndarray:
    y = jnp.einsum("...i,io->...o", x, p["w"], preferred_element_type=jnp.float32)
    if "b" in p:
        y = y + p["b"]
    return y.astype(x.dtype)


def init_norm(d: int, *, kind: str = "rms"):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layer":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def rms_norm(p, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


def layer_norm(p, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def apply_norm(p, x, *, kind: str = "rms"):
    return rms_norm(p, x) if kind == "rms" else layer_norm(p, x)


def init_embedding(key, vocab: int, d: int):
    return {"table": jax.random.normal(key, (vocab, d), jnp.float32) * 0.02}


def embed(p, tokens: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.take(p["table"], tokens, axis=0).astype(dtype)


def unembed(p, x: jnp.ndarray) -> jnp.ndarray:
    """Tied logits projection: (B, S, d) @ table^T -> (B, S, V), f32."""
    return jnp.einsum(
        "...d,vd->...v", x, p["table"], preferred_element_type=jnp.float32
    )


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(
    x: jnp.ndarray, positions: jnp.ndarray, *, theta: float = 10_000.0,
    yarn=None,
) -> jnp.ndarray:
    """Rotary embedding.  x: (..., S, H, D), positions: broadcastable (S,)
    or (B, S).  With ``yarn`` (a ``configs.base.Yarn``) the frequencies
    are its ``inv_freq`` and cos/sin carry its attention factor."""
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freq, scale = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half), 1.0
    else:
        freq, scale = jnp.asarray(yarn.inv_freq(d, theta)), yarn.attention_factor
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * freq[None, :]  # (S, half)
        ang = ang[None, :, None, :]  # (1, S, 1, half)
    else:
        ang = positions.astype(jnp.float32)[..., None] * freq  # (B, S, half)
        ang = ang[:, :, None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if yarn is not None:
        sin, cos = sin * scale, cos * scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain GELU)
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, d_ff: int, *, kind: str = "swiglu"):
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "w_gate": _he(k1, (d, d_ff)),
            "w_up": _he(k2, (d, d_ff)),
            "w_down": _he(k3, (d_ff, d)),
        }
    return {"w_up": _he(k1, (d, d_ff)), "w_down": _he(k2, (d_ff, d))}


def mlp(p, x: jnp.ndarray, *, kind: str = "swiglu") -> jnp.ndarray:
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
        g = act(
            jnp.einsum("...d,df->...f", x, p["w_gate"], preferred_element_type=jnp.float32)
        )
        u = jnp.einsum("...d,df->...f", x, p["w_up"], preferred_element_type=jnp.float32)
        h = (g * u).astype(x.dtype)
    else:
        h = jax.nn.gelu(
            jnp.einsum("...d,df->...f", x, p["w_up"], preferred_element_type=jnp.float32)
        ).astype(x.dtype)
    # row-parallel (f sharded over "model"): reduce partial sums on the
    # wire in the activation dtype (Megatron-style bf16 TP all-reduce; the
    # MXU still accumulates f32 within a chip)
    return jnp.einsum("...f,fd->...d", h, p["w_down"],
                      preferred_element_type=x.dtype)
