"""Attention: GQA with global / sliding-window / chunked-local masking.

Layout is TP-first: query/output heads live on a single flat ``H`` axis
(shardable over the "model" mesh axis whenever ``H % tp == 0``), and the
``KV`` heads are broadcast to ``H`` at compute time (``repeat``), so no
einsum ever reshapes a sharded dimension — the MaxText-style GQA
formulation.  KV caches store only the ``KV`` heads.

Three execution regimes, one parameter set:

  * ``attend_train``  — full-sequence causal attention.  For long
    sequences a blocked online-softmax formulation (lax.scan over KV
    blocks) keeps peak memory at O(S·T) instead of O(S²) — the pure-JAX
    equivalent of flash attention, which XLA maps onto MXU-friendly
    block matmuls.
  * ``prefill*``      — train-shaped pass that also materializes the KV
    cache (dense, or ring-buffer for bounded-window layers).
  * ``decode_step``   — one new token against the cache; positions are
    tracked explicitly so ring buffers mask correctly.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import Yarn
from repro.distributed.sharding import constrain_attn, constrain_kv_cache

from .layers import _he, rope

__all__ = [
    "AttnSpec",
    "init_attention",
    "attend_train",
    "init_cache",
    "insert_slot",
    "prefill_into_cache",
    "decode_step",
    "cross_kv",
    "attend_cross",
]


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    mode: str = "global"  # global | local | chunked
    window: int = 0  # window size (local) or chunk size (chunked)
    rope_theta: float = 10_000.0
    yarn: Optional[Yarn] = None  # YaRN-scaled rope (global layers)
    use_rope: bool = True
    causal: bool = True  # False for encoder self-attention
    block_size: int = 1024  # KV block for the online-softmax path
    max_cache: int = 0  # decode-cache capacity for global layers (0 = seq)

    @property
    def groups(self) -> int:
        return self.n_heads // self.n_kv


def init_attention(key, spec: AttnSpec):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, hq, hk, dh = spec.d_model, spec.n_heads, spec.n_kv, spec.d_head
    return {
        "wq": _he(kq, (d, hq, dh)),
        "wk": _he(kk, (d, hk, dh)),
        "wv": _he(kv, (d, hk, dh)),
        "wo": _he(ko, (hq, dh, d), scale_axis=1),
    }


def _expand_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(B, S, KV, dh) -> (B, S, H, dh); head h reads kv head h // groups."""
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def _qkv(p, x, spec: AttnSpec, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"], preferred_element_type=jnp.float32)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"], preferred_element_type=jnp.float32)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"], preferred_element_type=jnp.float32)
    q, k, v = q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype)
    if spec.use_rope:
        q = rope(q, positions, theta=spec.rope_theta, yarn=spec.yarn)
        k = rope(k, positions, theta=spec.rope_theta, yarn=spec.yarn)
    return q, k, v


def _mask(spec: AttnSpec, qpos, kpos):
    """Boolean (Sq, Sk) mask from query/key positions (int32)."""
    valid = kpos[None, :] >= 0
    if spec.causal:
        m = kpos[None, :] <= qpos[:, None]
    else:
        m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if spec.mode == "local" and spec.window:
        m &= kpos[None, :] > qpos[:, None] - spec.window
    elif spec.mode == "chunked" and spec.window:
        m &= (kpos[None, :] // spec.window) == (qpos[:, None] // spec.window)
    return m & valid


def _sdpa(q, k_full, v_full, mask, d_head):
    """Direct path. q: (B,Sq,H,dh), k_full/v_full: (B,Sk,H,dh)."""
    scale = 1.0 / jnp.sqrt(d_head).astype(jnp.float32)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k_full, preferred_element_type=jnp.float32)
    s = jnp.where(mask[None, None], s * scale, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqs,bshk->bqhk", p.astype(v_full.dtype), v_full)


def _blocked_sdpa(q, k_full, v_full, spec: AttnSpec, qpos, kpos):
    """Online-softmax over KV blocks; O(S·T) live memory."""
    b, sq, h, dh = q.shape
    sk = k_full.shape[1]
    t = min(spec.block_size, sk)
    nb = -(-sk // t)
    pad = nb * t - sk
    if pad:
        k_full = jnp.pad(k_full, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_full = jnp.pad(v_full, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, (0, pad), constant_values=-1)
    kb = k_full.reshape(b, nb, t, h, dh).transpose(1, 0, 2, 3, 4)
    vb = v_full.reshape(b, nb, t, h, dh).transpose(1, 0, 2, 3, 4)
    pb = kpos.reshape(nb, t)
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)

    def step(carry, blk):
        m_run, l_run, acc = carry
        kj, vj, pj = blk
        s = (
            jnp.einsum("bqhk,bthk->bhqt", q, kj, preferred_element_type=jnp.float32)
            * scale
        )
        msk = _mask(spec, qpos, pj)  # (Sq, T)
        s = jnp.where(msk[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m_run), m_run - m_safe, -jnp.inf))
        corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
        l_new = l_run * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqt,bthk->bhqk", p, vj.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    q = constrain_attn(q, 2, 1)  # (B, Sq, H, dh): TP on heads or SP on Sq
    m0 = constrain_attn(jnp.full((b, h, sq), -jnp.inf, jnp.float32), 1, 2)
    l0 = constrain_attn(jnp.zeros((b, h, sq), jnp.float32), 1, 2)
    a0 = constrain_attn(jnp.zeros((b, h, sq, dh), jnp.float32), 1, 2)
    (m_f, l_f, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb))
    o = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return o.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,Sq,H,dh)


def _attend(p, q, k, v, spec: AttnSpec, qpos, kpos, x_dtype):
    kf = _expand_kv(k, spec.groups)
    vf = _expand_kv(v, spec.groups)
    sq, sk = q.shape[1], kf.shape[1]
    if max(sq, sk) <= 2 * spec.block_size:
        o = _sdpa(q, kf, vf, _mask(spec, qpos, kpos), spec.d_head)
    else:
        o = _blocked_sdpa(q, kf, vf, spec, qpos, kpos)
    # row-parallel over heads: bf16 wire reduction (see layers.mlp)
    return jnp.einsum(
        "bqhk,hkd->bqd", o, p["wo"], preferred_element_type=x_dtype
    ).astype(x_dtype)


def attend_train(p, x, spec: AttnSpec, positions=None) -> jnp.ndarray:
    """Full-sequence attention (training / encoder / prefill compute)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    q, k, v = _qkv(p, x, spec, positions)
    return _attend(p, q, k, v, spec, positions, positions, x.dtype)


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder)
# ---------------------------------------------------------------------------


def cross_kv(p, memory, spec: AttnSpec):
    """Project the encoder memory once; reused by every decode step."""
    k = jnp.einsum(
        "bsd,dhk->bshk", memory, p["wk"], preferred_element_type=jnp.float32
    ).astype(memory.dtype)
    v = jnp.einsum(
        "bsd,dhk->bshk", memory, p["wv"], preferred_element_type=jnp.float32
    ).astype(memory.dtype)
    return k, v


def attend_cross(p, x, k, v, spec: AttnSpec) -> jnp.ndarray:
    """Full (non-causal, non-rotary) attention of x over precomputed
    memory K/V.  x: (B, Sq, d); k/v: (B, Sk, KV, dh).  Long memories go
    through the blocked online-softmax path like self-attention."""
    q = jnp.einsum(
        "bsd,dhk->bshk", x, p["wq"], preferred_element_type=jnp.float32
    ).astype(x.dtype)
    qpos = jnp.arange(q.shape[1], dtype=jnp.int32)
    kpos = jnp.arange(k.shape[1], dtype=jnp.int32)
    kf = _expand_kv(k.astype(q.dtype), spec.groups)
    vf = _expand_kv(v.astype(q.dtype), spec.groups)
    if max(q.shape[1], kf.shape[1]) <= 2 * spec.block_size:
        o = _sdpa(q, kf, vf, _mask(spec, qpos, kpos), spec.d_head)
    else:
        o = _blocked_sdpa(q, kf, vf, spec, qpos, kpos)
    return jnp.einsum(
        "bqhk,hkd->bqd", o, p["wo"], preferred_element_type=x.dtype
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# KV cache (dense or ring) + decode
# ---------------------------------------------------------------------------


def cache_len(spec: AttnSpec, seq_len: int) -> int:
    """Physical cache capacity for a layer at a given serving seq_len."""
    if spec.mode in ("local", "chunked") and spec.window:
        return min(spec.window, seq_len)
    if spec.max_cache:
        return min(spec.max_cache, seq_len)
    return seq_len


def init_cache(spec: AttnSpec, batch: int, seq_len: int, dtype=jnp.bfloat16):
    c = cache_len(spec, seq_len)
    shape = (batch, c, spec.n_kv, spec.d_head)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        # original position per cache slot, per sequence: (B, c) so batch
        # rows at different decode positions (continuous batching) mask
        # independently — every cache leaf is batch-leading
        "pos": jnp.full((batch, c), -1, jnp.int32),
    }


def insert_slot(cache, one, slot, axis: int = 0):
    """Slot-local cache insertion: write batch row 0 of the batch-1 cache
    pytree ``one`` into batch row ``slot`` of ``cache``, leaving every
    other row untouched.

    Every cache leaf — dense/ring KV (``k``/``v``/``pos``), cross-attn
    memory (``ck``/``cv``), and the recurrent states — is batch-leading
    (at ``axis``; rep-stacked leaves are ``(R, B, ...)`` so pass
    ``axis=1``), which makes admission in the serving loop a pure pytree
    row scatter: a new request's prefill can never clobber another active
    slot's cache.
    """

    def ins(full, single):
        src = jax.lax.index_in_dim(single, 0, axis=axis, keepdims=False)
        idx = (slice(None),) * axis + (slot,)
        return full.at[idx].set(src.astype(full.dtype))

    return jax.tree.map(ins, cache, one)


def prefill_into_cache(p, x, spec: AttnSpec, cache, start: int = 0):
    """Run attention over a prompt of length S and fill the cache with the
    final ``cache_len`` positions.  Returns (output, cache)."""
    b, s, _ = x.shape
    positions = start + jnp.arange(s, dtype=jnp.int32)
    q, k, v = _qkv(p, x, spec, positions)
    out = _attend(p, q, k, v, spec, positions, positions, x.dtype)

    c = cache["k"].shape[1]
    take = min(c, s)
    tail_pos = positions[s - take :]
    slots = tail_pos % c  # ring placement; identity when c >= S
    cache = {
        "k": cache["k"].at[:, slots].set(k[:, s - take :].astype(cache["k"].dtype)),
        "v": cache["v"].at[:, slots].set(v[:, s - take :].astype(cache["v"].dtype)),
        "pos": cache["pos"].at[:, slots].set(tail_pos),
    }
    return out, cache


def decode_step(p, x, spec: AttnSpec, cache, pos):
    """One token: x (B, 1, d); ``pos`` is a scalar or a (B,) vector of
    per-sequence positions (continuous batching serves mixed-length
    requests, so every batch row decodes at its own position).  Returns
    (y, cache)."""
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.full((b,), pos, jnp.int32)
    positions = pos[:, None]  # (B, 1): per-row rope + mask query positions
    q, k, v = _qkv(p, x, spec, positions)
    c = cache["k"].shape[1]
    slot = pos % c  # (B,) ring placement per sequence
    bidx = jnp.arange(b)
    kc = constrain_kv_cache(
        cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
    )
    vc = constrain_kv_cache(
        cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
    )
    pc = cache["pos"].at[bidx, slot].set(pos)

    # Flash-decode sharding: the cache is the big tensor, so the compute
    # follows ITS layout (sequence over "model").  GQA scores are taken in
    # (KV, G) form — the cache is never head-expanded (an _expand_kv here
    # makes GSPMD reshard/replicate the whole 88-layer stack per step);
    # only the one-token q is reshaped/resharded.  The softmax reduces
    # over the sharded cache length via psums of (B,KV,G,1)-sized partials.
    q5 = q.reshape(b, 1, spec.n_kv, spec.groups, spec.d_head)
    scale = 1.0 / jnp.sqrt(spec.d_head).astype(jnp.float32)
    s = (
        jnp.einsum(
            "bqegk,bsek->begqs", q5, kc.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # (B, KV, G, 1, c)
    # per-row mask: row i attends under its own query position pos[i]
    # against its own cached key positions pc[i]
    msk = jax.vmap(lambda qp, kp: _mask(spec, qp, kp))(positions, pc)  # (B,1,c)
    s = jnp.where(msk[:, None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    o = jnp.einsum(
        "begqs,bsek->bqegk", w.astype(q.dtype), vc.astype(q.dtype)
    ).reshape(b, 1, spec.n_heads, spec.d_head)
    y = jnp.einsum(
        "bqhk,hkd->bqd", o, p["wo"], preferred_element_type=x.dtype
    ).astype(x.dtype)
    return y, {"k": kc, "v": vc, "pos": pc}
