"""Block assembly + layer stacks.

A model is a sequence of *blocks* tiled from a short *pattern*
(configs.base.ArchConfig.pattern).  The stack executes as

    scan over `reps` full repetitions of the pattern   (compact HLO)
  + an unrolled tail of `n_layers % len(pattern)` blocks.

All heterogeneous architectures reduce to this: gemma3 is
``(local×5, global)``, recurrentgemma ``(rec, rec, local)``, xLSTM
``(mlstm, slstm)``, llama4 ``(moe_chunked×3, moe_global)``, and dense
archs are a pattern of one.  Pattern-position parameters are stacked along
a leading ``reps`` axis (pytree leaves ``params["reps"][i]``), which the
sharding rules treat as a pure stacking dim.

Three regimes per block/stack, mirroring attention.py / recurrent.py:
``*_train`` (full sequence), ``*_prefill`` (full sequence + cache out),
``*_decode`` (one token + cache in/out).  ``stack_decode`` takes an
operator for every block's second residual branch (GUST serving plugs
its plans in there) and returns which held experts each row was routed
to in every expert layer.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain_activation

from . import attention as A
from . import recurrent as R
from .layers import apply_norm, init_mlp, init_norm, mlp
from .moe import MoESpec, init_moe, moe_ffn

__all__ = [
    "BlockCfg",
    "StackCfg",
    "make_block_cfg",
    "make_stack_cfg",
    "init_stack",
    "stack_train",
    "stack_prefill",
    "stack_decode",
    "init_stack_caches",
    "insert_slot_caches",
]


@dataclasses.dataclass(frozen=True)
class BlockCfg:
    kind: str  # attn_mlp | attn_moe | rec | mlstm | slstm | enc | xattn
    d_model: int
    norm_kind: str = "rms"
    mlp_kind: str = "swiglu"
    d_ff: int = 0
    attn: Optional[A.AttnSpec] = None
    cross: Optional[A.AttnSpec] = None
    moe: Optional[MoESpec] = None
    mlstm: Optional[R.MLSTMSpec] = None
    slstm: Optional[R.SLSTMSpec] = None
    rglru: Optional[R.RGLRUSpec] = None


def make_block_cfg(cfg: ArchConfig, block_type: str) -> BlockCfg:
    d = cfg.d_model
    base_attn = dict(
        d_model=d,
        n_heads=cfg.n_heads,
        n_kv=cfg.n_kv,
        d_head=cfg.head_dim,
        rope_theta=cfg.rope_theta,
        block_size=cfg.attn_block_size,
    )
    common = dict(d_model=d, norm_kind=cfg.norm_kind, mlp_kind=cfg.mlp_kind, d_ff=cfg.d_ff)

    if block_type in ("global", "moe_global"):
        attn = A.AttnSpec(mode="global", max_cache=cfg.global_cache_cap,
                          yarn=cfg.global_yarn, **base_attn)
    elif block_type in ("local", "moe_local"):
        attn = A.AttnSpec(mode="local", window=cfg.local_window, **base_attn)
    elif block_type in ("chunked", "moe_chunked"):
        attn = A.AttnSpec(mode="chunked", window=cfg.chunk_size, **base_attn)
    elif block_type == "enc":
        attn = A.AttnSpec(mode="global", causal=False, **base_attn)
    elif block_type == "xattn":
        attn = A.AttnSpec(mode="global", max_cache=cfg.global_cache_cap, **base_attn)
    else:
        attn = None

    if block_type.startswith("moe_"):
        moe = MoESpec(
            d_model=d,
            d_ff=cfg.d_ff,
            n_experts=cfg.n_experts,
            top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
            n_held=cfg.experts_held,
        )
        return BlockCfg(kind="attn_moe", attn=attn, moe=moe, **common)
    if block_type in ("global", "local", "chunked"):
        return BlockCfg(kind="attn_mlp", attn=attn, **common)
    if block_type == "enc":
        return BlockCfg(kind="enc", attn=attn, **common)
    if block_type == "xattn":
        cross = A.AttnSpec(mode="global", causal=False, use_rope=False, **base_attn)
        return BlockCfg(kind="xattn", attn=attn, cross=cross, **common)
    if block_type == "rec":
        return BlockCfg(kind="rec", rglru=R.RGLRUSpec(d_model=d), **common)
    if block_type == "mlstm":
        return BlockCfg(
            kind="mlstm",
            mlstm=R.MLSTMSpec(d_model=d, n_heads=cfg.n_heads, expand=cfg.mlstm_expand),
            **common,
        )
    if block_type == "slstm":
        return BlockCfg(
            kind="slstm", slstm=R.SLSTMSpec(d_model=d, n_heads=cfg.n_heads), **common
        )
    raise ValueError(f"unknown block type {block_type!r}")


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------


def init_block(key, bc: BlockCfg):
    ks = jax.random.split(key, 4)
    d = bc.d_model
    p = {}
    if bc.kind in ("attn_mlp", "attn_moe", "enc", "xattn"):
        p["ln_attn"] = init_norm(d, kind=bc.norm_kind)
        p["attn"] = A.init_attention(ks[0], bc.attn)
        if bc.kind == "xattn":
            p["ln_cross"] = init_norm(d, kind=bc.norm_kind)
            p["cross"] = A.init_attention(ks[1], bc.cross)
        p["ln_mlp"] = init_norm(d, kind=bc.norm_kind)
        if bc.kind == "attn_moe":
            p["moe"] = init_moe(ks[2], bc.moe)
        else:
            p["mlp"] = init_mlp(ks[2], d, bc.d_ff, kind=bc.mlp_kind)
    elif bc.kind == "rec":
        p["ln_rec"] = init_norm(d, kind=bc.norm_kind)
        p["rec"] = R.init_rglru(ks[0], bc.rglru)
        p["ln_mlp"] = init_norm(d, kind=bc.norm_kind)
        p["mlp"] = init_mlp(ks[1], d, bc.d_ff, kind=bc.mlp_kind)
    elif bc.kind == "mlstm":
        p["ln"] = init_norm(d, kind=bc.norm_kind)
        p["core"] = R.init_mlstm(ks[0], bc.mlstm)
    elif bc.kind == "slstm":
        p["ln"] = init_norm(d, kind=bc.norm_kind)
        p["core"] = R.init_slstm(ks[0], bc.slstm)
    else:
        raise ValueError(bc.kind)
    return p


def _ffn(p, x, bc: BlockCfg, serving: bool = False):
    """Second residual branch: MLP or MoE.  Returns (delta, aux, routed):
    ``routed`` (B, S, held) marks the held experts each token was routed
    to (None for an MLP).  ``serving`` routes without a capacity."""
    h = apply_norm(p["ln_mlp"], x, kind=bc.norm_kind)
    if bc.kind == "attn_moe":
        with jax.named_scope("moe"):  # routing, experts and combine
            return moe_ffn(p["moe"], h, bc.moe, serving=serving)
    return mlp(p["mlp"], h, kind=bc.mlp_kind), 0.0, None


def _decode_ffn(p, x, bc: BlockCfg, ffn, where, g):
    """The decode's second residual branch: ``ffn(where, bc, p, h, g)`` on
    the normed hidden state when an operator is plugged in (``where``:
    the block's id, ``"i"`` for pattern position i or ``"tailj"``; ``g``:
    its slice of the operands), else the block's own MLP or MoE.
    Returns (delta, routed (B, held) or None)."""
    if ffn is None:
        delta, _, routed = _ffn(p, x, bc, serving=True)
        return delta, None if routed is None else routed[:, 0]
    h = apply_norm(p["ln_mlp"], x, kind=bc.norm_kind)
    with (jax.named_scope("moe") if bc.kind == "attn_moe"
          else contextlib.nullcontext()):
        return ffn(where, bc, p, h, g)


def block_train(p, x, bc: BlockCfg, memory=None):
    aux = 0.0
    if bc.kind in ("attn_mlp", "attn_moe", "enc", "xattn"):
        h = apply_norm(p["ln_attn"], x, kind=bc.norm_kind)
        x = x + A.attend_train(p["attn"], h, bc.attn)
        if bc.kind == "xattn":
            h = apply_norm(p["ln_cross"], x, kind=bc.norm_kind)
            k, v = A.cross_kv(p["cross"], memory, bc.cross)
            x = x + A.attend_cross(p["cross"], h, k, v, bc.cross)
        delta, aux, _ = _ffn(p, x, bc)
        x = x + delta
    elif bc.kind == "rec":
        h = apply_norm(p["ln_rec"], x, kind=bc.norm_kind)
        x = x + R.rglru_train(p["rec"], h, bc.rglru)
        delta, aux, _ = _ffn(p, x, bc)
        x = x + delta
    elif bc.kind == "mlstm":
        h = apply_norm(p["ln"], x, kind=bc.norm_kind)
        x = x + R.mlstm_train(p["core"], h, bc.mlstm)
    elif bc.kind == "slstm":
        h = apply_norm(p["ln"], x, kind=bc.norm_kind)
        x = x + R.slstm_train(p["core"], h, bc.slstm)
    return x, aux


def init_block_cache(bc: BlockCfg, batch: int, seq_len: int, enc_seq: int = 0,
                     dtype=jnp.bfloat16):
    if bc.kind in ("attn_mlp", "attn_moe", "enc"):
        return A.init_cache(bc.attn, batch, seq_len, dtype)
    if bc.kind == "xattn":
        return {
            "self": A.init_cache(bc.attn, batch, seq_len, dtype),
            "ck": jnp.zeros((batch, enc_seq, bc.cross.n_kv, bc.cross.d_head), dtype),
            "cv": jnp.zeros((batch, enc_seq, bc.cross.n_kv, bc.cross.d_head), dtype),
        }
    if bc.kind == "rec":
        return R.rglru_init_state(bc.rglru, batch, dtype)
    if bc.kind == "mlstm":
        return R.mlstm_init_state(bc.mlstm, batch, dtype)
    if bc.kind == "slstm":
        return R.slstm_init_state(bc.slstm, batch, dtype)
    raise ValueError(bc.kind)


def block_prefill(p, x, bc: BlockCfg, cache, memory=None, start: int = 0):
    aux_unused = 0.0
    if bc.kind in ("attn_mlp", "attn_moe", "enc", "xattn"):
        h = apply_norm(p["ln_attn"], x, kind=bc.norm_kind)
        if bc.kind == "xattn":
            y, self_cache = A.prefill_into_cache(p["attn"], h, bc.attn, cache["self"], start)
            x = x + y
            hc = apply_norm(p["ln_cross"], x, kind=bc.norm_kind)
            k, v = A.cross_kv(p["cross"], memory, bc.cross)
            x = x + A.attend_cross(p["cross"], hc, k, v, bc.cross)
            cache = {
                "self": self_cache,
                "ck": k.astype(cache["ck"].dtype),
                "cv": v.astype(cache["cv"].dtype),
            }
        else:
            y, cache = A.prefill_into_cache(p["attn"], h, bc.attn, cache, start)
            x = x + y
        delta, _, _ = _ffn(p, x, bc, serving=True)
        x = x + delta
    elif bc.kind == "rec":
        h = apply_norm(p["ln_rec"], x, kind=bc.norm_kind)
        y, cache = R.rglru_train(p["rec"], h, bc.rglru, cache, return_state=True)
        x = x + y
        delta, _, _ = _ffn(p, x, bc, serving=True)
        x = x + delta
    elif bc.kind == "mlstm":
        h = apply_norm(p["ln"], x, kind=bc.norm_kind)
        y, cache = R.mlstm_train(p["core"], h, bc.mlstm, cache, return_state=True)
        x = x + y
    elif bc.kind == "slstm":
        h = apply_norm(p["ln"], x, kind=bc.norm_kind)
        y, cache = R.slstm_train(p["core"], h, bc.slstm, cache, return_state=True)
        x = x + y
    return x, cache


def block_decode(p, x, bc: BlockCfg, cache, pos, memory=None, ffn=None,
                 where="0", g=None):
    """One token through one block.  ``ffn``/``where``/``g``: the operator
    plugged into the second residual branch, the block's id and its
    operands (:func:`_decode_ffn`).  Returns (x, cache, routed or None)."""
    routed = None
    if bc.kind in ("attn_mlp", "attn_moe", "enc", "xattn"):
        h = apply_norm(p["ln_attn"], x, kind=bc.norm_kind)
        with jax.named_scope("attn"):  # attention and its KV update
            if bc.kind == "xattn":
                y, self_cache = A.decode_step(p["attn"], h, bc.attn,
                                              cache["self"], pos)
                x = x + y
                hc = apply_norm(p["ln_cross"], x, kind=bc.norm_kind)
                x = x + A.attend_cross(
                    p["cross"], hc, cache["ck"], cache["cv"], bc.cross
                )
                cache = {"self": self_cache, "ck": cache["ck"],
                         "cv": cache["cv"]}
            else:
                y, cache = A.decode_step(p["attn"], h, bc.attn, cache, pos)
                x = x + y
        delta, routed = _decode_ffn(p, x, bc, ffn, where, g)
        x = x + delta
    elif bc.kind == "rec":
        h = apply_norm(p["ln_rec"], x, kind=bc.norm_kind)
        y, cache = R.rglru_decode(p["rec"], h, bc.rglru, cache)
        x = x + y
        delta, routed = _decode_ffn(p, x, bc, ffn, where, g)
        x = x + delta
    elif bc.kind == "mlstm":
        h = apply_norm(p["ln"], x, kind=bc.norm_kind)
        y, cache = R.mlstm_decode(p["core"], h, bc.mlstm, cache)
        x = x + y
    elif bc.kind == "slstm":
        h = apply_norm(p["ln"], x, kind=bc.norm_kind)
        y, cache = R.slstm_decode(p["core"], h, bc.slstm, cache)
        x = x + y
    return x, cache, routed


# ---------------------------------------------------------------------------
# Stack = scan over pattern repetitions + unrolled tail
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StackCfg:
    pattern: Tuple[BlockCfg, ...]
    reps: int
    n_tail: int  # tail blocks reuse pattern[:n_tail] configs
    enc_seq: int = 0

    @property
    def n_layers(self) -> int:
        return self.reps * len(self.pattern) + self.n_tail


def make_stack_cfg(cfg: ArchConfig, pattern: Tuple[str, ...], n_layers: int) -> StackCfg:
    blocks = tuple(make_block_cfg(cfg, t) for t in pattern)
    reps = n_layers // len(pattern)
    n_tail = n_layers % len(pattern)
    return StackCfg(pattern=blocks, reps=reps, n_tail=n_tail, enc_seq=cfg.enc_seq)


def init_stack(key, sc: StackCfg):
    k_reps, k_tail = jax.random.split(key)
    rep_params = []
    for i, bc in enumerate(sc.pattern):
        keys = jax.random.split(jax.random.fold_in(k_reps, i), sc.reps)
        rep_params.append(jax.vmap(lambda k, b=bc: init_block(k, b))(keys))
    tail_params = [
        init_block(jax.random.fold_in(k_tail, i), sc.pattern[i])
        for i in range(sc.n_tail)
    ]
    return {"reps": tuple(rep_params), "tail": tail_params}


def stack_train(params, x, sc: StackCfg, memory=None, remat: bool = True):
    def body(carry, xs):
        x, aux = carry
        # gather the sequence-sharded saved carry once per block; compute
        # inside the block stays batch-sharded (avoids the per-op
        # resharding storm of full sequence parallelism)
        x = constrain_activation(x, "btd_gather")
        for i, bc in enumerate(sc.pattern):
            x, a = block_train(xs[i], x, bc, memory)
            aux = aux + a
        # the carry is the remat save point; under an SP activation ctx it
        # is STORED sequence-sharded (model-axis-times smaller per chip)
        x = constrain_activation(x, "btd_save")
        return (x, aux), None

    if remat:
        # Full recompute: save only the (bf16) layer-boundary carries.
        # Dot-saving policies keep f32 pre-cast projection outputs per
        # layer — 8-16x the carry footprint (see EXPERIMENTS.md §Perf).
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, 0.0), params["reps"])
    for i in range(sc.n_tail):
        blk = block_train
        if remat:
            blk = jax.checkpoint(block_train, static_argnums=(2,))
        x, a = blk(params["tail"][i], x, sc.pattern[i], memory)
        aux = aux + a
    return x, aux


def init_stack_caches(sc: StackCfg, batch: int, seq_len: int, dtype=jnp.bfloat16):
    rep_caches = []
    for bc in sc.pattern:
        one = init_block_cache(bc, batch, seq_len, sc.enc_seq, dtype)
        rep_caches.append(
            jax.tree.map(lambda a: jnp.broadcast_to(a, (sc.reps,) + a.shape).copy(), one)
        )
    tail_caches = [
        init_block_cache(sc.pattern[i], batch, seq_len, sc.enc_seq, dtype)
        for i in range(sc.n_tail)
    ]
    return {"reps": tuple(rep_caches), "tail": tail_caches}


def insert_slot_caches(caches, one, slot):
    """Serving admission: copy batch row 0 of a batch-1 stack-cache pytree
    into batch row ``slot`` of the full stack cache (all layers, attention
    KV + recurrent states alike).  Rep-stacked leaves carry batch at axis
    1 (``(R, B, ...)``), tail leaves at axis 0."""
    reps = tuple(
        A.insert_slot(cf, co, slot, axis=1)
        for cf, co in zip(caches["reps"], one["reps"])
    )
    tail = [
        A.insert_slot(cf, co, slot, axis=0)
        for cf, co in zip(caches["tail"], one["tail"])
    ]
    return {"reps": reps, "tail": tail}


def stack_prefill(params, x, sc: StackCfg, caches, memory=None, start: int = 0):
    def body(x, xs):
        p_sl, c_sl = xs
        new_c = []
        for i, bc in enumerate(sc.pattern):
            x, c = block_prefill(p_sl[i], x, bc, c_sl[i], memory, start)
            new_c.append(c)
        return x, tuple(new_c)

    x, rep_caches = jax.lax.scan(body, x, (params["reps"], caches["reps"]))
    tail_caches = []
    for i in range(sc.n_tail):
        x, c = block_prefill(
            params["tail"][i], x, sc.pattern[i], caches["tail"][i], memory, start
        )
        tail_caches.append(c)
    return x, {"reps": rep_caches, "tail": tail_caches}


def stack_decode(params, x, sc: StackCfg, caches, pos, memory=None, ffn=None,
                 ffn_xs=None):
    """One token through the stack.  ``ffn(where, bc, p, h, g)`` replaces
    each block's second residual branch (``where`` the block's id: ``"i"``
    for pattern position i, ``"tailj"`` for tail block j; ``h`` the normed
    hidden state; ``g`` the block's slice of ``ffn_xs``, laid out as ``params``:
    ``{"reps": per-position rep-stacked pytrees, "tail": per-block}``;
    None where a block has none).  Returns (x, caches, routes): routes
    (L, B, held) marks, for each expert layer in depth order, the held
    experts each row was routed to; None without expert layers."""
    if ffn_xs is None:
        ffn_xs = {"reps": (None,) * len(sc.pattern), "tail": [None] * sc.n_tail}

    def body(x, xs):
        p_sl, c_sl, g_sl = xs
        new_c, routes = [], []
        for i, bc in enumerate(sc.pattern):
            x, c, r = block_decode(p_sl[i], x, bc, c_sl[i], pos, memory, ffn,
                                   str(i), g_sl[i])
            new_c.append(c)
            if r is not None:
                routes.append(r)
        return x, (tuple(new_c), tuple(routes))

    x, (rep_caches, rep_routes) = jax.lax.scan(
        body, x, (params["reps"], caches["reps"], tuple(ffn_xs["reps"])))
    # depth order: rep r's expert layers, then rep r + 1's, then the tail
    routes = ([jnp.stack(rep_routes, axis=1).reshape(
        (-1,) + rep_routes[0].shape[1:])] if rep_routes else [])
    tail_caches = []
    for i in range(sc.n_tail):
        x, c, r = block_decode(
            params["tail"][i], x, sc.pattern[i], caches["tail"][i], pos,
            memory, ffn, f"tail{i}", ffn_xs["tail"][i],
        )
        tail_caches.append(c)
        if r is not None:
            routes.append(r[None])
    routes = jnp.concatenate(routes) if routes else None
    return x, {"reps": rep_caches, "tail": tail_caches}, routes
