"""Mixture-of-Experts FFN: token-choice top-k routing, with a capacity in
training and dropless on the serving path.

Routing (:meth:`MoESpec.route`): softmax over every expert of the layer,
the top k, their weights renormalised over those k.  Serving routes with
a float32 router matmul at ``HIGHEST`` precision (bfloat16 rounding of
the logits would flip near-tied choices); training keeps the default
precision.

A layer may hold only a share of its experts (``MoESpec.expert_first``,
``n_held``): one chip of an expert-parallel layer.  The router keeps its
full width and its top k; the layer computes only the held experts'
terms of the combine and leaves out what the experts held elsewhere
would add (model-configs section 4), so the shares' outputs sum to the
uncut layer's.

Training dispatch is scatter-based (MegaBlocks-style grouping rather
than the GShard (T, E, C) one-hot einsum): each selected (token, held
expert) pair gets a rank within its expert via a cumulative count; pairs
past the capacity are dropped (their combine weight contributes nothing,
matching capacity-bounded token-choice semantics).  The grouped
activations (E, C, d) then run through the held experts as one batched
einsum — the layout that experts-sharded (EP) meshes want, since the E
dimension is the sharding axis and the scatter/gather become all-to-alls
under GSPMD.

Serving (``serving=True``: prefill and decode) is dropless: the (token,
held expert) pairs, at most min(k, held) per token, are sorted by expert
and run as one grouped matmul per expert matrix
(``jax.lax.ragged_dot``), so no pair is dropped, every row's output
depends on that row alone, and the buffers hold min(k, held)·T rows
whatever the routing.  On the TPU ``ragged_dot`` lowers to XLA's own
ragged dot; elsewhere JAX lowers it as a dense dot over every group
(held times the rows), which is what a CPU dry run's memory sees.

Used by llama4-scout (16e top-1), dbrx (16e top-4) and mellum2 (64e
top-8, 8 held).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain_ep

from .layers import _he

__all__ = ["MoESpec", "init_moe", "moe_ffn"]


@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    d_ff: int
    n_experts: int  # the router's width: every expert of the layer
    top_k: int
    capacity_factor: float = 1.25
    min_capacity: int = 4
    router_z_coef: float = 1e-3
    token_chunk: int = 16_384  # dispatch chunk: bounds the (E, C, d)
    # grouped buffer at prefill scale (1M tokens would otherwise need a
    # 64 GiB scatter buffer); capacity is enforced per chunk
    expert_first: int = 0  # held experts: expert_first .. + held - 1
    n_held: int = 0  # 0 = every expert

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts

    def route(self, router, xf: jnp.ndarray, serving: bool = True):
        """(T, d) -> (logits (T, E), probs (T, E), weights (T, k), experts
        (T, k)): softmax over all E experts, top k, weights renormalised
        over the k.  ``serving`` computes the logits in float32 at
        ``HIGHEST``; training at the default precision."""
        if serving:
            logits = jnp.einsum(
                "td,de->te", xf.astype(jnp.float32),
                router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
        else:
            logits = jnp.einsum("td,de->te", xf, router,
                                preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, self.top_k)  # (T, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        return logits, probs, gate_vals, expert_idx

    def held_weights(self, gate_vals, expert_idx):
        """Per token and held expert: its combine weight (0 where the
        token was not routed to it) and whether it was routed there, both
        (T, held)."""
        sel = jax.nn.one_hot(expert_idx - self.expert_first, self.held,
                             dtype=jnp.float32)  # (T, k, held); others -> 0
        return (gate_vals[..., None] * sel).sum(1), sel.sum(1) > 0


def init_moe(key, spec: MoESpec):
    kr, kg, ku, kd = jax.random.split(key, 4)
    e, d, f = spec.held, spec.d_model, spec.d_ff
    return {
        "router": _he(kr, (d, spec.n_experts)),
        "w_gate": _he(kg, (e, d, f), scale_axis=1),
        "w_up": _he(ku, (e, d, f), scale_axis=1),
        "w_down": _he(kd, (e, f, d), scale_axis=1),
    }


def _capacity(tokens: int, spec: MoESpec) -> int:
    c = int(tokens * spec.top_k * spec.capacity_factor / spec.n_experts)
    return max(c, spec.min_capacity)


def moe_ffn(p, x: jnp.ndarray, spec: MoESpec, *, serving: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(B, S, d) -> ((B, S, d), aux_loss, routed (B, S, held) bool).
    aux = load-balance + z-loss.  ``serving``: dropless, with the router
    at ``HIGHEST`` (module docstring).

    Token streams longer than ``token_chunk`` are dispatched chunk by
    chunk (lax.scan): per-chunk capacity keeps the grouped (E, C, d)
    buffer bounded regardless of sequence length."""
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    if t > spec.token_chunk and t % spec.token_chunk == 0:
        nc = t // spec.token_chunk
        chunks = xf.reshape(nc, spec.token_chunk, d)

        def body(aux_acc, xc):
            yc, aux, routed = _moe_tokens(p, xc, spec, serving)
            return aux_acc + aux, (yc, routed)

        aux_sum, (ys, routed) = jax.lax.scan(body, 0.0, chunks)
        return (ys.reshape(b, s, d).astype(x.dtype), aux_sum / nc,
                routed.reshape(b, s, spec.held))
    out, aux, routed = _moe_tokens(p, xf, spec, serving)
    return (out.reshape(b, s, d).astype(x.dtype), aux,
            routed.reshape(b, s, spec.held))


def _moe_tokens(p, xf: jnp.ndarray, spec: MoESpec, serving: bool):
    """(T, d) -> ((T, d), aux, routed (T, held))."""
    logits, probs, gate_vals, expert_idx = spec.route(p["router"], xf,
                                                      serving)
    if serving:
        out = _dropless(p, xf, spec, gate_vals, expert_idx)
    else:
        out = _capacity_dispatch(p, xf, spec, gate_vals, expert_idx)

    # aux losses: Switch-style load balance over every expert + router
    # z-loss
    n = spec.n_experts
    me = probs.mean(axis=0)  # (E,)
    ce = (jax.nn.one_hot(expert_idx, n).sum(1) > 0).astype(jnp.float32
                                                          ).mean(axis=0)
    lb = n * jnp.sum(me * ce)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = lb + spec.router_z_coef * z
    return out, aux, spec.held_weights(gate_vals, expert_idx)[1]


def _dropless(p, xf, spec: MoESpec, gate_vals, expert_idx):
    """Every (token, held expert) pair through its expert, nothing
    dropped: the pairs sorted by expert (pairs routed to experts held
    elsewhere sort last and are cut off), each expert matrix one
    ``ragged_dot`` over the groups, the outputs weighted and added back
    by token; (T, d)."""
    t, d = xf.shape
    e, k = spec.held, spec.top_k
    local = (expert_idx - spec.expert_first).reshape(t * k)
    group = jnp.where((local >= 0) & (local < e), local, e)
    rows = min(k, e) * t  # a token picks an expert at most once
    order = jnp.argsort(group, stable=True)[:rows]
    sizes = jnp.bincount(group, length=e + 1)[:e].astype(jnp.int32)
    token = order // k
    gx = jnp.take(xf, token, axis=0)  # (rows, d), grouped by expert

    def grouped(a, w, dtype):
        return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=dtype)

    g = jax.nn.silu(grouped(gx, p["w_gate"], jnp.float32))
    u = grouped(gx, p["w_up"], jnp.float32)
    y = grouped((g * u).astype(xf.dtype), p["w_down"], xf.dtype)
    # rows past the held pairs belong to no group: their weight is 0
    held = jnp.arange(rows) < sizes.sum()
    w = jnp.where(held, gate_vals.reshape(t * k)[order], 0.0)
    y_w = jnp.where(held[:, None], y.astype(jnp.float32) * w[:, None], 0.0)
    return jnp.zeros((t, d), jnp.float32).at[token].add(y_w).astype(xf.dtype)


def _capacity_dispatch(p, xf, spec: MoESpec, gate_vals, expert_idx):
    """Training dispatch with a capacity: pairs past an expert's capacity
    are dropped; (T, d)."""
    t, d = xf.shape
    e, k = spec.held, spec.top_k
    cap = _capacity(t, spec)

    # rank of each (token, slot) within its held expert, computed via a
    # one-hot cumulative sum over the flattened (token-major) selection
    # order; a slot routed to an expert held elsewhere selects nothing
    local = expert_idx - spec.expert_first
    sel = jax.nn.one_hot(local, e, dtype=jnp.int32)  # (T, k, held)
    flat_sel = sel.reshape(t * k, e)
    rank = jnp.cumsum(flat_sel, axis=0) - flat_sel  # exclusive count
    rank = (rank * flat_sel).sum(-1).reshape(t, k)  # (T, k)
    keep = (rank < cap) & (sel.sum(-1) > 0)

    dest = local * cap + rank  # (T, k) slot in (E*C)
    dest = jnp.where(keep, dest, e * cap)  # dropped or not held

    # Dispatch via the INVERSE index: scatter token ids (4 bytes/slot)
    # instead of token vectors (2d bytes/slot), then gather rows.  The
    # big (E, C, d) buffer is then produced by a gather whose output is
    # EP-sharded, so under GSPMD the d-sized data crosses the mesh once
    # ((T, d) all-gather) rather than as a full (E, C, d) scatter
    # all-reduce — ~2kd/4 ≈ 3000x less index traffic and ~C·E/T less
    # payload traffic (the dbrx train cell's collective term dropped 4x).
    token_of = jnp.repeat(jnp.arange(t), k).reshape(t, k)
    inv = jnp.full((e * cap,), t, jnp.int32)  # t = zero-row sentinel
    inv = inv.at[dest.reshape(-1)].set(
        token_of.reshape(-1).astype(jnp.int32), mode="drop"
    )
    w_slot = jnp.zeros((e * cap,), jnp.float32)
    w_slot = w_slot.at[dest.reshape(-1)].set(
        (gate_vals * keep).reshape(-1).astype(jnp.float32), mode="drop"
    )
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
    gx = constrain_ep(jnp.take(xf_pad, inv, axis=0).reshape(e, cap, d))

    # expert FFN (SwiGLU), batched over experts
    g = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", gx, p["w_gate"], preferred_element_type=jnp.float32)
    )
    u = jnp.einsum("ecd,edf->ecf", gx, p["w_up"], preferred_element_type=jnp.float32)
    y = jnp.einsum(
        "ecf,efd->ecd", (g * u).astype(xf.dtype), p["w_down"],
        preferred_element_type=xf.dtype,
    ).astype(xf.dtype)
    y = constrain_ep(y)

    # combine: weight in place, scatter-add back by token id (drops land
    # on the sentinel row and are sliced off)
    y_w = y.reshape(e * cap, d).astype(jnp.float32) * w_slot[:, None]
    out = jnp.zeros((t + 1, d), jnp.float32).at[inv].add(y_w)[:t]
    out = out.astype(xf.dtype)
    return out
