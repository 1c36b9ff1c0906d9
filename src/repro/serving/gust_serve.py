"""GUST-sparse serving: the paper's technique as a first-class feature.

Decode-time LM inference is matvec-dominated.  ``gustify`` converts a
trained model's MLP weights into GUST plans (magnitude pruning ->
``repro.plan`` -> packed blocks), **once**, at weight-load time — the
paper's §3.3/§5.3 amortization ("the scheduling for each matrix only
needs to be computed once ... even if the vector changes").
``decode_step_gust`` then mirrors the model's decode step but routes each
layer's MLP matvecs through :meth:`GustPlan.spmm`.

Layer stacking is :meth:`GustPlan.stack`: per-layer packed artifacts are
equalized to a uniform stream length (padded layout: uniform C_pad via
``repad_to``; ragged layout: uniform block count via ``repad_to_blocks``)
so the leaves stack along the reps axis and the layer scan stays a single
compact HLO — the GUST plan is literally part of the serving checkpoint.
With ``GustServeConfig.ragged`` the stack holds ragged color-block
streams, so skewed pruned matrices stop streaming dead padding cycles
through every decode step.  The wire format is the plan's
``to_spec``/``from_spec`` leaves/meta codec, shared with ``dryrun_specs``.

Applies to pattern-length-1 dense archs (phi3/yi/mistral-large/llava/
gemma3 would need per-position stacks — gemma3 and the MoE archs run the
per-expert variant documented in DESIGN.md §5).  ``dryrun_specs`` sizes
the schedule stream from the paper's Eq. 9 bound
(:meth:`GustPlan.spec_for`) so the 512-chip dry-run lowers the GUST
decode path without running the scheduler.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.bounds import expected_colors_bound
from repro.core.formats import COOMatrix
from repro.core.gust_linear import prune_by_magnitude
from repro.core.packing import default_cache, stacked_leaf_specs
from repro.core.plan import GustPlan, PlanConfig, plan
from repro.core.plan_store import PlanStore
from repro.core.scheduler import sched_counters
from repro.core.spans import Span
from repro.models import transformer as T
from repro.models.layers import apply_norm
from repro.models.model_zoo import LM
from repro.resilience.fallback import fallback_counters

__all__ = ["GustServeConfig", "gustify", "decode_step_gust", "dryrun_specs"]

_MLP_MATS = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class GustServeConfig:
    enable: bool = True
    density: float = 0.1
    gust_length: int = 256
    load_balance: bool = True
    method: str = "fast"
    use_kernel: bool = False  # Pallas path (interpret on CPU) vs XLA path
    compact: bool = False  # bf16 values + int16 indices: 12 -> 6 B/slot,
    # the TPU analogue of the paper's (64 + log l)-bit packed stream
    ragged: bool = False  # ragged color-block streams: per-layer stacks
    # hold only real cycle blocks (pruned LLM matrices are skewed — the
    # padded layout streams every window at the heaviest window's C_pad)
    gather: str = "auto"  # Buffer-Filler mode: "resident" (whole x in
    # VMEM), "local" (stream only each block's S_blk referenced x tiles —
    # the wide-d_ff fast path), or "auto" (measured locality ratio)
    plan_store: Optional[str] = None  # directory for the persistent
    # PlanStore: warm server starts load packed plans off disk instead of
    # re-paying the edge coloring (the paper's §5.3 amortization extended
    # across process boundaries)
    store_verify: str = "off"  # "load" runs the static artifact verifier
    # (repro.analysis) on every store read: a failing artifact is a
    # counted corrupt miss and gets re-packed — never served, never an
    # exception
    mats: Tuple[str, ...] = _MLP_MATS

    @property
    def value_dtype(self):
        return jnp.bfloat16 if self.compact else jnp.float32

    @property
    def index_dtype(self):
        return jnp.int16 if self.compact else jnp.int32

    @property
    def plan_config(self) -> PlanConfig:
        """These knobs in the one canonical spelling — every serving path
        (gustify, decode, dry-run specs) plans through this config."""
        return PlanConfig(
            l=self.gust_length,
            colorer=self.method,
            load_balance=self.load_balance,
            c_blk=8,
            layout="ragged" if self.ragged else "padded",
            backend="pallas" if self.use_kernel else "jnp",
            gather=self.gather,
            value_dtype=jnp.dtype(self.value_dtype).name,
            index_dtype=jnp.dtype(self.index_dtype).name,
        )


def _prune_to_coo(w: np.ndarray, cfg: GustServeConfig) -> COOMatrix:
    """w: (d_in, d_out) layer weight; GUST computes y = M x with
    M = w^T (d_out, d_in)."""
    m = prune_by_magnitude(np.asarray(w, np.float32).T, cfg.density)
    rows, cols = np.nonzero(m)
    return COOMatrix(m.shape, rows.astype(np.int64), cols.astype(np.int64),
                     m[rows, cols].astype(np.float32))


def _plan_cycles(p: GustPlan) -> int:
    """Cycle count for stats: store-loaded plans carry no GustSchedule
    (the coloring never ran), only the persisted ``summary`` sidecar."""
    if p.sched is not None:
        return int(p.sched.cycles)
    if p.summary is not None and "cycles" in p.summary:
        return int(p.summary["cycles"])
    return -1  # loaded artifact predates summary sidecars


def gustify(lm: LM, params, cfg: GustServeConfig, *,
            store: Optional[PlanStore] = None) -> Dict:
    """Build stacked GUST plans for every rep-layer MLP matrix.

    Returns ``{"mats": {name: {"leaves": {...(R, ...)}, "meta": static
    layout tuple}}, "stats": {...}}`` — per matrix, the
    :meth:`GustPlan.stack` of one plan per layer.  ``stats`` holds one
    entry per matrix, ``gustify_s`` (this call's host seconds) and
    ``build_s``: the seconds of its phases, each a span
    (:class:`~repro.core.spans.Span`) — ``prune`` (weights to the host,
    magnitude pruning), ``colour`` and ``pack`` (``repro.plan``'s spans,
    read off ``sched_counters``), ``stack`` (equalize and stack the
    layers) and ``upload`` (wait until the stacked leaves are on the
    device).

    With ``cfg.plan_store`` (or an explicit ``store``), plans read
    through the persistent :class:`PlanStore`: a warm start rebuilds
    every stacked artifact from disk with zero coloring work.
    """
    if len(lm.stack.pattern) != 1 or lm.stack.pattern[0].kind != "attn_mlp":
        raise ValueError(
            "gustify currently targets homogeneous dense stacks "
            f"(got pattern {[b.kind for b in lm.stack.pattern]})"
        )
    if store is None and cfg.plan_store is not None:
        store = PlanStore(cfg.plan_store, verify=cfg.store_verify)
    t0 = time.perf_counter()
    mlp_params = params["stack"]["reps"][0]["mlp"]
    reps = lm.stack.reps
    pc = cfg.plan_config
    phases = dict.fromkeys(("prune", "colour", "pack", "stack", "upload"), 0.0)
    out: Dict = {"mats": {}, "stats": {}}
    fb0 = dict(fallback_counters)  # attribute downgrades to this build
    sc0 = {k: sched_counters[k] for k in ("colour_s", "pack_s")}
    plans_of = {}
    for name in cfg.mats:
        with Span("build.prune", phases, "prune"):
            w_stack = np.asarray(mlp_params[name])  # (R, d_in, d_out)
        # one plan per layer, through the content-keyed cache: re-gustifying
        # the same weights (e.g. a compact re-export) reuses the schedule
        plans = []
        for r in range(reps):
            with Span("build.prune", phases, "prune"):
                coo = _prune_to_coo(w_stack[r], cfg)
            plans.append(plan(coo, pc, cache=default_cache, store=store))
        arts = [p.artifact for p in plans]  # packs each layer (build.pack)
        with Span("build.stack", phases, "stack"):
            out["mats"][name] = GustPlan.stack(arts)
        plans_of[name] = plans
    with Span("build.upload", phases, "upload"):
        jax.block_until_ready([m["leaves"] for m in out["mats"].values()])
    for name, plans in plans_of.items():
        # uniform stream size after stacking = max over layers (stack()
        # equalizes to it); read off the artifacts, not meta positions
        if cfg.ragged:
            size_stat = {
                "num_blocks": max(p.artifact.num_blocks for p in plans)
            }
        else:
            size_stat = {"c_pad": max(p.artifact.c_pad for p in plans)}
        leaves = out["mats"][name]["leaves"]
        nnz = int(np.count_nonzero(np.asarray(leaves["m_blk"])))
        slots = leaves["m_blk"].size
        out["stats"][name] = {
            "cycles_per_layer": [_plan_cycles(p) for p in plans],
            "stream_utilization": nnz / max(slots, 1),
            "streamed_slots": int(slots),
            **size_stat,
        }
    phases["colour"] = sched_counters["colour_s"] - sc0["colour_s"]
    phases["pack"] = sched_counters["pack_s"] - sc0["pack_s"]
    out["stats"]["build_s"] = phases
    if store is not None:
        out["stats"]["plan_store"] = store.stats()
    fb = {k: v - fb0[k] for k, v in fallback_counters.items() if v - fb0[k]}
    if fb:
        # degradations applied while building (e.g. stored -> fresh on a
        # failing store read): counted, surfaced, never an exception
        out["stats"]["fallbacks"] = fb
    out["stats"]["gustify_s"] = time.perf_counter() - t0
    return out


def _gust_mlp(gust_slice, metas, x, mlp_kind: str, cfg: GustServeConfig):
    """x: (B, 1, d).  SwiGLU/GeGLU with every matvec through GUST."""
    b = x.shape[0]
    xt = x[:, 0].T.astype(jnp.float32)  # (d, B)
    act = jax.nn.silu if mlp_kind == "swiglu" else jax.nn.gelu
    pc = cfg.plan_config

    def mv(name, v):
        # one layer's slice of the stacked plan, rebuilt through the
        # leaves/meta codec — the same GustPlan route every entry point takes
        p = GustPlan.from_spec(
            {"leaves": gust_slice[name], "meta": metas[name]}, config=pc
        )
        return p.spmm(v)

    g = act(mv("w_gate", xt).astype(jnp.float32))
    u = mv("w_up", xt).astype(jnp.float32)
    h = (g * u)  # (f, B)
    y = mv("w_down", h)  # (d, B)
    return y.T[:, None, :].astype(x.dtype)  # (B, 1, d)


def decode_step_gust(lm: LM, params, gust, caches, tokens, pos, *,
                     cfg: GustServeConfig, dtype=jnp.bfloat16):
    """Mirror of LM.decode_step with the per-layer MLP routed through GUST.

    ``gust`` is the pytree produced by :func:`gustify` (or dryrun_specs).
    ``pos`` is a scalar or (B,) vector of per-slot positions — the GUST
    path shares the continuous-batching machinery (slot-local caches,
    per-row attention masks) with the dense decode, so mixed-length
    request batches serve correctly through ``ServeLoop`` here too.
    """
    sc = lm.stack
    bc = sc.pattern[0]
    x = lm._embed_tokens(params, tokens, dtype)
    metas = {k: v["meta"] for k, v in gust["mats"].items()}
    gust_leaves = {k: v["leaves"] for k, v in gust["mats"].items()}

    def body(x, xs):
        p_sl, c_sl, g_sl = xs
        h = apply_norm(p_sl["ln_attn"], x, kind=bc.norm_kind)
        from repro.models import attention as A

        with jax.named_scope("attn"):  # attention and its KV update
            y, cache = A.decode_step(p_sl["attn"], h, bc.attn, c_sl, pos)
        x = x + y
        h = apply_norm(p_sl["ln_mlp"], x, kind=bc.norm_kind)
        x = x + _gust_mlp(g_sl, metas, h, bc.mlp_kind, cfg)
        return x, cache

    x, rep_caches = jax.lax.scan(
        body, x, (params["stack"]["reps"][0], caches["reps"][0], gust_leaves)
    )
    new_caches = {"reps": (rep_caches,), "tail": caches["tail"]}
    logits = lm._logits(params, x)
    return logits, new_caches


def dryrun_specs(lm: LM, cfg: GustServeConfig) -> Dict:
    """ShapeDtypeStruct stand-in for the gust pytree, with the scheduled
    stream sized from Eq. 9: C = E[colors] bound at the pruned density —
    the dry-run proof that the GUST decode path lowers and fits.  Each
    matrix is a :meth:`GustPlan.spec_for` plan (honoring ``cfg.ragged``:
    a ragged config dry-runs the ragged program, the bound sizing every
    window's block count), stacked across reps by the shared codec."""
    reps = lm.stack.reps
    d = lm.cfg.d_model
    f = lm.cfg.d_ff
    pc = cfg.plan_config
    out: Dict = {"mats": {}, "stats": {}}
    for name in cfg.mats:
        m, n = (d, f) if name == "w_down" else (f, d)
        proto = GustPlan.spec_for(
            m, n, pc, colors=expected_colors_bound(n, cfg.density, pc.l)
        )
        spec = proto.to_spec()
        out["mats"][name] = {
            "leaves": stacked_leaf_specs(proto.artifact, reps),
            "meta": spec["meta"],
        }
    return out
