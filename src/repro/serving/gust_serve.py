"""GUST-sparse serving: the paper's technique as a first-class feature.

Decode-time LM inference is matvec-dominated.  ``gustify`` converts a
trained model's MLP and expert weights into GUST plans (magnitude
pruning -> ``repro.plan`` -> packed blocks), **once**, at weight-load
time — the paper's §3.3/§5.3 amortization ("the scheduling for each
matrix only needs to be computed once ... even if the vector changes").
``gust_ffn`` plugs the plans into the model's own decode
(``LM.decode``'s operator hook), so every block kind with an MLP or an
expert layer, at any pattern position, decodes its matvecs through
:meth:`GustPlan.spmm` with the model's attention and caches.

Plans are grouped per block of the stack: pattern position ``i`` (its
layers stacked along the reps axis) is ``"i"``, tail block ``j`` is
``"tailj"``; the tree's ``mats`` key is ``"<block>.<matrix>"``.  An
expert layer holds one plan per held expert and matrix, stacked along
(rep, expert).  Stacking is :meth:`GustPlan.stack`: the packed artifacts
are equalized to a uniform stream length (padded layout: uniform C_pad
via ``repad_to``; ragged layout: uniform block count via
``repad_to_blocks``) so the leaves stack and the layer scan stays a
single compact HLO — the GUST plan is literally part of the serving
checkpoint.  With ``GustServeConfig.ragged`` the stack holds ragged
color-block streams, so skewed pruned matrices stop streaming dead
padding cycles through every decode step.  The wire format is the plan's
``to_spec``/``from_spec`` leaves/meta codec, shared with ``dryrun_specs``.

The expert FFN runs every held expert's three plans on all B columns of
the batch, each token's hidden vector scaled by its combine weight (0
where the token was not routed to that expert) before ``w_down``: exact,
dropless and static in shape, at held·B computed columns against the
routed (token, held expert) pairs (``ServeLoop`` counts both).

``dryrun_specs`` sizes the schedule stream from the paper's Eq. 9 bound
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
from repro.models.model_zoo import LM
from repro.resilience.fallback import fallback_counters

__all__ = ["GustServeConfig", "gustify", "gust_ffn", "decode_step_gust",
           "dryrun_specs"]

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


def _blocks(lm: LM):
    """The blocks GUST serves, as (block id, group, index, BlockCfg):
    every pattern position (its layers rep-stacked) and every tail block.
    Raises for a block kind whose FFN GUST cannot serve yet."""
    sc = lm.stack
    kinds = {bc.kind for bc in sc.pattern}
    if lm.cfg.is_encdec or not kinds <= {"attn_mlp", "attn_moe"}:
        raise ValueError(
            "gustify serves attention blocks with an MLP or an expert layer "
            f"(got pattern {[b.kind for b in sc.pattern]})"
        )
    return ([(str(i), "reps", i, bc) for i, bc in enumerate(sc.pattern)]
            + [(f"tail{j}", "tail", j, sc.pattern[j])
               for j in range(sc.n_tail)])


def gustify(lm: LM, params, cfg: GustServeConfig, *,
            store: Optional[PlanStore] = None) -> Dict:
    """Build stacked GUST plans for every MLP and held-expert matrix.

    Returns ``{"mats": {"<block>.<matrix>": {"leaves": {...}, "meta":
    static layout tuple}}, "stats": {...}}`` — per block and matrix the
    :meth:`GustPlan.stack` of one plan per layer (leaves ``(R, ...)``) or,
    in an expert layer, per layer and held expert (``(R, E, ...)``); a
    tail block's leaves lead with 1.  ``stats`` holds one entry per
    key (each plan's cycles and nonzeros, nested as the leaves lead),
    ``gustify_s`` (this call's host seconds) and ``build_s``: the seconds
    of its phases, each a span (:class:`~repro.core.spans.Span`) —
    ``prune`` (weights to the host, magnitude pruning), ``colour`` and
    ``pack`` (``repro.plan``'s spans, read off ``sched_counters``),
    ``stack`` (equalize and stack the layers) and ``upload`` (wait until
    the stacked leaves are on the device).

    With ``cfg.plan_store`` (or an explicit ``store``), plans read
    through the persistent :class:`PlanStore`: a warm start rebuilds
    every stacked artifact from disk with zero coloring work.
    """
    blocks = _blocks(lm)
    if store is None and cfg.plan_store is not None:
        store = PlanStore(cfg.plan_store, verify=cfg.store_verify)
    t0 = time.perf_counter()
    pc = cfg.plan_config
    phases = dict.fromkeys(("prune", "colour", "pack", "stack", "upload"), 0.0)
    out: Dict = {"mats": {}, "stats": {}}
    fb0 = dict(fallback_counters)  # attribute downgrades to this build
    sc0 = {k: sched_counters[k] for k in ("colour_s", "pack_s")}
    plans_of = {}
    for where, group, i, bc in blocks:
        weights = params["stack"][group][i]
        weights = weights["moe" if bc.kind == "attn_moe" else "mlp"]
        for name in cfg.mats:
            key = f"{where}.{name}"
            with Span("build.prune", phases, "prune"):
                w = np.asarray(weights[name])  # ([R,] [E,] d_in, d_out)
            if group == "tail":
                w = w[None]
            lead = w.shape[:-2]  # (R,) or (R, E)
            # one plan per layer (and held expert), through the
            # content-keyed cache: re-gustifying the same weights (e.g. a
            # compact re-export) reuses the schedule
            plans, nnz = [], []
            for m in w.reshape((-1,) + w.shape[-2:]):
                with Span("build.prune", phases, "prune"):
                    coo = _prune_to_coo(m, cfg)
                plans.append(plan(coo, pc, cache=default_cache, store=store))
                nnz.append(coo.nnz)
            arts = [p.artifact for p in plans]  # packs each (build.pack)
            with Span("build.stack", phases, "stack"):
                st = GustPlan.stack(arts)
                st["leaves"] = jax.tree.map(
                    lambda a: a.reshape(lead + a.shape[1:]), st["leaves"])
            out["mats"][key] = st
            plans_of[key] = (plans, nnz, lead)
    with Span("build.upload", phases, "upload"):
        jax.block_until_ready([m["leaves"] for m in out["mats"].values()])
    for key, (plans, plan_nnz, lead) in plans_of.items():
        # uniform stream size after stacking = max over plans (stack()
        # equalizes to it); read off the artifacts, not meta positions
        if cfg.ragged:
            size_stat = {
                "num_blocks": max(p.artifact.num_blocks for p in plans)
            }
        else:
            size_stat = {"c_pad": max(p.artifact.c_pad for p in plans)}
        leaves = out["mats"][key]["leaves"]
        nnz = int(np.count_nonzero(np.asarray(leaves["m_blk"])))
        slots = leaves["m_blk"].size

        def nested(vals):
            return np.asarray(vals).reshape(lead).tolist()

        out["stats"][key] = {
            "cycles_per_layer": nested([_plan_cycles(p) for p in plans]),
            "nnz": nested(plan_nnz),
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


def _act(kind: str):
    return jax.nn.silu if kind == "swiglu" else jax.nn.gelu


def _matvec(leaves, meta, pc: PlanConfig, v):
    """One plan's slice of a stack, rebuilt through the leaves/meta codec
    (the same GustPlan route every entry point takes), times v (n, B)."""
    p = GustPlan.from_spec({"leaves": leaves, "meta": meta}, config=pc)
    return p.spmm(v).astype(jnp.float32)


def _gust_mlp(g, metas, xt, act, pc: PlanConfig):
    """xt: (d, B).  SwiGLU/GeGLU with every matvec through GUST; (d, B)."""
    h = act(_matvec(g["w_gate"], metas["w_gate"], pc, xt)) * _matvec(
        g["w_up"], metas["w_up"], pc, xt)  # (f, B)
    return _matvec(g["w_down"], metas["w_down"], pc, h)


def _gust_experts(g, metas, xt, w, pc: PlanConfig):
    """xt: (d, B); w: (held, B) combine weights.  Every held expert's
    SwiGLU on all B columns, its hidden vectors scaled by the weights
    before ``w_down``, summed over the experts (a scan over them); (d, B)."""

    def one(y, xs):
        leaves, we = xs
        h = jax.nn.silu(_matvec(leaves["w_gate"], metas["w_gate"], pc, xt))
        h = h * _matvec(leaves["w_up"], metas["w_up"], pc, xt) * we[None, :]
        return y + _matvec(leaves["w_down"], metas["w_down"], pc, h), None

    y, _ = jax.lax.scan(one, jnp.zeros(xt.shape, jnp.float32), (g, w))
    return y


def gust_ffn(lm: LM, gust, cfg: GustServeConfig):
    """``(ffn, ffn_xs)`` for :meth:`LM.decode`: the plans of ``gust`` (a
    :func:`gustify` or :func:`dryrun_specs` tree) as every served block's
    second residual branch.  An MLP block runs its three plans; an expert
    layer routes on the device (``MoESpec.route``, float32 at
    ``HIGHEST``) and runs :func:`_gust_experts`, returning which held
    experts each row was routed to."""
    pc = cfg.plan_config
    names = {}
    for key in gust["mats"]:
        where, name = key.split(".", 1)
        names.setdefault(where, []).append(name)

    def group(where, tail=False):
        if where not in names:
            return None
        g = {n: gust["mats"][f"{where}.{n}"]["leaves"] for n in names[where]}
        return jax.tree.map(lambda a: a[0], g) if tail else g

    sc = lm.stack
    ffn_xs = {"reps": tuple(group(str(i)) for i in range(len(sc.pattern))),
              "tail": [group(f"tail{j}", tail=True)
                       for j in range(sc.n_tail)]}

    def ffn(where, bc, p, h, g):
        metas = {n: gust["mats"][f"{where}.{n}"]["meta"] for n in names[where]}
        xt = h[:, 0].T.astype(jnp.float32)  # (d, B)
        if bc.kind == "attn_moe":
            _, _, gate, idx = bc.moe.route(p["moe"]["router"], h[:, 0])
            w, routed = bc.moe.held_weights(gate, idx)  # (B, held)
            y = _gust_experts(g, metas, xt, w.T, pc)
        else:
            y, routed = _gust_mlp(g, metas, xt, _act(bc.mlp_kind), pc), None
        return y.T[:, None, :].astype(h.dtype), routed  # (B, 1, d)

    return ffn, ffn_xs


def decode_step_gust(lm: LM, params, gust, caches, tokens, pos, *,
                     cfg: GustServeConfig, dtype=jnp.bfloat16,
                     routes: bool = False):
    """``LM.decode`` with every served block's MLP or expert layer
    through the GUST plans of ``gust`` (:func:`gust_ffn`); returns
    (logits, caches), with ``routes`` also ``LM.decode``'s routes (which
    held experts each row was routed to).  ``pos`` is a scalar or (B,)
    vector of per-slot positions — the GUST path is the model's own
    decode, so mixed-length request batches serve through ``ServeLoop``
    as the dense path does."""
    ffn, ffn_xs = gust_ffn(lm, gust, cfg)
    logits, caches, r = lm.decode(params, caches, tokens, pos, dtype=dtype,
                                  ffn=ffn, ffn_xs=ffn_xs)
    return (logits, caches, r) if routes else (logits, caches)


def dryrun_specs(lm: LM, cfg: GustServeConfig) -> Dict:
    """ShapeDtypeStruct stand-in for the gust pytree, with the scheduled
    stream sized from Eq. 9: C = E[colors] bound at the pruned density —
    the dry-run proof that the GUST decode path lowers and fits.  Each
    matrix is a :meth:`GustPlan.spec_for` plan (honoring ``cfg.ragged``:
    a ragged config dry-runs the ragged program, the bound sizing every
    window's block count), stacked as :func:`gustify` stacks them by the
    shared codec."""
    d, f = lm.cfg.d_model, lm.cfg.d_ff
    pc = cfg.plan_config
    out: Dict = {"mats": {}, "stats": {}}
    for where, group, _, bc in _blocks(lm):
        lead = (lm.stack.reps if group == "reps" else 1,)
        if bc.kind == "attn_moe":
            lead += (bc.moe.held,)
        for name in cfg.mats:
            m, n = (d, f) if name == "w_down" else (f, d)
            proto = GustPlan.spec_for(
                m, n, pc, colors=expected_colors_bound(n, cfg.density, pc.l)
            )
            one = stacked_leaf_specs(proto.artifact, 1)
            out["mats"][f"{where}.{name}"] = {
                "leaves": {k: jax.ShapeDtypeStruct(lead + v.shape[1:], v.dtype)
                           for k, v in one.items()},
                "meta": proto.to_spec()["meta"],
            }
    return out
