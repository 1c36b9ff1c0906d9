import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds the real step function (train_step /
prefill / decode_step), the real sharding rules, and ShapeDtypeStruct
inputs (no allocation), then proves the distribution config is coherent:

    jit(step, in_shardings=...).lower(**specs).compile()

Success per cell yields ``memory_analysis()`` (fits-per-chip proof),
``cost_analysis()``, and the loop-aware HLO analysis (launch/
hlo_analysis.py) feeding EXPERIMENTS.md §Dry-run / §Roofline.  Results
are cached as JSON under ``results/dryrun/`` (one file per cell) so
repeated invocations only compile what changed.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi_6b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --gust-decode  # GUST cell
"""

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import numpy as np

# jax imported only after XLA_FLAGS is pinned (first two lines).
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCH_IDS, SHAPES, get_arch
from repro.distributed.sharding import (
    activation_ctx,
    cache_spec_overrides,
    dp_axes,
    param_specs,
)
from repro.launch.hlo_analysis import analyze_hlo, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.models.model_zoo import build_model
from repro.training import TrainConfig, init_train_state, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


# ---------------------------------------------------------------------------
# Cell policies
# ---------------------------------------------------------------------------


def microbatches_for(n_params: int, shape, mesh) -> int:
    """Gradient-accumulation depth: targets per-chip microbatch rows of
    1 (>=15B), 2 (>=3B) or 4 (smaller).  Always >= 1 row per chip."""
    dp = 1
    for a in dp_axes(mesh):
        dp *= mesh.shape[a]
    rows = 1 if n_params > 15e9 else (2 if n_params > 3e9 else 4)
    mb = max(shape.global_batch // (dp * rows), 1)
    while shape.global_batch % (mb * dp) or (shape.global_batch // mb) % dp:
        mb -= 1
    return max(mb, 1)


def skip_reason(arch_id: str, shape_name: str) -> Optional[str]:
    cfg = get_arch(arch_id)
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return "pure full attention: long_500k disqualified (DESIGN.md S5)"
    if shape_name == "long_500k" and cfg.is_encdec:
        return "enc-dec: 0.5M-frame source out of family spec (DESIGN.md S5)"
    return None


def _count_params(specs) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(specs)))


def _batch_sharding(mesh, specs: Dict) -> Dict:
    """Batch inputs: shard dim 0 over DP axes only when divisible (the
    long_500k cells run global_batch=1 — all parallelism is model-axis)."""
    dp = dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    def sh(v):
        lead = dp if v.shape and v.shape[0] % dp_size == 0 else None
        return NamedSharding(mesh, P(lead, *([None] * (len(v.shape) - 1))))

    return {k: sh(v) for k, v in specs.items()}


def _bf16_params(params_specs):
    def cast(x):
        dt = jnp.bfloat16 if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype
        return jax.ShapeDtypeStruct(x.shape, dt)

    return jax.tree.map(cast, params_specs)


# ---------------------------------------------------------------------------
# Cell construction: (step_fn, args_specs, in_shardings)
# ---------------------------------------------------------------------------


def build_cell(arch_id: str, shape_name: str, mesh):
    cfg = get_arch(arch_id)
    lm = build_model(cfg)
    shape = SHAPES[shape_name]
    dp = dp_axes(mesh)
    key = jax.random.PRNGKey(0)

    if shape.kind == "train":
        params_specs = jax.eval_shape(lambda: lm.init(key))
        n_params = _count_params(params_specs)
        tc = TrainConfig(
            microbatches=microbatches_for(n_params, shape, mesh),
            dtype="bfloat16",
            remat=True,
        )
        state_specs = jax.eval_shape(lambda: init_train_state(lm, key, tc))
        pspecs = param_specs(state_specs["params"], mesh, mode="train")
        state_sh = {
            "params": pspecs,
            "opt": {"m": pspecs, "v": pspecs, "step": NamedSharding(mesh, P())},
        }
        batch_specs = lm.input_specs(shape.seq_len, shape.global_batch, "train")
        bsh = _batch_sharding(mesh, batch_specs)
        step = make_train_step(lm, tc)
        return step, (state_specs, batch_specs), (state_sh, bsh), {
            "n_params": n_params,
            "microbatches": tc.microbatches,
            "tokens_per_step": shape.global_batch * shape.seq_len,
        }

    params_specs = _bf16_params(jax.eval_shape(lambda: lm.init(key)))
    n_params = _count_params(params_specs)
    pspecs = param_specs(params_specs, mesh, mode="serve")
    cache_specs = jax.eval_shape(
        lambda: lm.init_caches(shape.global_batch, shape.seq_len, jnp.bfloat16)
    )
    csh = jax.tree_util.tree_map_with_path(
        cache_spec_overrides(mesh, shape.global_batch), cache_specs
    )

    if shape.kind == "prefill":
        batch_specs = lm.input_specs(shape.seq_len, shape.global_batch, "prefill")
        bsh = _batch_sharding(mesh, batch_specs)

        def prefill_fn(params, batch, caches):
            return lm.prefill(params, batch, caches, dtype=jnp.bfloat16)

        return prefill_fn, (params_specs, batch_specs, cache_specs), (
            pspecs, bsh, csh,
        ), {"n_params": n_params, "tokens_per_step": shape.global_batch * shape.seq_len}

    # decode
    tok_specs = lm.input_specs(shape.seq_len, shape.global_batch, "decode")
    tok_sh = _batch_sharding(mesh, tok_specs)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    pos_sh = NamedSharding(mesh, P())

    def decode_fn(params, caches, tokens, pos):
        return lm.decode_step(params, caches, tokens, pos, dtype=jnp.bfloat16)

    return decode_fn, (params_specs, cache_specs, tok_specs["tokens"], pos_spec), (
        pspecs, csh, tok_sh["tokens"], pos_sh,
    ), {"n_params": n_params, "tokens_per_step": shape.global_batch}


def build_gust_decode_cell(arch_id: str, mesh, density: float = 0.1,
                           gust_length: int = 256):
    """Beyond-assignment cell: the GUST-sparse decode path, schedule stream
    sized from the paper's Eq. 9 bound (``GustPlan.spec_for`` via
    serving/gust_serve.dryrun_specs).  REPRO_GUST_COMPACT/REPRO_GUST_RAGGED
    select the plan's dtype policy and layout (GustServeConfig.plan_config
    is the one spelling of those knobs)."""
    from repro.serving.gust_serve import GustServeConfig, decode_step_gust, dryrun_specs

    cfg = get_arch(arch_id)
    lm = build_model(cfg)
    shape = SHAPES["decode_32k"]
    dp = dp_axes(mesh)
    compact = os.environ.get("REPRO_GUST_COMPACT", "0") == "1"
    ragged = os.environ.get("REPRO_GUST_RAGGED", "0") == "1"
    gcfg = GustServeConfig(density=density, gust_length=gust_length,
                           use_kernel=False, compact=compact, ragged=ragged)
    pc = gcfg.plan_config
    gust_specs = dryrun_specs(lm, gcfg)
    params_specs = _bf16_params(jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0))))
    pspecs = param_specs(params_specs, mesh, mode="serve")
    cache_specs = jax.eval_shape(
        lambda: lm.init_caches(shape.global_batch, shape.seq_len, jnp.bfloat16)
    )
    csh = jax.tree_util.tree_map_with_path(
        cache_spec_overrides(mesh, shape.global_batch), cache_specs
    )
    # only the array leaves are jit arguments; the static meta (shapes,
    # lane geometry) stays a closure constant
    gust_leaves = {k: v["leaves"] for k, v in gust_specs["mats"].items()}
    gust_meta = {k: v["meta"] for k, v in gust_specs["mats"].items()}
    # schedule stream replicated across the mesh here; the distributed
    # row-window split (paper §5.5 parallel GUSTs) is exercised in
    # core.spmv.distributed_spmv tests
    gsh = jax.tree.map(lambda leaf: NamedSharding(mesh, P()), gust_leaves)
    tok_spec = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)

    def step(params, gleaves, caches, tokens, pos):
        gust = {"mats": {k: {"leaves": gleaves[k], "meta": gust_meta[k]}
                         for k in gleaves}}
        return decode_step_gust(
            lm, params, gust, caches, tokens, pos, cfg=gcfg, dtype=jnp.bfloat16
        )

    return step, (params_specs, gust_leaves, cache_specs, tok_spec,
                  jax.ShapeDtypeStruct((), jnp.int32)), (
        pspecs, gsh, csh,
        _batch_sharding(mesh, {"tokens": tok_spec})["tokens"],
        NamedSharding(mesh, P()),
    ), {"n_params": _count_params(params_specs), "gust_density": density,
        "gust_layout": pc.layout, "gust_dtypes": (pc.value_dtype, pc.index_dtype),
        "gust_gather": pc.gather,
        # spec plans size the gather table at the worst case (no measured
        # locality); the per-mat S_blk lets the roofline read the x-tile
        # working set without running the scheduler.  Read through the
        # codec (not meta-tuple indices) so meta-layout changes can't
        # silently misreport it.
        "gust_s_blk": {
            k: _spec_artifact(v).s_blk for k, v in gust_specs["mats"].items()
        },
        "tokens_per_step": shape.global_batch}


def _spec_artifact(entry):
    """Rebuild one dryrun_specs mat entry through the leaves/meta codec
    (works on ShapeDtypeStruct leaves; only static attrs are read)."""
    from repro.core.packing import packed_from_leaves, ragged_from_leaves

    meta = tuple(entry["meta"])
    decode = ragged_from_leaves if meta and meta[0] == "ragged" else \
        packed_from_leaves
    return decode(entry["leaves"], meta)


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             gust: bool = False) -> Dict:
    mesh_name = "multi" if multi_pod else "single"
    t0 = time.time()
    rec: Dict = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "gust": gust, "ok": False,
    }
    reason = skip_reason(arch_id, shape_name)
    if reason:
        rec.update(skipped=True, reason=reason, ok=True)
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        if gust:
            step, specs, shardings, meta = build_gust_decode_cell(arch_id, mesh)
            donate = (2,)  # caches updated in place
        else:
            step, specs, shardings, meta = build_cell(arch_id, shape_name, mesh)
            # donate the mutable aggregate: train state / caches — the
            # in-place-update contract every serving/training runtime uses
            kind = SHAPES[shape_name].kind
            donate = {"train": (0,), "prefill": (2,), "decode": (1,)}[kind]
        rec.update(meta)
        # SP: training shards the residual-carry sequence dim over "model"
        # (16x smaller remat saves); serving keeps batch-only activations
        seq_sp = (
            (not gust) and SHAPES[shape_name].kind == "train"
            and os.environ.get("REPRO_SP", "0") == "1"
        )
        with activation_ctx(mesh, seq_sharded=seq_sp):
            lowered = jax.jit(
                step, in_shardings=shardings, donate_argnums=donate
            ).lower(*specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(
                ma.argument_size_in_bytes + ma.temp_size_in_bytes
            ),
        }
        ca = compiled.cost_analysis()
        rec["xla_cost"] = {
            "flops": float(ca.get("flops", -1.0)),
            "bytes": float(ca.get("bytes accessed", -1.0)),
        }
        st = analyze_hlo(compiled.as_text())
        rec["hlo"] = st.to_dict()
        rec["roofline"] = roofline_terms(st)
        rec["timing"] = {"lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)}
        rec["ok"] = True
    except Exception as e:  # record the failure, don't crash the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=10)
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def cell_path(arch_id: str, shape_name: str, mesh_name: str, gust=False) -> str:
    tag = f"{arch_id}__{shape_name}__{mesh_name}" + ("__gust" if gust else "")
    return os.path.join(RESULTS_DIR, tag + ".json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--gust-decode", action="store_true",
                    help="run the GUST-sparse decode dry-run cell")
    ap.add_argument("--force", action="store_true", help="ignore cache")
    args = ap.parse_args()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ARCH_IDS if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]

    n_fail = 0
    for mesh_name in meshes:
        for arch in archs:
            if args.gust_decode:
                path = cell_path(arch, "decode_32k", mesh_name, gust=True)
                if os.path.exists(path) and not args.force:
                    continue
                rec = run_cell(arch, "decode_32k", mesh_name == "multi", gust=True)
                json.dump(rec, open(path, "w"), indent=1)
                status = "OK" if rec["ok"] else "FAIL"
                print(f"[{status}] {arch} gust-decode {mesh_name} ({rec['wall_s']}s)")
                n_fail += 0 if rec["ok"] else 1
                continue
            for shape in shapes:
                path = cell_path(arch, shape, mesh_name)
                if os.path.exists(path) and not args.force:
                    prev = json.load(open(path))
                    if prev.get("ok"):
                        continue
                rec = run_cell(arch, shape, mesh_name == "multi")
                json.dump(rec, open(path, "w"), indent=1)
                if rec.get("skipped"):
                    print(f"[SKIP] {arch} {shape} {mesh_name}: {rec['reason']}")
                    continue
                status = "OK" if rec["ok"] else "FAIL"
                extra = ""
                if rec["ok"]:
                    peak = rec["memory"]["peak_bytes"] / 2**30
                    dom = rec["roofline"]["dominant"]
                    extra = f" peak={peak:.1f}GiB dom={dom}"
                else:
                    extra = " " + rec["error"][:120]
                print(f"[{status}] {arch} {shape} {mesh_name} ({rec['wall_s']}s){extra}")
                n_fail += 0 if rec["ok"] else 1
    print("dry-run failures:", n_fail)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
