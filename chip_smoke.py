#!/usr/bin/env python3
"""Chip smoke test: GUST-sparse yi_6b serving on one TPU at published widths.

    python chip_smoke.py              # one chip: the serving phases
    python chip_smoke.py --chips 4    # four chips: row-window-sharded SpMV only

One chip.  yi_6b (d_model 4096, d_ff 11008, 32 heads / 4 KV heads,
d_head 128, vocab 64000) with random weights from ``--seed``, depth cut
to ``LAYERS``.  Every MLP matrix is magnitude-pruned to density 0.1 and
planned with l=256 (``gustify``), so each decode matvec runs through
``GustPlan.spmm`` on the compiled Pallas kernels, in the padded and the
ragged layout, with the default gather and pipeline.  Phases:

  exact   per layout, a one-nonzero-per-row matrix through the kernels
          must equal ``value * x[col]`` bitwise: the gather and the
          one-hot routing move data without rounding it;
  compile per backend, the served decode program (``ServeLoop``'s own
          jit, lowered with its own plan leaves): the kernel backends'
          hold a ``tpu_custom_call`` and the jnp backend's none;
  serve   8 mixed-length requests (prompts of 16-64 tokens, 16 new
          tokens, batch 4) through ``ServeLoop`` with continuous
          batching, once per backend: every request DONE, no contained
          decode retries, no fallback.  The host-clock time of each
          decode call is printed, as information;
  compare each kernel run against the jnp run of the same pruned plans,
          request by request (``compare``): logits within ``LOGIT_RTOL``
          while the tokens agree, and a near-tie where they first differ.

Four chips.  ``GustPlan.shard`` (the row-window-sharded SpMV) over a
4-device mesh, on the full-size ``SHARD_MATRIX`` Table-3 surrogate from
``repro.data.matrices``; the result must match the one-chip ``spmv`` of
the same plan and a float64 reference.

Earlier stdout lines are one JSON object per phase.  On success the last
line is ``{"ok": true, "device": {...}}``; a failed check, a missing TPU
or a missing ``repro`` package exits nonzero without printing it, and so
does a child process (the coloring workers' forkserver or resource
tracker) still running once they are stopped.  Matmuls run at the default precision, as in ``launch/serve.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "yi_6b"
LAYERS = 4  # depth cut; the widths are the published ones
DENSITY = 0.1
GUST_LENGTH = 256
BATCH = 4
SEQ_LEN = 128
REQUESTS = 8
PROMPT_LENS = (16, 32, 48, 64)
MAX_NEW = 16
#: kernel vs jnp logits: |diff| <= LOGIT_RTOL * max|jnp logit|.  At the
#: default precision the dense matmuls round their f32 operands to bf16
#: (spacing 2**-8), and the two MLP paths differ by f32 summation order,
#: which can flip such a rounding; the limit is two bf16 spacings.
LOGIT_RTOL = 2.0 ** -7
#: four-chip phase: the largest Table-3 surrogate, at full size
SHARD_MATRIX = "PFlow_742"
#: sharded vs one-chip / float64 SpMV: |diff| <= SPMV_RTOL * max|ref|
SPMV_RTOL = 1e-4


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def memory() -> dict:
    """Peak host RSS and peak device bytes so far, in GiB."""
    import resource

    import jax

    out = {"host_peak_gib": resource.getrusage(resource.RUSAGE_SELF)
           .ru_maxrss / 2 ** 20}
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        out["device_peak_gib"] = stats["peak_bytes_in_use"] / 2 ** 30
    return out


def variants():
    """The served backends: two kernel layouts and the jnp reference."""
    from repro.serving import GustServeConfig

    def cfg(use_kernel, ragged):
        return GustServeConfig(density=DENSITY, gust_length=GUST_LENGTH,
                               use_kernel=use_kernel, ragged=ragged)

    return {
        "pallas_padded": cfg(True, False),
        "pallas_ragged": cfg(True, True),
        "jnp": cfg(False, False),
    }


def phase_exact(gcfgs, *, n: int, batch: int, seed: int) -> None:
    """One nonzero per row: each output is one product, so the kernel
    result must be bitwise ``value * x[col]``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.formats import COOMatrix
    from repro.core.plan import plan

    rng = np.random.default_rng(seed)
    cols = rng.permutation(n)
    vals = rng.standard_normal(n).astype(np.float32)
    coo = COOMatrix((n, n), np.arange(n), cols, vals)
    x = rng.standard_normal((n, batch)).astype(np.float32)
    want = vals[:, None] * x[cols]
    for name, g in gcfgs.items():
        if not g.use_kernel:
            continue
        p = plan(coo, g.plan_config)
        check(not p._interpret(), f"{name}: kernels would run interpreted")
        got = np.asarray(p.spmm(jnp.asarray(x)))
        check(np.array_equal(got, want),
              f"{name}: kernel result differs from value * x[col] "
              f"(max |diff| {float(np.max(np.abs(got - want)))})")
    emit("exact", n=n, batch=batch, layouts=[
        k for k, g in gcfgs.items() if g.use_kernel], bitwise=True)


def _fusable(tree) -> bool:
    from repro.core.plan import GustPlan

    return all(
        GustPlan.from_spec({"leaves": {k: v[0] for k, v in m["leaves"].items()},
                            "meta": m["meta"]}).artifact.fusable
        for m in tree["mats"].values()
    )


def phase_serve(cfg, gcfgs, *, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.scheduler import sched_counters
    from repro.models.model_zoo import build_model
    from repro.resilience.fallback import fallback_counters
    from repro.resilience.lifecycle import RequestStatus
    from repro.serving import ServeConfig, ServeLoop

    t0 = time.perf_counter()
    lm = build_model(cfg)
    params = jax.block_until_ready(jax.jit(lm.init)(jax.random.PRNGKey(seed)))
    emit("init", seconds=time.perf_counter() - t0)

    loops = {}
    for name, g in gcfgs.items():
        t0 = time.perf_counter()
        sc = ServeConfig(batch=BATCH, seq_len=SEQ_LEN, dtype="float32",
                         gust=g, queue_capacity=REQUESTS)
        loops[name] = ServeLoop(lm, params, sc, seed=seed)
        tree = loops[name].gust_tree
        check(_fusable(tree), f"{name}: a packed stream is not fusable, "
              "so the kernel backend would run the jnp path")
        emit("gustify", variant=name, seconds=time.perf_counter() - t0,
             interpret=_interpret(g) if g.use_kernel else None,
             stream_utilization={k: tree["stats"][k]["stream_utilization"]
                                 for k in tree["mats"]},
             build_s=tree["stats"]["build_s"],
             sched_counters=dict(sched_counters), **memory())
        if g.use_kernel:
            check(not _interpret(g), f"{name}: kernels would run interpreted")

    # -- the served decode programs: Pallas kernels compiled in? ---------
    rng = np.random.default_rng(seed)
    toks = jnp.zeros((BATCH, 1), jnp.int32)
    pos = jnp.zeros((BATCH,), jnp.int32)
    compile_s = {}
    for name, loop in loops.items():
        t0 = time.perf_counter()
        text = loop._decode.lower(loop.params, loop.caches, toks, pos,
                                  loop._counts, None,
                                  *loop._decode_extra).compile().as_text()
        compile_s[name] = time.perf_counter() - t0
        has_kernel = "tpu_custom_call" in text
        check(has_kernel == gcfgs[name].use_kernel,
              f"{name}: compiled decode {'lacks' if not has_kernel else 'has'}"
              " a Pallas kernel")
    emit("compile", decode_s=compile_s)

    # -- serve: mixed-length requests through ServeLoop ------------------
    prompts = [
        rng.integers(0, cfg.vocab, PROMPT_LENS[r % len(PROMPT_LENS)])
        .astype(np.int32)
        for r in range(REQUESTS)
    ]
    served = {}
    for name, loop in loops.items():
        rec = _record(loop)
        fb0 = dict(fallback_counters)
        t0 = time.perf_counter()
        rids = [loop.enqueue(p, max_new=MAX_NEW) for p in prompts]
        loop.run_to_completion()
        wall = time.perf_counter() - t0
        res = [loop.results.get(r) for r in rids]
        statuses = [None if r is None else r.status.name for r in res]
        fb = {k: v - fb0[k] for k, v in fallback_counters.items()}
        steady = sorted(rec["step_s"][1:])
        emit("serve", variant=name, wall_s=wall,
             decode_steps=loop.stats["decode_steps"],
             decode_step_s={"min": steady[0],
                            "median": steady[len(steady) // 2],
                            "max": steady[-1]},
             tokens=sum(len(r.tokens) for r in res if r is not None),
             statuses=statuses, decode_retries=loop.stats["decode_retries"],
             fallback_counters=fb, **memory())
        check(all(r is not None and r.status is RequestStatus.DONE
                  for r in res), f"{name}: not every request DONE: {statuses}")
        check(loop.stats["decode_retries"] == 0,
              f"{name}: {loop.stats['decode_retries']} contained decode "
              "failures")
        check(not any(fb.values()), f"{name}: fallbacks applied: {fb}")
        served[name] = [(r.tokens, {j: rec["rows"][(rid, j)]
                                    for j in range(len(r.tokens))})
                        for rid, r in zip(rids, res)]
    for name in loops:
        if name != "jnp":
            compare(name, served[name], served["jnp"])
    check(not any(fallback_counters.values()),
          f"fallbacks applied: {dict(fallback_counters)}")
    emit("counters", fallback_counters=dict(fallback_counters),
         sched_counters=dict(sched_counters))


def _record(loop) -> dict:
    """Instrument ``loop`` in place around its own compiled programs:
    time each decode call on the host clock (synchronised), and keep the
    logits row each token was sampled from, keyed (request id, token
    index)."""
    import jax
    import numpy as np

    rec = {"step_s": [], "rows": {}}
    decode, sample = loop._decode, loop._sample_rows

    def timed_decode(*args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(decode(*args))
        rec["step_s"].append(time.perf_counter() - t0)
        return out

    def recording_sample(logits_rows, rid_step):
        rows = np.asarray(logits_rows, np.float32)
        for r, key in enumerate(rid_step):
            # first wins: idle decode rows reuse the key (0, 0), which
            # the first request's prefill token has already taken
            rec["rows"].setdefault(key, rows[r])
        return sample(logits_rows, rid_step)

    loop._decode, loop._sample_rows = timed_decode, recording_sample
    return rec


def compare(name: str, got, want) -> None:
    """Kernel run vs jnp run, request by request.  While the two token
    streams agree, both runs sampled from the same context, so their
    logits rows must agree within ``LOGIT_RTOL`` of the row's largest
    jnp |logit|.  At the first differing token the kernel's choice must be
    a near-tie in the jnp row: its jnp logit within the same tolerance of
    the jnp maximum.  Later tokens of that request follow another context
    and are not compared."""
    import numpy as np

    worst, compared, diverged, problems = 0.0, 0, {}, []
    for i, ((tk, rows_k), (tr, rows_r)) in enumerate(zip(got, want)):
        first = next((j for j, (a, b) in enumerate(zip(tk, tr)) if a != b),
                     None)
        if first is None and len(tk) != len(tr):
            problems.append(f"request {i}: {len(tk)} tokens, jnp {len(tr)}")
        for j in range(len(tk) if first is None else first + 1):
            ref = rows_r[j]
            scale = float(np.max(np.abs(ref)))
            diff = float(np.max(np.abs(rows_k[j] - ref)))
            if not diff <= LOGIT_RTOL * scale:  # NaN fails too
                problems.append(f"request {i} token {j}: logits differ by "
                                f"{diff} (limit {LOGIT_RTOL} x {scale})")
            worst = max(worst, diff / scale)
            compared += 1
        if first is not None:
            ref = rows_r[first]
            gap = float(ref[tr[first]] - ref[tk[first]])
            diverged[i] = {"token": first, "jnp_gap": gap,
                           "jnp_scale": float(np.max(np.abs(ref)))}
            if not gap <= LOGIT_RTOL * diverged[i]["jnp_scale"]:
                problems.append(f"request {i} token {first}: kernel chose "
                                f"{tk[first]}, {gap} below the jnp maximum")
    emit("compare", variant=name, rtol=LOGIT_RTOL, rows_compared=compared,
         max_rel_diff=worst, diverged=diverged)
    check(not problems, f"{name} vs jnp: {'; '.join(problems)}")


def _interpret(g) -> bool:
    from repro.kernels.gust_spmv import _resolve_interpret

    return _resolve_interpret(g.plan_config.interpret)


def phase_shard(n_dev: int, *, matrix: str, scale: float, seed: int) -> None:
    """Row-window-sharded SpMV over ``n_dev`` devices vs the one-chip SpMV
    of the same plan and a float64 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.plan import PlanConfig, plan
    from repro.data.matrices import REAL_WORLD_SUITE, make_real_world_surrogate

    spec = {s.name: s for s in REAL_WORLD_SUITE}[matrix]
    t0 = time.perf_counter()
    coo = make_real_world_surrogate(spec, scale=scale, seed=seed)
    p = plan(coo, PlanConfig(l=GUST_LENGTH, layout="ragged", backend="jnp"))
    art = p.artifact
    build_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    v = rng.standard_normal(coo.shape[1]).astype(np.float32)
    ref = np.bincount(
        coo.rows, weights=np.asarray(coo.vals, np.float64)
        * v[coo.cols].astype(np.float64), minlength=coo.shape[0])

    mesh = jax.make_mesh((n_dev,), ("x",), devices=jax.devices()[:n_dev])
    sp = p.shard(mesh, "x")
    t0 = time.perf_counter()
    y_shard = np.asarray(jax.block_until_ready(sp.spmv(jnp.asarray(v))))
    shard_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    y_one = np.asarray(jax.block_until_ready(p.spmv(jnp.asarray(v))))
    one_s = time.perf_counter() - t0
    scale_ref = float(np.max(np.abs(ref)))
    d_one = float(np.max(np.abs(y_shard - y_one)))
    d_ref = float(np.max(np.abs(y_shard - ref)))
    slot_bytes = (art.m_blk.dtype.itemsize + art.col_blk.dtype.itemsize
                  + art.row_blk.dtype.itemsize)
    emit("shard", matrix=matrix, scale=scale, shape=list(coo.shape),
         nnz=int(coo.nnz), devices=n_dev, build_s=build_s,
         stream_bytes_per_device=int(art.m_blk.size * slot_bytes // n_dev),
         first_call_s={"sharded": shard_s, "one_chip": one_s},
         max_diff={"one_chip": d_one, "float64": d_ref}, ref_scale=scale_ref)
    check(np.all(np.isfinite(y_shard)), "sharded SpMV is not finite")
    check(d_one <= SPMV_RTOL * scale_ref,
          f"sharded vs one-chip SpMV differ by {d_one}")
    check(d_ref <= SPMV_RTOL * scale_ref,
          f"sharded vs float64 SpMV differ by {d_ref}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the row-window-sharded SpMV phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, matrices and prompts")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print("chip_smoke: the repro package is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.core.scheduler import stop_workers
    from repro.launch.serve import _arch, _use_compile_cache

    cache = _use_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:
        print(f"chip_smoke: no accelerator: {err}", file=sys.stderr)
        return 3
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    emit("device", **device, compile_cache=cache)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing "
              "to run on another backend", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 3

    try:
        if args.chips == 4:
            phase_shard(4, matrix=SHARD_MATRIX, scale=1.0, seed=args.seed)
        else:
            full_depth = _arch(ARCH, reduced=False).n_layers
            cfg = _arch(ARCH, reduced=False, layers=LAYERS)
            emit("config", arch=cfg.name, d_model=cfg.d_model,
                 d_ff=cfg.d_ff, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
                 d_head=cfg.head_dim, vocab=cfg.vocab,
                 layers=cfg.n_layers, cut=f"depth {full_depth} -> "
                 f"{cfg.n_layers} layers (widths as published)",
                 density=DENSITY,
                 gust_length=GUST_LENGTH, batch=BATCH, seq_len=SEQ_LEN)
            gcfgs = variants()
            phase_exact(gcfgs, n=cfg.d_model, batch=BATCH, seed=args.seed)
            phase_serve(cfg, gcfgs, seed=args.seed)
        stop_workers()
        left = children()
        check(not left, f"processes still running: {left}")
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


def children() -> list:
    """Command lines of this process's live child processes (Linux)."""
    pids = []
    for task in os.listdir("/proc/self/task"):
        with open(f"/proc/self/task/{task}/children") as f:
            pids += f.read().split()
    out = []
    for pid in pids:
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                out.append(f.read().replace(b"\0", b" ").decode().strip())
        except OSError:  # exited meanwhile
            pass
    return out


if __name__ == "__main__":
    sys.exit(main())
