"""Serving driver: a mixed-length request stream against a (reduced or
full) model, dense or GUST-sparse decode, with continuous batching.

Requests are enqueued up front (bounded admission queue) and the loop
admits into free slots while other requests are mid-decode: per-slot
prefill + per-slot positions make every request's output identical to a
solo run, so batching is purely a throughput knob (reported as
``tok_per_s`` / ``slot_occupancy``; ``--serial`` forces the old
one-request-at-a-time pattern for comparison).

The GUST path plans every MLP and held-expert matrix once at engine
build (``serving.gust_serve.gustify`` -> ``repro.plan``) and executes
each decode step through the stacked :class:`~repro.core.plan.GustPlan`
leaves; ``--ragged``/``--compact``/``--use-kernel`` map onto the plan's
layout/dtype/backend knobs.  GUST decode shares the continuous-batching
machinery with the dense path.  A model with expert layers (e.g.
``--arch mellum2_12b_a2_5b``) also reports its routing counters under
``moe``: routed (token, held expert) pairs, expert columns computed, and
tokens and busy decode steps per expert layer and held expert.

``--no-reduced`` serves the published widths; ``--layers N`` cuts depth
to the first N layers (the only cut at full width).  Without an
installed fault plan, a request that ends FAILED makes the command exit
nonzero: a lowering or execution error on the device is an error, not a
statistic.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch yi_6b \
        --requests 6 --max-new 16 [--gust --density 0.2 --ragged --compact]
    PYTHONPATH=src python -m repro.launch.serve --arch yi_6b --no-reduced \
        --layers 4 --gust --density 0.1 --gust-length 256 --use-kernel
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

from repro.configs.base import ArchConfig, get_arch
from repro.models.model_zoo import build_model
from repro.resilience import faults
from repro.serving import GustServeConfig, ServeConfig, ServeLoop

__all__ = ["run_serving"]

#: Checkout-local compile cache used when JAX_COMPILATION_CACHE_DIR is
#: unset (a fixed path: the cache is keyed by it, so it must not move).
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def _use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache.  ``JAX_COMPILATION_CACHE_DIR``
    wins when set (JAX reads it itself, so no other directory is set
    here); otherwise the checkout's ``.jax_cache``.  Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR


def _arch(arch: str, *, reduced: bool, layers: int = None) -> ArchConfig:
    """The served configuration: the reduced smoke widths or the published
    ones, with depth optionally cut to ``layers``."""
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    if layers is not None:
        if not 0 < layers <= cfg.n_layers:
            raise ValueError(f"--layers must be in [1, {cfg.n_layers}]")
        cfg = dataclasses.replace(cfg, n_layers=layers)
    return cfg


def run_serving(
    arch: str,
    *,
    reduced: bool = True,
    layers: int = None,
    batch: int = 4,
    seq_len: int = 128,
    requests: int = 4,
    prompt_len: int = 8,
    max_new: int = 8,
    gust: bool = False,
    density: float = 0.25,
    gust_length: int = 32,
    use_kernel: bool = False,
    ragged: bool = False,
    compact: bool = False,
    plan_store: str = None,
    serial: bool = False,
    temperature: float = 0.0,
    eos_id=None,
    seed: int = 0,
    deadline_steps: int = None,
    deadline_s: float = None,
):
    cfg = _arch(arch, reduced=reduced, layers=layers)
    lm = build_model(cfg)
    params = jax.jit(lm.init)(jax.random.PRNGKey(seed))
    gcfg = None
    if gust:
        gcfg = GustServeConfig(
            density=density, gust_length=gust_length, use_kernel=use_kernel,
            ragged=ragged, compact=compact, plan_store=plan_store,
        )
    sc = ServeConfig(batch=batch, seq_len=seq_len, dtype="float32", gust=gcfg,
                     temperature=temperature, eos_id=eos_id,
                     queue_capacity=max(requests, 64),
                     max_steps_per_request=deadline_steps,
                     max_seconds_per_request=deadline_s)
    loop = ServeLoop(lm, params, sc, seed=seed)
    rng = np.random.default_rng(seed)
    # mixed-length trace: prompt lengths cycle between prompt_len//2 and
    # prompt_len — exactly the workload per-slot positions exist for
    lengths = [max(1, prompt_len // 2), prompt_len, max(1, 3 * prompt_len // 4)]
    prompts = [
        rng.integers(0, cfg.vocab, lengths[r % len(lengths)]).astype(np.int32)
        for r in range(requests)
    ]
    t0 = time.time()
    done = {}
    if serial:  # one-request-at-a-time baseline
        for prompt in prompts:
            rid = loop.submit(prompt, max_new=max_new)
            loop.run_to_completion()
            done[rid] = loop.completed[rid]
    else:  # continuous batching: enqueue the stream, drain the queue
        rids = [loop.enqueue(prompt, max_new=max_new) for prompt in prompts]
        loop.run_to_completion()
        # non-DONE requests (TIMEOUT under a deadline, SHED past
        # capacity) carry their terminal result instead of completed[]
        done = {
            rid: loop.completed.get(
                rid, loop.results[rid].tokens if rid in loop.results else []
            )
            for rid in rids
        }
    dt = time.time() - t0
    toks = sum(len(v) for v in done.values())
    stats = {
        "requests": len(done),
        "tokens_generated": toks,
        "wall_s": round(dt, 2),
        "tok_per_s": round(toks / dt, 1),
        "decode_steps": loop.stats["decode_steps"],
        "slot_occupancy": round(loop.occupancy, 4),
        "mode": "serial" if serial else "continuous",
        "gust": bool(gust),
        # the serve loop's spans (serve.step, serve.admit, serve.wait, ...):
        # host milliseconds per decode step
        "serve_ms_per_step": {
            k[len("serve."):-len("_s")]: round(
                v / max(loop.stats["decode_steps"], 1) * 1e3, 3)
            for k, v in loop.stats.items() if k.startswith("serve.")
        },
        # lifecycle + degradation counters (PR 10): terminal statuses
        # and the process-wide fallback counters
        "resilience": loop.resilience_stats(),
    }
    # the expert layers' routing counters (ServeLoop.read_counters):
    # routed (token, held expert) pairs against expert columns computed,
    # and tokens and busy steps per expert layer and held expert
    moe = {k[len("moe."):]: v for k, v in loop.read_counters().items()
           if k.startswith("moe.")}
    if moe:
        stats["moe"] = moe
    if gust and loop.gust_tree is not None:
        mat_stats = {k: loop.gust_tree["stats"][k]
                     for k in loop.gust_tree["mats"]}
        stats["gust_stream_utilization"] = {
            k: round(v["stream_utilization"], 4) for k, v in mat_stats.items()
        }
        stats["gust_streamed_slots"] = {
            k: v["streamed_slots"] for k, v in mat_stats.items()
        }
        # gustify's wall time and its phases (prune, colour, pack, stack,
        # upload), host seconds
        stats["gustify_s"] = round(loop.gust_tree["stats"]["gustify_s"], 3)
        stats["gust_build_s"] = {
            k: round(v, 3)
            for k, v in loop.gust_tree["stats"]["build_s"].items()
        }
        if "plan_store" in loop.gust_tree["stats"]:
            stats["gust_plan_store"] = loop.gust_tree["stats"]["plan_store"]
    return done, stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the family-preserving smoke widths "
                    "(default); --no-reduced serves the published widths")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut depth to the first N layers")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--gust", action="store_true")
    ap.add_argument("--density", type=float, default=0.25)
    ap.add_argument("--gust-length", type=int, default=32)
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--ragged", action="store_true",
                    help="stack ragged color-block streams (only real "
                    "cycle blocks) instead of the padded C_pad layout")
    ap.add_argument("--compact", action="store_true",
                    help="bf16 values + int16 indices: halves the streamed "
                    "schedule bytes (the paper's packed-word analogue)")
    ap.add_argument("--plan-store", type=str, default=None,
                    help="directory for the persistent PlanStore: warm "
                    "starts load packed plans off disk with zero coloring")
    ap.add_argument("--serial", action="store_true",
                    help="one-request-at-a-time baseline (default is "
                    "continuous batching over the admission queue)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="retire a request when it samples this token")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="per-request decode-step budget; expiry retires "
                    "the request with status=TIMEOUT (tokens kept)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock budget in seconds")
    args = ap.parse_args(argv)
    _use_compile_cache()
    _, stats = run_serving(
        args.arch, reduced=args.reduced, layers=args.layers,
        batch=args.batch, seq_len=args.seq_len,
        requests=args.requests, prompt_len=args.prompt_len,
        max_new=args.max_new, gust=args.gust, density=args.density,
        gust_length=args.gust_length, use_kernel=args.use_kernel,
        ragged=args.ragged, compact=args.compact,
        plan_store=args.plan_store, serial=args.serial,
        temperature=args.temperature, eos_id=args.eos_id,
        deadline_steps=args.deadline_steps, deadline_s=args.deadline_s,
    )
    print(json.dumps(stats))
    failed = stats["resilience"]["failed"]
    if failed and not faults.enabled():
        print(f"{failed} request(s) FAILED with no fault plan installed",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
