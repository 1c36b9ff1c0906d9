"""Chaos benchmark: the serving stack under a deterministic fault schedule.

Drives the PR 10 resilience layer end to end and hard-gates its
contracts (ROADMAP §Resilience invariants):

  * **Serving lifecycle** — a mixed-length request trace runs once
    fault-free, then twice under the same seeded :class:`FaultPlan`
    (admission kill, two contained batched-decode faults, a per-slot
    fault, a step-budget deadline, and queue backpressure).  Gates:
    every admitted request ends with exactly one definite status
    (DONE / FAILED / TIMEOUT / SHED — zero lost requests), no exception
    escapes the loop, surviving requests' token streams are **bitwise
    equal** to the fault-free run (the PR 4 slot-isolation contract
    under fire), the TIMEOUT request's tokens are a bitwise prefix, and
    the two chaos runs produce identical fault fingerprints and results
    (determinism by seed).
  * **Graceful degradation** — the three fallback chains through the
    one decision point (``resolve_fallback``): Pallas kernel fault →
    jnp executor (tolerance-equal), ``gather="local"`` fault →
    resident (bitwise, the PR 5 contract), and store-read faults
    during a warm ``gustify`` → fresh packs (bitwise, the PR 7
    warm==cold contract), each counted in ``fallback_counters``.
  * **Zero-overhead off** — reports (does not gate: shared runners)
    the per-call cost of a disabled ``faults.trip``.

Usage:
    PYTHONPATH=src python benchmarks/chaos_bench.py [--tiny]
        [--arch yi_6b] [--batch 4] [--requests 8] [--max-new 8]
        [--out BENCH_chaos.json]
"""

import argparse
import json
import os
import tempfile
import time

import jax
import numpy as np

from repro.configs.base import get_arch
from repro.core.plan import plan
from repro.models.model_zoo import build_model
from repro.resilience import faults
from repro.resilience.faults import FaultPlan, FaultSpec
from repro.resilience.fallback import fallback_counters
from repro.serving import (
    GustServeConfig,
    RequestStatus,
    ServeConfig,
    ServeLoop,
    gustify,
)


def mixed_trace(n: int, vocab: int, lengths, seed: int = 0):
    """Deterministic mixed-length prompt trace cycling through `lengths`."""
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, vocab, lengths[i % len(lengths)]).astype(np.int32)
        for i in range(n)
    ]


def _warmup(loop: ServeLoop, lengths, vocab: int):
    """Compile every (prefill, decode, insert) program the trace will
    hit, then scrub the warmup requests from the lifecycle books so the
    zero-lost-request accounting below sees only the timed trace."""
    rng = np.random.default_rng(123)
    for ln in sorted(set(lengths)):
        rid = loop.submit(rng.integers(0, vocab, ln).astype(np.int32), max_new=1)
        loop.run_to_completion()
        loop.completed.pop(rid, None)
        loop.results.pop(rid, None)
    for k in loop.stats:
        loop.stats[k] = 0


def _drive(loop: ServeLoop, prompts, max_new: int, deadlines=None):
    """Enqueue the whole trace (per-request deadline overrides from
    ``deadlines[idx]``) and drain.  An exception escaping here is itself
    a gate failure — step() promises containment."""
    rids = []
    for j, pr in enumerate(prompts):
        kw = {}
        if deadlines and j in deadlines:
            kw["deadline_steps"] = deadlines[j]
        rids.append(loop.enqueue(pr, max_new=max_new, **kw))
    try:
        loop.run_to_completion()
    except Exception as err:
        raise AssertionError(
            f"exception escaped the serving loop under faults: {err!r}"
        ) from err
    return rids


def _serving_fault_plan(rids, seed: int) -> FaultPlan:
    """The chaos schedule, targeted at known request ids: kill rids[1]
    at admission, fault rids[2]'s slot retirement once, and fail the
    batched decode twice (contained, state untouched, retried)."""
    return FaultPlan(
        [
            FaultSpec("serve.admit", tag=str(rids[1])),
            FaultSpec("serve.slot", tag=str(rids[2])),
            FaultSpec("serve.decode", times=2),
        ],
        seed=seed,
    )


def _chaos_serving_run(lm, params, args, cfg_kwargs, baseline_tokens):
    """One seeded chaos run over the trace; returns the replayable
    record after asserting every lifecycle gate."""
    n = args.requests
    sc = ServeConfig(batch=args.batch, seq_len=args.seq_len, dtype="float32",
                     queue_capacity=n - 1, **cfg_kwargs)
    loop = ServeLoop(lm, params, sc, seed=args.seed)
    cfg = get_arch(args.arch).reduced()
    _warmup(loop, args.lengths, cfg.vocab)
    prompts = mixed_trace(n, cfg.vocab, args.lengths, args.seed)

    # rids are assigned sequentially, so the fault plan can target them
    base = loop._next_id
    fp = _serving_fault_plan([base + j for j in range(n)], args.seed)
    with faults.injected(fp):
        rids = _drive(loop, prompts, args.max_new, deadlines={3: 2})
    assert rids == [base + j for j in range(n)]

    # gate: zero lost requests — every rid has exactly one definite status
    assert len(loop.results) == n, (
        f"lost requests: {n} admitted, {len(loop.results)} terminal"
    )
    statuses = {r: loop.results[r].status for r in rids}
    expected = {rids[j]: RequestStatus.DONE for j in range(n)}
    expected[rids[1]] = RequestStatus.FAILED   # admission fault
    expected[rids[2]] = RequestStatus.FAILED   # per-slot fault
    expected[rids[3]] = RequestStatus.TIMEOUT  # deadline_steps=2
    expected[rids[-1]] = RequestStatus.SHED    # queue_capacity = n-1
    assert statuses == expected, f"statuses {statuses} != expected {expected}"

    # gate: survivors are bitwise equal to the fault-free run (the two
    # contained decode faults left all state untouched; slot isolation
    # kept the killed requests' rows from touching anyone else's)
    for j, rid in enumerate(rids):
        if statuses[rid] is RequestStatus.DONE:
            assert loop.results[rid].tokens == baseline_tokens[j], (
                f"survivor rid {rid} diverged from fault-free run"
            )
    # gate: the timed-out request got a clean prefix, not garbage
    t_toks = loop.results[rids[3]].tokens
    assert t_toks == baseline_tokens[3][: len(t_toks)], (
        "TIMEOUT tokens are not a prefix of the fault-free stream"
    )
    assert loop.stats["decode_retries"] == 2
    return {
        "statuses": {int(r): str(s) for r, s in statuses.items()},
        "tokens": {int(r): loop.results[r].tokens for r in rids},
        "fired": [list(ev) for ev in fp.fingerprint()],
        "fault_counts": fp.counts(),
        "stats": loop.resilience_stats(),
    }


def serving_leg(lm, params, args):
    cfg = get_arch(args.arch).reduced()
    prompts = mixed_trace(args.requests, cfg.vocab, args.lengths, args.seed)

    # fault-free baseline: ample queue, no deadlines, everything DONE
    sc = ServeConfig(batch=args.batch, seq_len=args.seq_len, dtype="float32",
                     queue_capacity=args.requests + 8)
    base_loop = ServeLoop(lm, params, sc, seed=args.seed)
    _warmup(base_loop, args.lengths, cfg.vocab)
    t0 = time.perf_counter()
    base_rids = _drive(base_loop, prompts, args.max_new)
    base_wall = time.perf_counter() - t0
    assert all(
        base_loop.results[r].status is RequestStatus.DONE for r in base_rids
    )
    baseline_tokens = [base_loop.results[r].tokens for r in base_rids]

    # the same trace under fire, twice — determinism is a gate
    run1 = _chaos_serving_run(lm, params, args, {}, baseline_tokens)
    run2 = _chaos_serving_run(lm, params, args, {}, baseline_tokens)
    assert run1["fired"] == run2["fired"], "fault sequence not deterministic"
    assert run1["statuses"] == run2["statuses"]
    assert run1["tokens"] == run2["tokens"], "chaos outputs not deterministic"

    survivors = sum(
        1 for s in run1["statuses"].values() if s == str(RequestStatus.DONE)
    )
    run1.pop("tokens")  # bulky; the bitwise gate already consumed them
    return {
        "baseline": {
            "wall_s": round(base_wall, 4),
            "requests": args.requests,
            "done": len(base_rids),
        },
        "chaos": run1,
        "survivors_bitwise_ok": True,
        "deterministic_replay_ok": True,
        "survivors": survivors,
    }


def degradation_leg(seed: int):
    """The kernel and gather fallback chains on a small random matrix."""
    rng = np.random.default_rng(seed)
    m, n, b = 64, 96, 4
    mask = rng.random((m, n)) < 0.1
    dense = np.where(mask, rng.standard_normal((m, n)), 0.0).astype(np.float32)
    x = np.asarray(rng.standard_normal((n, b)), np.float32)
    fb0 = dict(fallback_counters)

    # pallas kernel fault -> jnp executor (tolerance-equal, not bitwise)
    p_jnp = plan(dense, l=32, backend="jnp", gather="resident", cache=None)
    y_ref = np.asarray(p_jnp.spmm(x))
    assert np.allclose(y_ref, dense @ x, rtol=1e-4, atol=1e-5)
    p_pal = plan(dense, l=32, backend="pallas", interpret=True,
                 gather="resident", cache=None)
    with faults.injected(FaultPlan(
            [FaultSpec("kernel.execute", tag="pallas")], seed=seed)):
        y_k = np.asarray(p_pal.spmm(x))
    assert fallback_counters["pallas_to_jnp"] == fb0["pallas_to_jnp"] + 1, (
        "kernel fallback not counted"
    )
    assert np.allclose(y_k, y_ref, rtol=1e-5, atol=1e-6), (
        "degraded kernel result diverged beyond tolerance"
    )

    # local-gather fault -> resident (bitwise: the PR 5 contract)
    p_res = plan(dense, l=32, backend="jnp", gather="resident", cache=None)
    y_res = np.asarray(p_res.spmm(x))
    p_loc = plan(dense, l=32, backend="jnp", gather="local", cache=None)
    with faults.injected(FaultPlan([FaultSpec("gather.local")], seed=seed)):
        y_g = np.asarray(p_loc.spmm(x))
    assert fallback_counters["local_to_resident"] == fb0["local_to_resident"] + 1
    assert np.array_equal(y_g, y_res), "local->resident fallback not bitwise"

    pc = p_pal.cost()
    return {
        "kernel_fallbacks": 1,
        "kernel_allclose_ok": True,
        "gather_fallbacks": 1,
        "gather_bitwise_ok": True,
        "cost_fallback_fields": {
            "fallback_kernel": pc.fallback_kernel,
            "fallback_gather": pc.fallback_gather,
        },
    }


def store_leg(lm, params, args):
    """Warm gustify() under a failing plan store: every read degrades
    stored -> fresh, counted, and the rebuilt stacks are bitwise equal
    to the cold build (the PR 7 warm==cold contract)."""
    density = 0.05 if args.tiny else 0.1
    reps = lm.stack.reps
    with tempfile.TemporaryDirectory() as d:
        gcfg = GustServeConfig(density=density, gust_length=64,
                               mats=("w_gate",), plan_store=d)
        cold = gustify(lm, params, gcfg)
        warm = gustify(lm, params, gcfg)
        assert warm["stats"]["plan_store"]["hits"] >= reps
        assert "fallbacks" not in warm["stats"]

        fp = FaultPlan(
            [
                FaultSpec("store.get", error=OSError, times=-1),
                FaultSpec("pack.materialize", kind="delay",
                          delay_s=0.002, times=2),
            ],
            seed=args.seed,
        )
        with faults.injected(fp):
            chaos = gustify(lm, params, gcfg)
        assert chaos["stats"]["fallbacks"]["stored_to_fresh"] == reps, (
            "every failed store read must be a counted stored->fresh fallback"
        )
        cold_leaves = cold["mats"]["0.w_gate"]["leaves"]
        chaos_leaves = chaos["mats"]["0.w_gate"]["leaves"]
        for k in cold_leaves:
            assert np.array_equal(
                np.asarray(cold_leaves[k]), np.asarray(chaos_leaves[k])
            ), f"stored->fresh rebuild not bitwise at leaf {k!r}"
        return {
            "reps": reps,
            "stored_to_fresh": reps,
            "store_io_errors": chaos["stats"]["plan_store"]["io_errors"],
            "store_io_retries": chaos["stats"]["plan_store"]["io_retries"],
            "fault_counts": fp.counts(),
            "rebuild_bitwise_ok": True,
        }


def overhead_leg(iters: int = 200_000):
    """Per-call cost of a disabled trip() vs an empty loop iteration.
    Report-only: shared CI runners are too noisy for a nanosecond gate."""
    faults.clear()
    t0 = time.perf_counter()
    for _ in range(iters):
        faults.trip("kernel.execute")
    t1 = time.perf_counter()
    acc = 0
    for _ in range(iters):
        acc += 1
    t2 = time.perf_counter()
    return {
        "iters": iters,
        "disabled_trip_ns": round((t1 - t0) / iters * 1e9, 1),
        "empty_loop_ns": round((t2 - t1) / iters * 1e9, 1),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--lengths", type=int, nargs="+", default=[4, 12, 6, 16])
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke preset: fewest requests/steps that still "
                    "exercise every terminal status and fallback chain")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.tiny:
        args.requests, args.max_new, args.lengths = 5, 4, [3, 7]
    if args.out is None:
        args.out = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_chaos_tiny.json" if args.tiny else "BENCH_chaos.json",
        )
    # the fault schedule targets trace indices 1/2/3 and sheds the last
    assert args.requests >= 5, "chaos trace needs >= 5 requests"
    assert args.max_new >= 3, "deadline_steps=2 must fire before max_new"

    cfg = get_arch(args.arch).reduced()
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(args.seed))

    report = {
        "arch": args.arch,
        "batch": args.batch,
        "requests": args.requests,
        "max_new": args.max_new,
        "prompt_lengths": args.lengths,
        "serving": serving_leg(lm, params, args),
        "degradation": degradation_leg(args.seed),
        "store": store_leg(lm, params, args),
        "disabled_overhead": overhead_leg(20_000 if args.tiny else 200_000),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    print(
        "PASS: zero lost requests, bitwise survivors, deterministic "
        "replay, all three fallback chains counted"
    )


if __name__ == "__main__":
    main()
