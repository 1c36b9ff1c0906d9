"""Driver for the sparse library: one ``GustPlan`` iterated many times.

Set-up: the configuration's Table-3 surrogate from the seed
(``lib/matrices``), ``repro.plan`` with the configuration's plan knobs
and every other at the program's default, coloured afresh, and
three warm-up iterations, the last of them timed.  Window: the power
iteration x <- A x / ||A x|| with the iterate kept on the device; each
call depends on the last, and about ``AHEAD_S`` seconds of calls are
kept in flight ahead of the host, so that the chip stays fed while the
host stands still.  When the window's time is up nothing more is sent,
every call sent is waited for, and the clock is read after that wait:
all of those calls count, over all of that time.
Check: once the window has closed, a sample of the window's calls drawn
from the seed (reservoir sampling, so every call is equally likely) is
recomputed as a float64 CSR product on the host, and the largest error
relative to the largest |y| is compared with the configuration's limit.
The control (``ctx.control``) is the same run with the configuration's
``control.plan`` knobs (its values in bfloat16) in the program's place.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import harness, matrices, traffic  # noqa: E402


def reference_product(shape, rows, cols, vals, x):
    """y = A x in float64, as a plain CSR (row-sorted COO) sum."""
    import numpy as np

    prod = np.asarray(vals, np.float64) * np.asarray(x, np.float64)[cols]
    return np.bincount(rows, weights=prod, minlength=shape[0])


def max_rel_error(samples, matrix) -> float:
    import numpy as np

    worst = 0.0
    for x, y in samples:
        ref = reference_product(*matrix, np.asarray(x))
        err = np.max(np.abs(np.asarray(y, np.float64) - ref))
        worst = max(worst, float(err / np.max(np.abs(ref))))
    return worst


def build_plan(ctx, matrix, **overrides):
    import jax

    from repro.core.formats import COOMatrix
    from repro.core.plan import PlanConfig, plan

    shape, rows, cols, vals = matrix
    coo = COOMatrix(shape, rows, cols, vals)
    t_p = time.perf_counter()
    p = plan(coo, PlanConfig(**dict(ctx.config["plan"], **overrides)))
    jax.block_until_ready(p.artifact)
    return p, time.perf_counter() - t_p


#: seconds of calls dispatched ahead of the one the host waits for
AHEAD_S = 5.0


def iterate(ctx, p, x0, seconds: float, sample_rng, samples: int):
    """The power iteration for ``seconds``: returns (calls, t0, t1,
    setup_s, [(x, y)] sampled calls)."""
    import collections
    import math

    import jax
    import jax.numpy as jnp

    normalize = jax.jit(lambda y: y / jnp.sqrt(jnp.sum(y * y)))
    x = jnp.asarray(x0)
    for _ in range(2):  # compile or load both programs
        x = normalize(p.spmv(x))
    jax.block_until_ready(x)
    t = time.perf_counter()
    x = jax.block_until_ready(normalize(p.spmv(x)))
    ahead = max(1, math.ceil(AHEAD_S / (time.perf_counter() - t)))
    kept, calls, inflight = [], 0, collections.deque()
    with ctx.window():
        t0 = time.perf_counter()
        setup_s = ctx.now_age()
        while time.perf_counter() - t0 < seconds:
            y = p.spmv(x)
            calls += 1
            if len(kept) < samples:
                kept.append((x, y))
            else:
                j = int(sample_rng.integers(0, calls))
                if j < samples:
                    kept[j] = (x, y)
            x = normalize(y)
            inflight.append(x)
            if len(inflight) > ahead:
                jax.block_until_ready(inflight.popleft())
        jax.block_until_ready(x)
        t1 = time.perf_counter()
    return calls, t0, t1, setup_s, kept


def run(ctx) -> dict:
    import jax
    import numpy as np

    cfg, mix = ctx.config, ctx.mix
    matrix = matrices.surrogate(cfg["matrix"], ctx.seed, ctx.structures_dir)
    shape, rows, cols, vals = matrix
    ctx.log(f"matrix drawn, {rows.shape[0]} nonzeros")
    # the control, where asked for, is the configuration's lower-precision
    # plan in the program's place
    overrides = cfg["control"]["plan"] if ctx.control else {}
    p, plan_build_s = build_plan(ctx, matrix, **overrides)
    ctx.log(f"plan built in {plan_build_s:.1f} s ({p.layout})")
    if ctx.require_chip:
        ctx.require(not p._interpret(), "GUST kernels would run interpreted")
    work = traffic.generate(mix, ctx.seed, shape[1], ctx.kinds_dir)
    calls, t0, t1, setup_s, kept = iterate(
        ctx, p, work["x0"], ctx.seconds, traffic.rng_for(ctx.seed, 2),
        int(mix["samples"]))
    rec = {
        "kind": "library",
        "setup_s": setup_s,
        "plan_build_s": plan_build_s,
        "window": {"t0": t0, "t1": t1, "seconds": t1 - t0},
        "calls": calls,
        "batch": int(mix["batch"]),
        "shape": list(shape),
        "nnz": int(rows.shape[0]),
        "use_kernel": bool(p._use_kernel()),
        "layout": p.layout,
        "memory_peak_bytes": harness.memory_peak_bytes(jax.devices()[:1]),
        "peak": ctx.peak,
        "attempted": calls,
        "failed": 0,
    }
    rec["trace"] = ctx.reduced_trace()
    ctx.log(f"window closed after {calls} calls")
    samples = [(np.asarray(x), np.asarray(y)) for x, y in kept]
    del kept, p
    err = max_rel_error(samples, matrix)
    rec["check"] = {"max_rel_error": err, "calls_compared": len(samples)}
    ctx.log(f"reference compared {len(samples)} calls")
    ctx.compare("max_rel_error", err, cfg["limits"]["max_rel_error"])
    return rec
