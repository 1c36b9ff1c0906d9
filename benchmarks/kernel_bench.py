"""Kernel-speed benchmark: double-buffered streaming, int8 values, tune.

Three claims from the kernel-speed PR, each measured the most honest way
this host allows (the committed BENCH_kernel.json is produced on a CPU
host where Pallas runs in interpret mode, so interpret-mode wall clock is
*report-only* everywhere and each gate measures the mechanism itself):

1. **Double-buffered tile streaming** — the DB kernels overlap the DMA
   fetching tile ``s+1`` with the accumulate of tile ``s``.  Interpret
   mode executes ``make_async_copy`` synchronously (no DMA engine), so
   the bench runs the same two-slot ping/pong pipeline at the host
   level: a producer memcpy-ing stream tiles into ping/pong slots (the
   DMA stand-in) and a consumer running the accumulate matmul, with the
   math stage auto-calibrated to the measured fetch bandwidth (the
   balanced regime double-buffering targets).  The gate is the
   *measured-stage overlap*: ``(t_fetch + t_math) / max(t_fetch,
   t_math)`` from the two separately measured stage times — what a
   concurrent DMA engine turns serial time into — and must reach
   ``--min-db-speedup`` (default 1.3x) at n >= 64k.  The threaded
   end-to-end wall clock is recorded too, but it is only gated when the
   host has more than one CPU core (on a single-core container no two
   stages can physically co-execute, hardware DMA engine or not).
   Kernel single-vs-double bit-identity is asserted here and locked by
   tests/test_quant_property.

2. **int8 per-block-scaled values** — the win is bandwidth: the value
   stream shrinks 4x (plus one f32 scale per ``c_blk`` block).  Gates:
   the measured *drain* of the value stream (memcpy through the host
   memory system, the bandwidth-bound stage) must speed up
   ``--min-int8-speedup`` (default 1.5x), and the deterministic packed
   ``stream_bytes`` ratio (values + indices + scales) must shrink
   ``--min-bytes-ratio`` (default 1.25x, exact arithmetic — stays hard).
   End-to-end int8-vs-f32 outputs are asserted within quantization
   tolerance; interpret wall clock is reported.

3. **Measured autotuner** — ``GustPlan.tune`` on the gather-bench matrix
   suite must return a plan no slower than the static
   ``resolve_layout``/``resolve_gather`` defaults (``--tune-tolerance``
   headroom for timer noise): ``resolve_tuning`` falls back to the
   baseline unless a candidate measures faster, so tuning can only help.

4. **Cost per color block on the chip** (``--block-cost``, run on a
   TPU, alone): the compiled double-buffered resident kernel every
   benchmark cell runs, at the cells' geometries, on random
   lane-structured collision-free streams (timing does not depend on
   the values); median of ``--iters`` samples of 20 calls in a row
   after a warm-up, reported as microseconds per color block.  The stream's routing
   operand is whatever the installed kernel takes (the adder index or
   the source-lane table, read off ``route_rows``'s signature), so the
   same script measures two checkouts of the program side by side.

Usage:
    PYTHONPATH=src python benchmarks/kernel_bench.py
        [--widths 16384 65536] [--iters 5] [--tiny] [--out BENCH_kernel.json]
    PYTHONPATH=src python benchmarks/kernel_bench.py --block-cost \
        [--geometries mousegene yi_w_up yi_w_down mellum_w_up mellum_w_down]
        [--iters 7] [--out block_cost.json]

``--tiny`` (CI smoke): small widths, every wall-clock gate report-only,
separate output file — never clobbers the committed full-run record.
"""

import argparse
import json
import os
import threading
import time

import numpy as np

import jax.numpy as jnp

from repro.core.plan import PlanConfig, plan

try:  # script execution (benchmarks/ on sys.path)
    from gather_bench import bench, synth_local_schedule
except ImportError:  # package execution (python -m benchmarks.run)
    from benchmarks.gather_bench import bench, synth_local_schedule

L = 128
TILE_ROWS = 8192  # stream-tile height for the pipeline emulation


# ---------------------------------------------------------------------------
# 1. double-buffered streaming
# ---------------------------------------------------------------------------


def _pipeline_emulation(n: int, iters: int, rng) -> dict:
    """Serial vs two-slot double-buffered stream pipeline on host threads.

    The stream is ``n // 1024`` distinct f32 tiles of ``TILE_ROWS`` rows
    (tens of MB at n >= 64k — well past cache, so the fetch stage is
    genuinely memory-bound); per tile the consumer runs the
    accumulate-stage matmul ``S (w, R) @ tile (R, b)``.  ``w`` is
    calibrated so the math stage roughly matches the measured fetch
    bandwidth — the balanced regime where overlapping fetch and compute
    pays (heavily skewed stages make *any* pipeline a no-op; the DB
    kernels target the balanced bandwidth-bound one).  The producer is
    one persistent thread feeding two ping/pong slots through a pair of
    semaphores — the same depth-2 pattern the kernels run with
    ``make_async_copy`` + a DMA semaphore pair, with numpy's
    GIL-releasing memcpy standing in for the DMA engine.
    """
    batch = 16
    num_tiles = max(n // 1024, 4)
    tiles = rng.standard_normal((num_tiles, TILE_ROWS, batch)).astype(
        np.float32
    )
    slots = np.empty((2, TILE_ROWS, batch), np.float32)

    # calibrate the math width w to the fetch time of one tile
    t0 = time.perf_counter()
    for i in range(num_tiles):
        np.copyto(slots[i % 2], tiles[i])
    t_fetch = (time.perf_counter() - t0) / num_tiles
    w, t_math = 8, 0.0
    while w <= 1024:
        s_mat = rng.standard_normal((w, TILE_ROWS)).astype(np.float32)
        t0 = time.perf_counter()
        for i in range(4):
            s_mat @ slots[i % 2]
        t_math = (time.perf_counter() - t0) / 4
        if t_math >= t_fetch:
            break
        w *= 2

    def serial() -> np.ndarray:
        acc = np.zeros((w, batch), np.float32)
        for i in range(num_tiles):
            np.copyto(slots[0], tiles[i])  # fetch ...
            acc += s_mat @ slots[0]  # ... then compute, one slot
        return acc

    def double() -> np.ndarray:
        free = threading.Semaphore(2)  # both slots start writable
        ready = threading.Semaphore(0)

        def producer():
            for i in range(num_tiles):
                free.acquire()
                np.copyto(slots[i % 2], tiles[i])
                ready.release()

        th = threading.Thread(target=producer)
        th.start()
        acc = np.zeros((w, batch), np.float32)
        for i in range(num_tiles):
            ready.acquire()  # wait for tile i's DMA
            acc += s_mat @ slots[i % 2]
            free.release()  # slot reusable: prefetch of i+2 may start
        th.join()
        return acc

    assert np.array_equal(serial(), double()), "pipeline emulation diverged"
    t_serial = bench(serial, iters)
    t_double = bench(double, iters)
    # stage times re-measured whole-stream (not per-tile estimates)
    t0 = time.perf_counter()
    for i in range(num_tiles):
        np.copyto(slots[i % 2], tiles[i])
    t_fetch_all = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(num_tiles):
        s_mat @ slots[i % 2]
    t_math_all = time.perf_counter() - t0
    modeled = (t_fetch_all + t_math_all) / max(t_fetch_all, t_math_all)
    return {
        "n": n,
        "tiles": num_tiles,
        "tile_bytes": int(slots[0].nbytes),
        "stream_mb": round(tiles.nbytes / 2**20, 1),
        "math_width": w,
        "host_cores": os.cpu_count(),
        "t_fetch_s": round(t_fetch_all, 5),
        "t_math_s": round(t_math_all, 5),
        "db_speedup_modeled": round(modeled, 2),
        "serial_s": round(t_serial, 5),
        "double_s": round(t_double, 5),
        "db_speedup_measured": round(t_serial / t_double, 2),
    }


def _interpret_db_check(iters: int) -> dict:
    """Kernel-level single vs double pipeline: bitwise equality (hard)
    and interpret-mode wall clock (report-only — interpret runs the
    async copies synchronously, so no overlap is observable here)."""
    sched = synth_local_schedule(4, 32, 1024, 2, c_w=8)
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((1024, 4)), jnp.float32
    )
    plans = {
        pipe: plan(
            sched,
            PlanConfig(layout="padded", backend="pallas", interpret=True,
                       c_blk=8, pipeline=pipe),
            cache=None,
        )
        for pipe in ("single", "double")
    }
    y_single = np.asarray(plans["single"].spmm(x))
    y_double = np.asarray(plans["double"].spmm(x))
    assert np.array_equal(y_single, y_double), \
        "single/double kernel outputs diverged"
    t_single = bench(lambda: plans["single"].spmm(x).block_until_ready(), iters)
    t_double = bench(lambda: plans["double"].spmm(x).block_until_ready(), iters)
    return {
        "bitwise_equal": True,
        "interpret_single_s": round(t_single, 5),
        "interpret_double_s": round(t_double, 5),
    }


# ---------------------------------------------------------------------------
# 2. int8 per-block-scaled values
# ---------------------------------------------------------------------------


def _int8_section(n: int, batch: int, iters: int, rng) -> dict:
    sched = synth_local_schedule(32, L, n, 4, c_w=32)
    plans = {
        vd: plan(
            sched,
            PlanConfig(layout="padded", backend="pallas", interpret=True,
                       c_blk=32, value_dtype=vd, index_dtype="int16"),
            cache=None,
        )
        for vd in ("float32", "int8")
    }
    bytes_f32 = plans["float32"].artifact.stream_bytes
    bytes_int8 = plans["int8"].artifact.stream_bytes

    # bandwidth-bound stage: drain the value stream through memory
    v_f32 = np.asarray(plans["float32"].artifact.m_blk)
    v_int8 = np.asarray(plans["int8"].artifact.m_blk)
    sink_f32, sink_int8 = np.empty_like(v_f32), np.empty_like(v_int8)

    def drain(sink, src):  # several passes per sample to outrun the timer
        def fn():
            for _ in range(16):
                np.copyto(sink, src)
        return fn

    t_drain_f32 = bench(drain(sink_f32, v_f32), iters)
    t_drain_int8 = bench(drain(sink_int8, v_int8), iters)

    x = jnp.asarray(rng.standard_normal((n, batch)), jnp.float32)
    y_f32 = np.asarray(plans["float32"].spmm(x))
    y_int8 = np.asarray(plans["int8"].spmm(x))
    # per-block absmax/127 quantization error bound on the accumulate
    scale = np.asarray(plans["int8"].artifact.scale_blk)
    err = np.abs(y_int8 - y_f32).max()
    tol = 0.5 * scale.max() * 32 * np.abs(np.asarray(x)).max() * 4
    assert err <= tol, f"int8 output error {err} above quant bound {tol}"
    t_f32 = bench(lambda: plans["float32"].spmm(x).block_until_ready(), iters)
    t_int8 = bench(lambda: plans["int8"].spmm(x).block_until_ready(), iters)
    return {
        "n": n,
        "batch": batch,
        "stream_bytes_f32": int(bytes_f32),
        "stream_bytes_int8": int(bytes_int8),
        "stream_bytes_ratio": round(bytes_f32 / bytes_int8, 2),
        "value_bytes_ratio": round(v_f32.nbytes / v_int8.nbytes, 2),
        "drain_f32_s": round(t_drain_f32, 6),
        "drain_int8_s": round(t_drain_int8, 6),
        "drain_speedup": round(t_drain_f32 / t_drain_int8, 2),
        "max_output_err": float(err),
        "interpret_f32_s": round(t_f32, 5),
        "interpret_int8_s": round(t_int8, 5),
    }


# ---------------------------------------------------------------------------
# 3. measured autotuner vs static defaults
# ---------------------------------------------------------------------------


def _tune_section(n: int, batch: int, iters: int, rng) -> dict:
    sched = synth_local_schedule(32, L, n, 4, c_w=16)
    cfg = PlanConfig(layout="auto", gather="auto", backend="jnp", c_blk=16)
    static = plan(sched, cfg, cache=None)
    x = jnp.asarray(rng.standard_normal((n, max(batch, 16))), jnp.float32)
    # min_improvement=1.3: on a noisy shared host, only leave the static
    # baseline for a solid measured win (resolve_tuning falls back
    # otherwise) — this is what makes the no-slower gate meaningful
    tuned = static.tune(x, iters=max(iters, 8), warmup=2,
                        min_improvement=1.3)
    r = tuned.tuning
    t_static = bench(lambda: static.spmm(x).block_until_ready(),
                     max(iters, 8))
    t_tuned = bench(lambda: tuned.spmm(x).block_until_ready(),
                    max(iters, 8))
    key = lambda k: f"c_blk={k[0]},l={k[1]},{k[2]},{k[3]}"
    return {
        "n": n,
        "baseline": key(r.baseline),
        "choice": key(r.choice),
        "candidates_timed": len(r.measurements),
        "candidates_pruned": len(r.pruned),
        "cost_consistent": r.cost_consistent,
        "tune_improvement": round(r.improvement, 2),
        "static_s": round(t_static, 5),
        "tuned_s": round(t_tuned, 5),
        "tuned_vs_static": round(t_static / t_tuned, 2),
    }


# ---------------------------------------------------------------------------
# 4. cost per color block of the compiled kernel (TPU)
# ---------------------------------------------------------------------------

#: (num_windows, seg_count, c_pad, batch) of the benchmark cells' GUST
#: matrices at l = 256: mouse_gene at B=1, yi_6b's MLP at the chat
#: cell's batch 8, Mellum2's experts at batch 64; ``tiny`` for a CPU run.
BLOCK_GEOMETRIES = {
    "mousegene": (177, 177, 888, 1),
    "yi_w_up": (43, 16, 552, 8),
    "yi_w_down": (16, 43, 1408, 8),
    "mellum_w_up": (4, 9, 296, 64),
    "mellum_w_down": (9, 4, 152, 64),
    "tiny": (2, 3, 16, 8),
}


def _random_stream(rows: int, l: int, seg_count: int, rng):
    """A collision-free lane-structured stream: every slot real, each
    cycle row's adders a permutation of the lanes (drawn from a pool),
    columns in a random segment at the slot's lane or its reverse.
    Returns ``(m, col, row, src)``."""
    pool = np.argsort(rng.random((256, l)), axis=1).astype(np.int32)
    row = pool[rng.integers(0, pool.shape[0], rows)]
    src = np.argsort(row, axis=1).astype(np.int32)
    lane = np.arange(l, dtype=np.int32)
    flip = rng.random((rows, l)) < 0.5
    col = (rng.integers(0, seg_count, (rows, l), dtype=np.int32) * l
           + np.where(flip, l - 1 - lane, lane))
    m = rng.standard_normal((rows, l)).astype(np.float32)
    return m, col.astype(np.int32), row, src


def _block_cost(name: str, iters: int, rng, l: int = 256, c_blk: int = 8,
                reps: int = 20):
    """Microseconds per color block of ``gust_spmv_padded_resident_db``
    at one geometry: each of ``iters`` samples times ``reps`` calls in a
    row and waits once, so the host's dispatch is not counted."""
    import inspect

    import jax

    from repro.kernels import gust_spmv as K

    w, seg, c_pad, b = BLOCK_GEOMETRIES[name]
    rows = w * c_pad
    m, col, row, src = _random_stream(rows, l, seg, rng)
    routing = "src" if "src_blk" in inspect.signature(
        K.route_rows).parameters else "row"
    x = rng.standard_normal((b, K._resident_x_rows(seg), l)).astype(
        np.float32)
    args = [jnp.asarray(a) for a in
            (m, col, src if routing == "src" else row, x)]
    fn = jax.jit(K.make_gust_spmv_db(w, c_pad, l, seg, b, c_blk=c_blk))
    out = fn(*args)
    out.block_until_ready()
    times = []
    for _ in range(iters):  # back-to-back calls: dispatch hides behind
        t0 = time.perf_counter()  # the device, which then never idles
        for _ in range(reps):
            y = fn(*args)
        y.block_until_ready()
        times.append((time.perf_counter() - t0) / reps)
    t = float(np.median(times))
    blocks = rows // c_blk
    return {
        "geometry": name, "num_windows": w, "seg_count": seg,
        "c_pad": c_pad, "b": b, "blocks": blocks, "routing": routing,
        "call_ms": t * 1e3, "us_per_block": t / blocks * 1e6,
        "times_ms": [x * 1e3 for x in times],
        "out_sha1": __import__("hashlib").sha1(
            np.asarray(out).tobytes()).hexdigest(),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", type=int, nargs="+", default=[16384, 65536])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--min-db-speedup", type=float, default=1.3,
                    help="fail if the double-buffered pipeline emulation "
                    "is not at least this much faster at n >= 64k "
                    "(0 = report-only)")
    ap.add_argument("--min-int8-speedup", type=float, default=1.5,
                    help="fail if the int8 value-stream drain is not at "
                    "least this much faster (0 = report-only)")
    ap.add_argument("--min-bytes-ratio", type=float, default=1.25,
                    help="fail if int8 packing shrinks total stream bytes "
                    "less than this (deterministic — stays hard)")
    ap.add_argument("--tune-tolerance", type=float, default=1.15,
                    help="fail if the tuned plan is more than this factor "
                    "slower than the static defaults")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: small widths, wall-clock gates "
                    "report-only, separate output file")
    ap.add_argument("--out", default=None)
    ap.add_argument("--block-cost", action="store_true",
                    help="only time the compiled kernel per color block "
                    "(section 4; on a TPU)")
    ap.add_argument("--geometries", nargs="+",
                    default=[g for g in BLOCK_GEOMETRIES if g != "tiny"],
                    choices=sorted(BLOCK_GEOMETRIES))
    args = ap.parse_args(argv)
    if args.block_cost:
        rows = []
        for g in args.geometries:
            rows.append(_block_cost(g, args.iters, np.random.default_rng(0)))
            print(json.dumps(rows[-1]), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
        return
    if args.tiny:
        args.widths = [16384]
        args.batch = min(args.batch, 4)
        args.iters = min(args.iters, 3)
        args.min_db_speedup = 0.0
        args.min_int8_speedup = 0.0
        args.tune_tolerance = 0.0
    if args.out is None:
        args.out = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_kernel_tiny.json" if args.tiny else "BENCH_kernel.json",
        )
    rng = np.random.default_rng(0)

    db_rows = [_pipeline_emulation(n, args.iters, rng) for n in args.widths]
    for r in db_rows:
        print(f"[db]   n={r['n']:>7}  fetch {r['t_fetch_s']*1e3:7.2f} ms + "
              f"math {r['t_math_s']*1e3:7.2f} ms -> overlap model "
              f"{r['db_speedup_modeled']:.2f}x; threaded "
              f"{r['serial_s']*1e3:.2f} -> {r['double_s']*1e3:.2f} ms "
              f"({r['db_speedup_measured']:.2f}x on {r['host_cores']} "
              f"core(s))")
    db_kernel = _interpret_db_check(args.iters)
    print(f"[db]   kernel single/double bitwise-equal; interpret "
          f"{db_kernel['interpret_single_s']*1e3:.1f} / "
          f"{db_kernel['interpret_double_s']*1e3:.1f} ms (report-only)")

    int8_rows = [_int8_section(n, args.batch, args.iters, rng)
                 for n in args.widths]
    for r in int8_rows:
        print(f"[int8] n={r['n']:>7}  stream bytes {r['stream_bytes_ratio']:.2f}x"
              f" smaller; value drain {r['drain_speedup']:.2f}x faster; "
              f"max |y_int8 - y_f32| = {r['max_output_err']:.4f}")

    tune_rows = [_tune_section(min(n, 16384), args.batch, args.iters, rng)
                 for n in args.widths[:1]]
    for r in tune_rows:
        print(f"[tune] n={r['n']:>7}  {r['baseline']} -> {r['choice']} "
              f"({r['tune_improvement']:.2f}x measured; tuned vs static "
              f"{r['tuned_vs_static']:.2f}x; pruned {r['candidates_pruned']})")

    payload = {
        "bench": "double-buffered streaming, int8 values, measured tuner",
        "double_buffering": {"pipeline_emulation": db_rows,
                             "kernel_check": db_kernel},
        "int8": int8_rows,
        "tune": tune_rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    print("wrote", args.out)

    wide = [r for r in db_rows if r["n"] >= 65536]
    if args.min_db_speedup > 0 and wide:
        worst = min(r["db_speedup_modeled"] for r in wide)
        if worst < args.min_db_speedup:
            raise SystemExit(
                f"FAIL: measured-stage overlap model only {worst}x "
                f"(< {args.min_db_speedup}x) at n >= 64k"
            )
        if (os.cpu_count() or 1) > 1:
            worst = min(r["db_speedup_measured"] for r in wide)
            if worst < args.min_db_speedup:
                raise SystemExit(
                    f"FAIL: threaded double-buffered pipeline only "
                    f"{worst}x faster (< {args.min_db_speedup}x) at "
                    f"n >= 64k on a multi-core host"
                )
    if args.min_int8_speedup > 0:
        worst = min(r["drain_speedup"] for r in int8_rows)
        if worst < args.min_int8_speedup:
            raise SystemExit(
                f"FAIL: int8 value-stream drain only {worst}x faster "
                f"(< {args.min_int8_speedup}x)"
            )
    worst_bytes = min(r["stream_bytes_ratio"] for r in int8_rows)
    if worst_bytes < args.min_bytes_ratio:
        raise SystemExit(
            f"FAIL: int8 stream only {worst_bytes}x smaller "
            f"(< {args.min_bytes_ratio}x)"
        )
    if args.tune_tolerance > 0:
        worst = max(r["tuned_s"] / max(r["static_s"], 1e-12)
                    for r in tune_rows)
        if worst > args.tune_tolerance:
            raise SystemExit(
                f"FAIL: tuned plan {worst:.2f}x slower than the static "
                f"defaults (> {args.tune_tolerance}x tolerance)"
            )


if __name__ == "__main__":
    main()
