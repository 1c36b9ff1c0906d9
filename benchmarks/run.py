"""Benchmark harness: one module per paper table/figure + every PR's
acceptance-gate family.
``python -m benchmarks.run [--full] [--only fig7,pack,spgemm,...]``.

Default (quick) mode scales the Table-3 surrogate suite to 4% of the
published dimensions so the full harness finishes in minutes on one CPU
core; ``--full`` uses larger surrogates (same structure, same scheduler).

The PR-gate families (``pack``, ``ragged``, ``gather``, ``kernel``,
``sched``, ``serve``, ``spgemm``) run in their ``--tiny``/quick modes —
one command reproduces every ``BENCH_*.json`` record (tiny records land
in the ``BENCH_*_tiny.json`` siblings, never clobbering the committed
full-run files).
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="", help="comma list: fig7,fig8,fig9,"
                    "table4,bound,pack,ragged,gather,kernel,sched,"
                    "serve,spgemm,chaos")
    args = ap.parse_args(argv)
    scale = 0.12 if args.full else 0.04
    only = set(args.only.split(",")) if args.only else None

    from . import (bound_validation, chaos_bench, fig7_designs,
                   fig8_speedup_energy, fig9_bandwidth, gather_bench,
                   kernel_bench, pack_bench, ragged_bench, sched_bench,
                   serve_bench, spgemm_bench, table4_serpens)

    jobs = [
        ("fig7", lambda: fig7_designs.run(scale=scale)),
        ("fig8", lambda: fig8_speedup_energy.run(scale=scale)),
        ("fig9", lambda: fig9_bandwidth.run(scale=scale)),
        ("table4", lambda: table4_serpens.run(scale=scale)),
        ("bound", lambda: bound_validation.run()),
        # PR acceptance-gate families, each in its quick/--tiny mode
        ("pack", lambda: pack_bench.main(["--tiny"])),
        ("ragged", lambda: ragged_bench.main(["--tiny"])),
        ("gather", lambda: gather_bench.main(["--tiny"])),
        ("kernel", lambda: kernel_bench.main(["--tiny"])),
        ("sched", lambda: sched_bench.main(["--tiny"])),
        ("serve", lambda: serve_bench.main(["--tiny"])),
        ("spgemm", lambda: spgemm_bench.main(["--tiny"])),
        ("chaos", lambda: chaos_bench.main(["--tiny"])),
    ]
    rc = 0
    for name, fn in jobs:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            fn()
            print(f"[bench:{name}] done in {time.time()-t0:.1f}s\n")
        except Exception as e:  # keep the harness going
            print(f"[bench:{name}] FAILED: {type(e).__name__}: {e}\n")
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
