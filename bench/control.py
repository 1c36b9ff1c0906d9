#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's numbers
and its control's, seed by seed, in one process, at the cell's own size.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13

The control is the cell's configuration in its lower precision put in
the program's place, as ``bench/run.py --control 1`` runs it:

- served model: the plain reference with int8 weights; at the same
  prompts and served tokens, the token it puts first is scored as if it
  had been served.  One run gives the program's numbers and the
  control's;
- sparse library: the program's plan with its values in bfloat16,
  driven through the same power iteration: a run of its own after the
  program's, whose plan reuses the colouring the first left in the
  process.

A sound program reads below each limit, and the control has to read
above one of them, so that ``correct`` comes out false.  Prints one JSON
line per seed and run, and writes them all to ``--out``.  Needs the
chip, as a run does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import run as bench_run
    from lib.harness import RunFailure

    bench_run.worker_env()

    rows = []
    for seed in args.seeds:
        for control in (1, 0):
            ns = argparse.Namespace(workload=args.workload, seed=seed,
                                    seconds=args.seconds, trace=args.trace,
                                    record=None, control=control)
            try:
                line, record = bench_run.run(ns, with_record=True)
            except RunFailure as err:
                row = {"seed": seed, "control": control, "failure": str(err)}
            else:
                row = {"seed": seed, "control": control,
                       "check": record["check"], "correct": line["correct"],
                       "compared": line["compared"],
                       "metrics": line["metrics"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
            if control and all(k in row.get("check", {})
                               for k in row.get("compared", {})):
                break  # the control's run also read the program's numbers
    bench_run._stop_children()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
