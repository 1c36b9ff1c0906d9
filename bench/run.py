#!/usr/bin/env python3
"""Chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --list        # each cell's files, found by name

Sets the cell up (weights or matrix from the seed, the program's plans,
every shape warmed), measures a window of ``--seconds``, checks the
outputs of the timed path against the benchmark's plain reference, and
prints one JSON line last on standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``: each number
compared with its limit.  Those numbers are also the last lines on
standard error.

The run fails with a nonzero exit code and no result line when JAX finds
no TPU or fewer chips than the cell asks for, when the program is not
beside the benchmark, when a fallback fires, when a GUST kernel would
run interpreted, and when anything compiles inside the window.

JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when
that is set, else ``bench/.jax_cache``; traces are kept in
``bench/.traces``.  ``--control 1`` puts the cell's control in the
program's place (see ``bench/control.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--record", help="also write the run record (JSON) here")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the cell's control (the configuration's "
                    "lower precision) in the program's place; a sound "
                    "benchmark then reports correct: false")
    ap.add_argument("--list", action="store_true",
                    help="print each cell's files and metric readers")
    return ap.parse_args(argv)


def list_cells(root: str = ROOT) -> list:
    from lib import harness

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    out = []
    for name in names:
        r = harness.resolve(name, root)
        rel = lambda p: os.path.relpath(p, root)  # noqa: E731
        files = {
            "workload": name,
            "config": f"bench/configs/{r['config_name']}.json",
            "traffic": f"bench/traffic/{r['cell']['traffic']}.json",
            "kind": f"bench/lib/kinds/{r['mix']['kind']}.py",
            "driver": rel(r["driver"]),
            "metrics": {g: [rel(m["reader"]) for m in ms.values()]
                        for g, ms in r["metrics"].items()},
        }
        if "matrix" in r["config"]:
            files["structure"] = ("bench/lib/structures/"
                                  f"{r['config']['matrix']['structure']}.py")
        out.append(files)
    return out


def usable_cores() -> int:
    """The cores this process may run on: its affinity mask, cut to the
    cgroup's CPU quota where one is set (``os.cpu_count()`` reports the
    host's cores, which a machine with a quota does not have)."""
    cores = len(os.sched_getaffinity(0))
    try:
        with open("/sys/fs/cgroup/cpu.max") as f:
            quota, period = f.read().split()[:2]
        if quota != "max":
            cores = min(cores, max(1, -(-int(quota) // int(period))))
    except (OSError, ValueError):
        pass
    return cores


def worker_env() -> None:
    """The environment the program's colouring workers start with: one
    worker per core this process may use, and glibc's allocator told to
    keep freed arrays of up to 32 MiB on its heap and to return memory to
    the system only in steps of 1 GiB.  Each worker allocates and frees
    arrays of its chunk's size many times a second; returned to the
    system one by one, that memory is not reclaimed as fast on every
    host (section 7 of PERF.md).  Variables already set are kept."""
    os.environ.setdefault("REPRO_SCHED_WORKERS", str(usable_cores()))
    os.environ.setdefault("MALLOC_MMAP_THRESHOLD_", str(32 << 20))
    os.environ.setdefault("MALLOC_TRIM_THRESHOLD_", str(1 << 30))


def _setup_jax(cache_default: str):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_default)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def run(args, *, require_chip: bool = True, root: str = ROOT,
        with_record: bool = False):
    """One run; returns the result line (a dict), with ``with_record``
    also the run record, or raises RunFailure.  With ``args.control``
    the cell's control stands in the program's place."""
    from lib import harness

    resolved = harness.resolve(args.workload, root)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise harness.RunFailure("the program (src/repro) is not beside "
                                 "the benchmark")
    if os.path.join(root, "src") not in sys.path:
        sys.path.insert(0, os.path.join(root, "src"))
    jax = _setup_jax(os.path.join(root, "bench", ".jax_cache"))
    try:
        devices = jax.devices()
    except RuntimeError as err:
        raise harness.RunFailure(f"no accelerator: {err}")
    chips = int(resolved["cell"]["chips"])
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            raise harness.RunFailure(f"no TPU: JAX found {dev.platform}")
        if len(devices) < chips:
            raise harness.RunFailure(f"the cell needs {chips} chips, JAX "
                                     f"found {len(devices)}")
        peak = harness.peak_for(dev.device_kind, root)
    else:
        peak = harness.peak_for("TPU v5 lite", root)
    used = devices[:chips]
    ctx = harness.Context(resolved, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), peak=peak,
                          require_chip=require_chip, root=root,
                          control=bool(getattr(args, "control", 0)))
    driver = harness.load_module(resolved["driver"], "driver_" +
                                 resolved["config"]["kind"])
    from repro.resilience.fallback import fallback_counters

    fb0 = dict(fallback_counters)
    record = driver.run(ctx)
    fb = {k: v - fb0.get(k, 0) for k, v in fallback_counters.items()
          if v != fb0.get(k, 0)}
    ctx.require(not fb, f"fallbacks applied: {fb}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    if args.trace:
        tr = record.get("trace") or {}
        ctx.require(tr.get("busy_s", 0) > 0,
                    "the trace holds no device operation in the window")
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    metrics = harness.read_metrics(resolved, record, bool(args.trace))
    if getattr(args, "record", None):
        with open(args.record, "w") as f:
            json.dump(record, f, default=str)
    group = "per_layer" if args.trace else "end_to_end"
    missing = set(resolved["metrics"][group]) - set(metrics)
    ctx.require(not missing, f"metrics of the cell not measured: "
                f"{sorted(missing)}")
    line = harness.result_line(record, metrics, device, ctx.checks,
                               record["failed"])
    return (line, record) if with_record else line


def _stop_children() -> list:
    """Stop the program's coloring helpers; the command lines of any
    child process still running after that."""
    try:
        from repro.core.scheduler import stop_workers
    except ImportError:
        return []
    stop_workers()
    pids = []
    for task in os.listdir("/proc/self/task"):
        with open(f"/proc/self/task/{task}/children") as f:
            pids += f.read().split()
    left = []
    for pid in pids:
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                left.append(f.read().replace(b"\0", b" ").decode().strip())
        except OSError:
            pass
    return left


def main(argv=None) -> int:
    args = _args(argv)
    sys.path.insert(0, HERE)
    from lib.harness import RunFailure

    if args.list:
        for cell in list_cells():
            print(json.dumps(cell))
        return 0
    if not args.workload:
        print("bench: --workload is required", file=sys.stderr)
        return 2
    worker_env()
    try:
        line = run(args)
    except RunFailure as err:
        _stop_children()
        print(f"bench: FAILED: {err}", file=sys.stderr)
        return 1
    left = _stop_children()
    if left:
        print(f"bench: FAILED: processes still running: {left}",
              file=sys.stderr)
        return 1
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
