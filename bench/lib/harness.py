"""The harness: finds a cell's files by name, checks the device, runs the
cell's driver, reads its metrics and prints the one result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the configuration; its ``kind`` names
  the driver ``bench/drivers/<kind>.py``;
- ``bench/traffic/<traffic>.json``: the mix, read by ``lib/traffic.py``
  through the generator its ``kind`` names, ``bench/lib/kinds/<kind>.py``;
- a matrix's ``structure`` names its generator,
  ``bench/lib/structures/<structure>.py``;
- ``bench/metrics/<metric>.py``: a reader ``read(record)`` that returns
  the metric's value from the run record, or None when it finds nothing
  to read.

A driver's ``run(ctx)`` sets the cell up, measures its window and checks
its outputs; it returns the run record, a dict the metric readers read.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class RunFailure(Exception):
    """The run cannot report: no chip, a fallback, an interpreted kernel,
    a compile inside the window, a missing file."""


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    if not os.path.isfile(path):
        raise RunFailure(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: str = ROOT) -> dict:
    """The files and entries one cell uses, found by name."""
    bench_json = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench_json["workloads"]}
    if workload not in cells:
        raise RunFailure(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    bench = os.path.join(root, "bench")
    config = _json(os.path.join(bench, "configs", cell["config"] + ".json"))
    mix = _json(os.path.join(bench, "traffic", cell["traffic"] + ".json"))
    driver = os.path.join(bench, "drivers", config["kind"] + ".py")

    def applies(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    metrics = {}
    for group in ("end_to_end", "per_layer"):
        metrics[group] = {}
        for m in bench_json[group]:
            if applies(m):
                metrics[group][m["name"]] = dict(
                    m, reader=os.path.join(bench, "metrics", m["name"] + ".py"))
    return {"cell": cell, "config": config, "mix": mix, "driver": driver,
            "metrics": metrics, "config_name": cell["config"]}


def peak_for(kind: str, root: str = ROOT) -> dict:
    peaks = _json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if kind not in peaks:
        raise RunFailure(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


class CompileCounter:
    """Counts JAX compilations (tracing, lowering, backend compiles and
    compile-cache lookups) while armed."""

    def __init__(self):
        import jax

        self.armed, self.events = False, []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if self.armed and event.startswith("/jax/core/compile"):
            self.events.append(event)

    def _on_event(self, event, **kw):
        if self.armed and event.startswith(
                "/jax/compilation_cache/compile_requests"):
            self.events.append(event)

    @contextlib.contextmanager
    def window(self):
        self.events, self.armed = [], True
        try:
            yield
        finally:
            self.armed = False


class Context:
    """What a driver gets: the cell's entries, the seed and length of the
    run, the device, and the tools that time, trace and check it."""

    def __init__(self, resolved, *, seed, seconds, trace, peak,
                 require_chip=True, root=ROOT, control=False):
        self.cell, self.config = resolved["cell"], resolved["config"]
        self.mix = resolved["mix"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.peak = peak
        self.require_chip = require_chip
        self.control = control  # also read the control's number
        bench = os.path.join(root, "bench")
        self.kinds_dir = os.path.join(bench, "lib", "kinds")
        self.structures_dir = os.path.join(bench, "lib", "structures")
        self.trace_dir = os.path.join(bench, ".traces", self.cell["name"])
        self.compiles = CompileCounter()
        self.checks = []  # [name, value, limit, ok]
        self.age0 = process_age_s() - time.perf_counter()

    def now_age(self) -> float:
        """Seconds since the process started, on the perf counter."""
        return self.age0 + time.perf_counter()

    def log(self, what: str) -> None:
        """A phase of the run on standard error, with the process's age."""
        print(f"bench: {self.now_age():.1f} s: {what}", file=sys.stderr,
              flush=True)

    def compare(self, name: str, value: float, limit: float) -> None:
        """One number compared with its limit; ``correct`` needs all."""
        ok = value == value and value <= limit  # NaN fails
        self.checks.append([name, float(value), float(limit), bool(ok)])

    def require(self, cond, msg: str) -> None:
        if not cond:
            raise RunFailure(msg)

    @contextlib.contextmanager
    def window(self):
        """The measured window: no compile may happen in it, and with
        ``--trace 1`` it runs under the profiler, annotated."""
        import jax

        tracing = self.trace
        if tracing:
            import shutil

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
        try:
            with self.compiles.window():
                if tracing:
                    with jax.profiler.TraceAnnotation("bench_window"):
                        yield
                else:
                    yield
        finally:
            if tracing:
                jax.profiler.stop_trace()
        self.require(not self.compiles.events,
                     f"{len(self.compiles.events)} compilation events inside "
                     f"the window: {sorted(set(self.compiles.events))}")

    def reduced_trace(self) -> dict:
        """The reduced trace of the window; beside the profile it keeps
        ``sample.json``: the reduction and every event of the window's
        first 20 ms, for a look by hand."""
        from . import trace

        if not self.trace:
            return {}
        events = trace.load(self.trace_dir)
        out = trace.reduce(events)
        win = [e for e in events if e["name"] == trace.WINDOW]
        lo = win[0]["start_ns"] if win else 0.0
        first = [e for e in events if lo <= e["start_ns"] < lo + 2e7][:3000]
        with open(os.path.join(self.trace_dir, "sample.json"), "w") as f:
            json.dump({"reduced": out, "window": win, "first_20ms": first,
                       "planes": sorted({(e["plane"], e["line"])
                                         for e in events})}, f)
        return out


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def read_metrics(resolved: dict, record: dict, trace: bool) -> dict:
    """Each metric of the run's group through its own reader; a reader
    that finds nothing returns None and the metric is left out."""
    group = "per_layer" if trace else "end_to_end"
    out = {}
    for name, m in resolved["metrics"][group].items():
        if not os.path.isfile(m["reader"]):
            raise RunFailure(f"no reader bench/metrics/{name}.py")
        value = load_module(m["reader"], "metric_" + name.replace(".", "_")
                            ).read(record)
        if value is not None:
            out[name] = {"value": value, "unit": m["unit"]}
    return out


def result_line(record: dict, metrics: dict, device: dict, checks: list,
                failed: int) -> dict:
    line = {
        "correct": bool(checks) and all(c[3] for c in checks) and failed == 0,
        "attempted": record["attempted"],
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if record.get("trace"):
        line["breakdown"] = record["trace"]["breakdown"]
    line["compared"] = {c[0]: {"value": c[1], "limit": c[2]} for c in checks}
    return line
