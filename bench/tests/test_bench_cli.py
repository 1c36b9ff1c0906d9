"""The command: it refuses to run without a TPU or without the program,
and finds every cell's files by name, so that a cell, a configuration, a
mix or a metric is added by adding files and an entry."""

import json
import os
import shutil
import subprocess
import sys

import benchtools

RUN = os.path.join(benchtools.BENCH, "run.py")


def _cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _cli(["--workload", "mousegene.spmv-power", "--seed", "5",
              "--seconds", "1", "--trace", "0"], benchtools.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(benchtools.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(benchtools.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    p = _cli(["--workload", "yi6b-gust.chat-decode", "--seed", "5",
              "--seconds", "1", "--trace", "1"], tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_listing_finds_every_cells_files():
    p = _cli(["--list"], benchtools.ROOT)
    assert p.returncode == 0, p.stderr
    cells = [json.loads(x) for x in p.stdout.splitlines()]
    with open(os.path.join(benchtools.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert [c["workload"] for c in cells] == [w["name"]
                                              for w in spec["workloads"]]
    for c in cells:
        for path in [c["config"], c["traffic"], c["kind"], c["driver"]] + [
                c[k] for k in ("structure",) if k in c] + [
                m for ms in c["metrics"].values() for m in ms]:
            assert os.path.isfile(os.path.join(benchtools.ROOT, path)), path


def test_a_cell_is_added_by_files_and_an_entry(tmp_path):
    from lib import harness

    root = benchtools.scratch_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    # a new mix, a new configuration and a new per-layer metric: files
    (root / "bench" / "traffic" / "new-mix.json").write_text(
        json.dumps(dict(benchtools.TINY_CHAT, name="new-mix", clients=3)))
    (root / "bench" / "configs" / "new-lm.json").write_text(
        json.dumps(dict(benchtools.TINY_LM, name="new-lm")))
    spec["workloads"].append({"name": "new.cell", "config": "new-lm",
                              "traffic": "new-mix", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "new_metric", "unit": "s",
                              "better": "lower", "source": "host_clock",
                              "layer": "serve loop", "moves": "setup_s",
                              "workloads": ["new.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    r = harness.resolve("new.cell", str(root))
    assert r["mix"]["clients"] == 3 and r["config"]["name"] == "new-lm"
    assert r["driver"].endswith("drivers/serving.py")
    assert r["metrics"]["per_layer"]["new_metric"]["reader"].endswith(
        "metrics/new_metric.py")
    assert "setup_s" in r["metrics"]["end_to_end"]


def test_peaks_are_keyed_by_device_kind():
    from lib import harness

    assert harness.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        harness.peak_for("TPU v9 imaginary")
    except harness.RunFailure:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")


FIXED_GAP = '''"""Open-loop arrivals every ``gap_s`` seconds, prompts of one length."""
import numpy as np
from lib import traffic


def generate(mix, seed, vocab):
    rng = traffic.rng_for(seed, 1)
    return {"arrivals": [(i * mix["gap_s"],
                          rng.integers(0, vocab, mix["prompt"], dtype=np.int32),
                          mix["max_new"]) for i in range(mix["requests"])]}
'''

DIAGONAL = '''"""A diagonal matrix with standard normal values."""
import numpy as np


def generate(n, nnz, spec, seed):
    rng = np.random.default_rng(seed)
    idx = np.arange(n)
    return (n, n), idx, idx, rng.standard_normal(n).astype(np.float32)
'''


def test_a_traffic_kind_and_a_matrix_structure_are_files(tmp_path):
    """A new kind of traffic (open-loop arrivals) and a new matrix
    structure are a file each beside the ones there; cells that use
    them run through the existing drivers and are correct."""
    root = benchtools.scratch_root(tmp_path)
    lib = root / "bench" / "lib"
    (lib / "kinds" / "fixed_gap.py").write_text(FIXED_GAP)
    (lib / "structures" / "diagonal.py").write_text(DIAGONAL)
    (root / "bench" / "traffic" / "trickle.json").write_text(json.dumps(
        {"kind": "fixed_gap", "gap_s": 0.05, "requests": 6, "prompt": 12,
         "max_new": 3}))
    spmv = dict(benchtools.TINY_SPMV)
    spmv["matrix"] = {"dim": 300, "nnz": 300, "structure": "diagonal"}
    (root / "bench" / "configs" / "diag.json").write_text(json.dumps(spmv))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"] += [
        {"name": "tiny.trickle", "config": "tiny-lm", "traffic": "trickle",
         "chips": 1, "why": "t"},
        {"name": "diag.power", "config": "diag", "traffic": "tiny-power",
         "chips": 1, "why": "t"}]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            w = m.get("workloads", [])
            if "tiny.chat" in w:
                w.append("tiny.trickle")
            if "tiny.power" in w:
                w.append("diag.power")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    line, rec = benchtools.bench_module("run.py", "bench_run").run(
        __import__("argparse").Namespace(
            workload="tiny.trickle", seed=2**32 + 1, seconds=0.6, trace=0,
            control=0), require_chip=False, root=str(root), with_record=True)
    assert line["correct"], line["compared"]
    dues = [r["due"] for r in rec["requests"]]
    assert all(d is not None for d in dues) and len(dues) >= 2
    assert all(r["sent"] >= r["due"] for r in rec["requests"])
    line, rec = benchtools.bench_module("run.py", "bench_run").run(
        __import__("argparse").Namespace(
            workload="diag.power", seed=5, seconds=0.3, trace=0, control=0),
        require_chip=False, root=str(root), with_record=True)
    assert rec["nnz"] == 300 and rec["shape"] == [300, 300]
    assert line["correct"], line["compared"]
