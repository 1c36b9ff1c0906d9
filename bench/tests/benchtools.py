"""Helpers for the benchmark's CPU tests: a scratch checkout whose
``BENCHMARK.json`` holds tiny cells, with the real harness, drivers,
metric readers and program linked in."""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TINY_LM = {
    "name": "tiny-lm", "kind": "serving", "source": "test",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rope_theta": 5000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "program": {"arch": "yi_6b", "reduced": True},
    "gust": {"density": 0.5, "gust_length": 16, "use_kernel": False},
    "serve": {"batch": 2, "seq_len": 64, "dtype": "float32"},
    "control": {"reference": "int8"},
    # tiny-size limits, set from CPU readings over 5 seeds of 2 s runs:
    # the program reads 0 for both, the int8 control at least 1.5e-3
    # (widest) and 2.3e-5 (mean)
    "limits": {"widest_logit_gap": 5e-5, "mean_logit_gap": 5e-7},
}
TINY_CHAT = {"name": "tiny-chat", "kind": "closed_loop", "clients": 2,
             "requests_per_client": 8, "strata": 2,
             "prompt_tokens": {"min": 8, "max": 16},
             "output_tokens": {"min": 4, "max": 12}}
TINY_SPMV = {
    "name": "tiny-spmv", "kind": "library", "source": "test",
    "matrix": {"name": "PFlow_742", "dim": 742793, "nnz": 37138461,
               "structure": "banded", "bandwidth_frac": 0.02,
               "scale": 0.002},
    "plan": {"l": 16, "backend": "jnp"},
    "control": {"plan": {"value_dtype": "bfloat16"}},
    "limits": {"max_rel_error": 1e-4},
}
TINY_POWER = {"name": "tiny-power", "kind": "power_iteration", "batch": 1,
              "samples": 4}


def scratch_root(tmp_path, configs=None, mixes=None, cells=None):
    """A checkout in ``tmp_path``: the benchmark's code and the program
    linked, and tiny configurations, mixes and cells written out."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    for name in ("drivers", "metrics", "peaks.json", "run.py"):
        os.symlink(os.path.join(BENCH, name), bench / name)
    # the files of lib/ linked one by one, so a test can add a traffic
    # kind or a matrix structure to this checkout alone
    for sub in ("", "kinds", "structures"):
        src = os.path.join(BENCH, "lib", sub)
        (bench / "lib" / sub).mkdir(parents=True, exist_ok=True)
        for f in os.listdir(src):
            if f.endswith(".py"):
                os.symlink(os.path.join(src, f), bench / "lib" / sub / f)
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    configs = configs or {"tiny-lm": TINY_LM, "tiny-spmv": TINY_SPMV}
    mixes = mixes or {"tiny-chat": TINY_CHAT, "tiny-power": TINY_POWER}
    for name, c in configs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(c))
    for name, m in mixes.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(m))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec = copy.deepcopy(spec)
    spec["workloads"] = cells or [
        {"name": "tiny.chat", "config": "tiny-lm", "traffic": "tiny-chat",
         "chips": 1, "why": "test"},
        {"name": "tiny.power", "config": "tiny-spmv", "traffic": "tiny-power",
         "chips": 1, "why": "test"},
    ]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = [
                    {"yi6b-gust.chat-decode": "tiny.chat",
                     "mousegene.spmv-power": "tiny.power"}.get(w, w)
                    for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def bench_module(rel: str, name: str):
    """A module of the benchmark loaded by its path under its own name
    (``run`` and ``drivers`` are names other test files also use)."""
    from lib import harness

    return harness.load_module(os.path.join(BENCH, rel), name)


def run_cell(root, workload, seed=3, seconds=1.0, trace=0, control=0):
    """One run without the look for a chip (CPU, interpreted kernels)."""
    bench_run = bench_module("run.py", "bench_run")

    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, list=False, control=control)
    return bench_run.run(args, require_chip=False, root=str(root))
