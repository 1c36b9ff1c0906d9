"""The served mixture-of-experts cell on the CPU at a tiny size (the
program's reduced Mellum2: 4 layers, sliding window 16 under sequences of
up to 40 positions, 2 of 4 experts held, top 2): a sound run is correct,
its counters feed the cell's per-layer metrics, and the int8 control and
two planted faults (one held expert left out of the decode, the sliding
layers' ring cache not wrapped) each read ``correct: false``;
``bench/run.py --list`` names every file of the new cell."""

import json
import os
import subprocess
import sys

import pytest

import benchtools

CELL = "mellum2-gust.chat-decode-64"

TINY_MOE = {
    "name": "tiny-moe", "kind": "serving_moe", "source": "test",
    "hidden_size": 64, "moe_intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 4, "vocab_size": 256, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4, "sliding_window": 16,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16, "original_max_position_embeddings":
                           8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "num_experts": 2, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "expert_share": {"router_experts": 4, "first": 0, "ways": 2},
    "program": {"arch": "mellum2_12b_a2_5b", "reduced": True},
    "gust": {"density": 0.5, "gust_length": 16, "use_kernel": False},
    "serve": {"batch": 4, "seq_len": 64, "dtype": "float32"},
    "control": {"reference": "int8"},
    # tiny-size limit, set from CPU readings of 1 s runs over 4 seeds:
    # the program reads 0 (float32 on both sides), the int8 control
    # 3.95e-4 to 9.17e-4
    "limits": {"mean_logit_gap": 2e-5},
}
TINY_CHAT_MOE = {"name": "tiny-chat-moe", "kind": "closed_loop",
                 "clients": 4, "requests_per_client": 4, "strata": 2,
                 "prompt_tokens": {"min": 8, "max": 16},
                 "output_tokens": {"min": 8, "max": 24}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = benchtools.scratch_root(
        tmp_path_factory.mktemp("bench_moe"), configs={"tiny-moe": TINY_MOE},
        mixes={"tiny-chat-moe": TINY_CHAT_MOE},
        cells=[{"name": "tiny.moe", "config": "tiny-moe",
                "traffic": "tiny-chat-moe", "chips": 1, "why": "test"}])
    spec = json.loads((r / "BENCHMARK.json").read_text())
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = ["tiny.moe" if w == CELL else w
                                  for w in m["workloads"]]
    (r / "BENCHMARK.json").write_text(json.dumps(spec))
    return r


def _run(root, seed, control=0, seconds=1.0):
    bench_run = benchtools.bench_module("run.py", "bench_run_moe")
    import argparse

    args = argparse.Namespace(workload="tiny.moe", seed=seed,
                              seconds=seconds, trace=0, list=False,
                              control=control)
    return bench_run.run(args, require_chip=False, root=str(root),
                         with_record=True)


def test_sound_moe_run_is_correct(root):
    line, rec = _run(root, seed=2**33 + 7)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"decode_tok_s", "itl_p95_ms", "setup_s"} <= set(line["metrics"])
    # the window saw routing: per-layer readers find their counters
    st = rec["stats"]
    assert 0 < st["moe.routed_pairs"] <= st["moe.computed_cols"]
    assert len(st["moe.expert_tokens"]) == 4  # expert layers
    assert sum(map(sum, st["moe.expert_tokens"])) == st["moe.routed_pairs"]
    assert all(s <= st["decode_steps"] for row in st["moe.expert_steps"]
               for s in row)
    from lib import harness

    read = lambda name: harness.load_module(  # noqa: E731
        os.path.join(benchtools.BENCH, "metrics", name + ".py"),
        "metric_" + name.replace(".", "_")).read
    share = read("moe_dispatch_share.mellum")(rec)
    assert 0 < share <= 1
    # op names as the device trace gives them: the instruction's text
    op = {"count": 1, "seconds": 1.0, "stats": {}, "parent": False}
    call = ' = f32[8] custom-call(), custom_call_target="tpu_custom_call"'
    trace = {"window_s": 1.0, "busy_s": 1.0, "ops": {
        "%gust_spmv_padded_resident_db.1" + call: op,
        "%ragged-dot-none.1" + call: op}}
    roof = read("gust_roofline.mellum")(dict(rec, trace=trace))
    assert roof is not None and 0 < roof < 100
    # the prefill's grouped matmuls are custom calls, not GUST kernels
    del trace["ops"]["%gust_spmv_padded_resident_db.1" + call]
    assert read("gust_roofline.mellum")(dict(rec, trace=trace)) is None
    assert read("gust_roofline.mellum")(rec) is None  # untraced


def test_moe_control_is_incorrect(root):
    line, _ = _run(root, seed=2**36 + 11, control=1)
    assert not line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0


def _held_expert_left_out(monkeypatch):
    import repro.serving.gust_serve as gs

    orig = gs._gust_experts

    def without_first(g, metas, xt, w, pc):
        return orig(g, metas, xt, w.at[0].set(0.0), pc)

    monkeypatch.setattr(gs, "_gust_experts", without_first)


def _ring_not_wrapped(monkeypatch):
    import jax
    import jax.numpy as jnp

    import repro.models.attention as A

    orig = A.decode_step

    def no_wrap(p, x, spec, cache, pos):
        y, new = orig(p, x, spec, cache, pos)
        if spec.mode != "local":
            return y, new
        # past the last slot a write is dropped instead of wrapping
        ok = jnp.broadcast_to(jnp.asarray(pos) < cache["k"].shape[1],
                              (x.shape[0],))
        return y, jax.tree.map(
            lambda n, o: jnp.where(ok.reshape((-1,) + (1,) * (n.ndim - 1)),
                                   n, o), new, cache)

    monkeypatch.setattr(A, "decode_step", no_wrap)


@pytest.mark.parametrize("fault", [_held_expert_left_out, _ring_not_wrapped],
                         ids=["held-expert-left-out", "ring-not-wrapped"])
def test_moe_fault_makes_the_run_incorrect(root, monkeypatch, fault):
    fault(monkeypatch)
    line, _ = _run(root, seed=2**35 + 3)
    assert not line["correct"], line["compared"]


def test_listing_names_the_moe_cells_files():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--list"],
                       cwd=benchtools.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    cell = next(json.loads(x) for x in p.stdout.splitlines()
                if json.loads(x)["workload"] == CELL)
    assert cell["config"] == "bench/configs/mellum2-gust-4L-e8.json"
    assert cell["traffic"] == "bench/traffic/chat-decode-64.json"
    assert cell["driver"] == "bench/drivers/serving_moe.py"
    assert set(cell["metrics"]["per_layer"]) == {
        "bench/metrics/moe_dispatch_share.mellum.py",
        "bench/metrics/gust_roofline.mellum.py"}
    assert set(cell["metrics"]["end_to_end"]) == {
        "bench/metrics/decode_tok_s.py", "bench/metrics/itl_p95_ms.py",
        "bench/metrics/setup_s.py"}
    for path in [cell["config"], cell["traffic"], cell["kind"],
                 cell["driver"]] + [m for ms in cell["metrics"].values()
                                    for m in ms]:
        assert os.path.isfile(os.path.join(benchtools.ROOT, path)), path
