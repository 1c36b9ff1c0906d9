"""Launch-layer tests: mesh construction, a miniature dry-run cell
(subprocess, 16 placeholder devices on a 4x4 mesh), the train driver
end-to-end with resume, and the serve driver."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from conftest import SRC, run_spmd_subprocess


def test_make_production_mesh_requires_devices():
    code = """
from repro.launch.mesh import make_production_mesh
try:
    make_production_mesh()
    raise SystemExit("should have raised")
except RuntimeError as e:
    assert "XLA_FLAGS" in str(e)
print("ok")
"""
    run_spmd_subprocess(code, devices=8)


def test_mesh_shapes():
    code = """
from repro.launch.mesh import mesh_shape
assert mesh_shape(False) == ((16, 16), ("data", "model"))
assert mesh_shape(True) == ((2, 16, 16), ("pod", "data", "model"))
print("ok")
"""
    run_spmd_subprocess(code, devices=8)


def test_miniature_dryrun_cell():
    """The dry-run machinery (param specs, cache shardings, lower+compile,
    hlo analysis) on a reduced arch over a 2x4 mesh."""
    run_spmd_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs.base import get_arch, register
from repro.models.model_zoo import build_model
from repro.training import TrainConfig, make_train_step, init_train_state
from repro.distributed.sharding import param_specs, activation_ctx, cache_spec_overrides
from repro.launch.hlo_analysis import analyze_hlo, roofline_terms
import dataclasses

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
cfg = get_arch("gemma3_4b").reduced()  # heterogeneous pattern + tail
lm = build_model(cfg)
tc = TrainConfig(dtype="bfloat16", microbatches=2, remat=True)
state_specs = jax.eval_shape(lambda: init_train_state(lm, jax.random.PRNGKey(0), tc))
pspecs = param_specs(state_specs["params"], mesh, mode="train")
state_sh = {"params": pspecs, "opt": {"m": pspecs, "v": pspecs,
                                      "step": NamedSharding(mesh, P())}}
batch = lm.input_specs(64, 8, "train")
bsh = {k: NamedSharding(mesh, P(("data",), *([None] * (len(v.shape) - 1))))
       for k, v in batch.items()}
with activation_ctx(mesh):
    compiled = jax.jit(make_train_step(lm, tc), in_shardings=(state_sh, bsh)
                       ).lower(state_specs, batch).compile()
st = analyze_hlo(compiled.as_text())
rt = roofline_terms(st)
assert st.dot_flops > 0 and rt["dominant"] in ("compute", "memory", "collective")
# decode cell too
params_b = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
    x.shape, jnp.bfloat16 if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype),
    state_specs["params"])
caches = jax.eval_shape(lambda: lm.init_caches(8, 64, jnp.bfloat16))
csh = jax.tree_util.tree_map_with_path(cache_spec_overrides(mesh, 8), caches)
tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
with activation_ctx(mesh):
    dec = jax.jit(lambda p, c, t, pos: lm.decode_step(p, c, t, pos, dtype=jnp.bfloat16),
                  in_shardings=(param_specs(params_b, mesh, mode="serve"), csh,
                                NamedSharding(mesh, P(("data",), None)),
                                NamedSharding(mesh, P())),
                  donate_argnums=(1,)).lower(params_b, caches, tok,
                                             jax.ShapeDtypeStruct((), jnp.int32)
                                             ).compile()
assert dec.memory_analysis().temp_size_in_bytes > 0
print("ok")
""", devices=8, timeout=600)


def test_train_driver_with_resume(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    args = [sys.executable, "-m", "repro.launch.train", "--arch", "yi_6b",
            "--steps", "8", "--seq-len", "16", "--batch", "4",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "4"]
    p1 = subprocess.run(args, env=env, capture_output=True, text=True, timeout=600)
    assert p1.returncode == 0, p1.stderr
    out = json.loads(p1.stdout.strip().splitlines()[-1])
    assert out["last_loss"] < out["first_loss"]
    # resume from the step-8 checkpoint and continue
    p2 = subprocess.run(args + ["--resume", "--steps", "10"], env=env,
                        capture_output=True, text=True, timeout=600)
    assert p2.returncode == 0, p2.stderr
    assert "resumed from step 8" in p2.stdout


def test_serve_driver_gust(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "yi_6b",
         "--requests", "2", "--max-new", "3", "--gust", "--density", "0.5",
         "--gust-length", "16"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr
    stats = json.loads(p.stdout.strip().splitlines()[-1])
    assert stats["requests"] == 2 and stats["gust"]
    assert all(0 < u <= 1 for u in stats["gust_stream_utilization"].values())
    # the serve loop's spans per decode step and gustify's build phases
    per_step = stats["serve_ms_per_step"]
    assert {"step", "admit", "prefill", "decode", "wait", "retire"} <= set(per_step)
    assert per_step["step"] >= per_step["wait"] + per_step["admit"] - 1e-2
    assert set(stats["gust_build_s"]) == {"prune", "colour", "pack", "stack",
                                          "upload"}
    assert sum(stats["gust_build_s"].values()) <= stats["gustify_s"] + 1e-2


def test_serve_published_widths_and_depth_cut(monkeypatch, tmp_path):
    """``--no-reduced`` reaches the published widths and ``--layers`` cuts
    only depth; the default stays the reduced smoke model."""
    from repro.launch import serve

    full = serve._arch("yi_6b", reduced=False, layers=4)
    assert (full.d_model, full.d_ff, full.n_heads, full.n_kv, full.head_dim,
            full.vocab, full.n_layers) == (4096, 11008, 32, 4, 128, 64000, 4)
    assert serve._arch("yi_6b", reduced=True).d_model == 64
    with pytest.raises(ValueError):
        serve._arch("yi_6b", reduced=False, layers=33)

    seen = []

    def fake_run(arch, **kw):
        seen.append(kw)
        return {}, {"resilience": {"failed": 0}}

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(serve, "run_serving", fake_run)
    assert serve.main(["--arch", "yi_6b", "--no-reduced", "--layers", "4"]) == 0
    assert serve.main(["--arch", "yi_6b"]) == 0
    assert (seen[0]["reduced"], seen[0]["layers"]) == (False, 4)
    assert (seen[1]["reduced"], seen[1]["layers"]) == (True, None)


def test_serve_exits_nonzero_on_failed_request(monkeypatch, tmp_path, capsys):
    """A FAILED request is an error unless a fault plan injected it."""
    from repro.launch import serve
    from repro.resilience.faults import FaultPlan, FaultSpec, injected
    from repro.serving.serve_loop import ServeLoop

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    argv = ["--arch", "yi_6b", "--requests", "2", "--max-new", "2"]
    assert serve.main(argv) == 0

    with injected(FaultPlan([FaultSpec("serve.admit", times=-1)], seed=0)):
        assert serve.main(argv) == 0  # failures were injected on purpose
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert stats["resilience"]["failed"] == 2

    def broken_admit(self, *a, **kw):
        raise RuntimeError("kernel refused to lower")

    monkeypatch.setattr(ServeLoop, "_admit", broken_admit)
    assert serve.main(argv) == 1
    assert "FAILED" in capsys.readouterr().err


def test_serve_driver_moe_gust(tmp_path):
    """The CLI serves the reduced Mellum2 (expert layers, sliding and
    full attention) through GUST and prints the routing counters."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "mellum2_12b_a2_5b", "--requests", "3", "--max-new", "20",
         "--prompt-len", "8", "--gust", "--density", "0.5",
         "--gust-length", "16"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr
    stats = json.loads(p.stdout.strip().splitlines()[-1])
    assert stats["requests"] == 3 and stats["gust"]
    moe = stats["moe"]
    # 5 expert layers of the reduced stack (a period and a tail), 2 held
    assert len(moe["expert_tokens"]) == 5
    assert all(len(row) == 2 for row in moe["expert_tokens"])
    assert 0 < moe["routed_pairs"] <= moe["computed_cols"]
    assert moe["routed_pairs"] == sum(map(sum, moe["expert_tokens"]))

