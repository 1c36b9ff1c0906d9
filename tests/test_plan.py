"""GustPlan lifecycle — the one plan/execute API (ISSUE 3).

Locks the acceptance criteria:
  * every legacy entry point (``spmv``, ``spmm_scheduled``, ``spmm_ragged``,
    ``distributed_spmv``, ``gust_spmm``, ``gust_spmm_auto``, ``GustLinear``,
    serving decode) routes through ``GustPlan.spmv``/``.spmm`` internally;
  * ``to_spec``/``from_spec`` round-trips both layouts bit-identically and
    preserves compact bf16/int16 leaf dtypes;
  * two plans over the same matrix schedule exactly once (content-keyed
    cache);
  * the batch-major ``transpose_io`` fast path is bit-identical to the
    legacy double-transpose round-trip;
  * the deprecated kwarg spellings warn with the new spelling.
"""

import warnings

import numpy as np
import pytest
from conftest import run_spmd_subprocess

import jax
import jax.numpy as jnp

from repro.core.formats import coo_from_dense
from repro.core.gust_linear import GustLinear, SparsityConfig
from repro.core.packing import (
    PackedSchedule,
    RaggedSchedule,
    ScheduleCache,
    pack_ragged,
    pack_schedule,
)
from repro.core.plan import GustPlan, PlanConfig, plan
from repro.core.scheduler import schedule

# repro.core re-exports the spmv *function*, shadowing the submodule
import importlib

spmv_mod = importlib.import_module("repro.core.spmv")
from repro.kernels.ops import execute_spmm, gust_spmm, gust_spmm_auto


def random_dense(rng, m, n, density):
    return ((rng.random((m, n)) < density) * rng.standard_normal((m, n))).astype(
        np.float32
    )


def power_law_dense(rng, m, n):
    d = ((rng.random((m, n)) < 0.03) * rng.standard_normal((m, n))).astype(
        np.float32
    )
    rows = rng.choice(m, max(m // 16, 1), replace=False)
    d[rows] = (rng.random((len(rows), n)) < 0.6) * rng.standard_normal(
        (len(rows), n)
    )
    return d


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


def test_plan_config_normalizes_and_validates():
    cfg = PlanConfig(value_dtype=jnp.bfloat16, index_dtype="int16")
    assert cfg.value_dtype == "bfloat16" and cfg.index_dtype == "int16"
    assert cfg.value_jnp == jnp.bfloat16
    assert PlanConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError):
        PlanConfig(layout="csr")
    with pytest.raises(ValueError):
        PlanConfig(backend="cuda")
    with pytest.raises(ValueError):
        PlanConfig(colorer="greedy")


# ---------------------------------------------------------------------------
# execution correctness through the plan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["padded", "ragged"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_plan_matches_dense(layout, backend):
    rng = np.random.default_rng(1)
    dense = random_dense(rng, 48, 64, 0.2)
    x = rng.standard_normal((64, 3)).astype(np.float32)
    p = plan(dense, PlanConfig(l=8, layout=layout, backend=backend), cache=None)
    y = np.asarray(p.spmm(jnp.asarray(x)))
    np.testing.assert_allclose(y, dense @ x, rtol=2e-4, atol=2e-4)
    yv = np.asarray(p.spmv(jnp.asarray(x[:, 0])))
    np.testing.assert_allclose(yv, dense @ x[:, 0], rtol=2e-4, atol=2e-4)


def test_plan_auto_layout_by_measured_waste():
    rng = np.random.default_rng(2)
    p_skew = plan(power_law_dense(rng, 128, 128), PlanConfig(l=8), cache=None)
    assert p_skew.layout == "ragged"
    assert isinstance(p_skew.artifact, RaggedSchedule)
    p_uni = plan(random_dense(rng, 64, 64, 0.3), PlanConfig(l=8), cache=None)
    assert p_uni.layout == "padded"
    assert isinstance(p_uni.artifact, PackedSchedule)
    # threshold is respected
    p_thr = plan(
        power_law_dense(rng, 128, 128),
        PlanConfig(l=8, waste_threshold=1e9),
        cache=None,
    )
    assert p_thr.layout == "padded"


def test_plan_accepts_schedule_and_adopts_its_l():
    rng = np.random.default_rng(3)
    sched = schedule(coo_from_dense(random_dense(rng, 32, 32, 0.3)), 8)
    p = plan(sched, PlanConfig(l=256, backend="jnp"))
    assert p.l == 8 and p.sched is sched


# ---------------------------------------------------------------------------
# schedule-once (content-keyed cache)
# ---------------------------------------------------------------------------


def test_two_plans_over_same_matrix_schedule_once(monkeypatch):
    import repro.core.scheduler as sched_mod

    calls = []
    real = sched_mod.schedule

    def counting(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(sched_mod, "schedule", counting)
    rng = np.random.default_rng(4)
    dense = random_dense(rng, 48, 48, 0.2)
    v = jnp.asarray(rng.standard_normal(48).astype(np.float32))
    cache = ScheduleCache()
    cfg = PlanConfig(l=8, backend="jnp")
    p1 = plan(coo_from_dense(dense), cfg, cache=cache)
    y1 = np.asarray(p1.spmv(v))
    p2 = plan(coo_from_dense(dense), cfg, cache=cache)
    y2 = np.asarray(p2.spmv(v))
    assert len(calls) == 1, "second plan over identical content re-scheduled"
    assert p2.artifact is p1.artifact, "pack not shared through the cache"
    assert np.array_equal(y1, y2)


def test_plan_packs_lazily():
    rng = np.random.default_rng(5)
    p = plan(random_dense(rng, 32, 32, 0.3), PlanConfig(l=8), cache=None)
    assert p._artifact is None, "plan() must not pack before execution"
    p.cost()  # cost reads the artifact
    assert p._artifact is not None


# ---------------------------------------------------------------------------
# to_spec / from_spec round-trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["padded", "ragged"])
@pytest.mark.parametrize("compact", [False, True])
def test_to_spec_round_trip(layout, compact):
    rng = np.random.default_rng(6)
    dense = random_dense(rng, 48, 64, 0.2)
    x = jnp.asarray(rng.standard_normal((64, 2)).astype(np.float32))
    vd, idd = ("bfloat16", "int16") if compact else ("float32", "int32")
    p = plan(
        dense,
        PlanConfig(l=8, layout=layout, backend="jnp", value_dtype=vd,
                   index_dtype=idd),
        cache=None,
    )
    spec = p.to_spec()
    p2 = GustPlan.from_spec(spec)
    # dtype preservation through the codec
    assert p2.artifact.m_blk.dtype == jnp.dtype(vd)
    assert p2.artifact.col_blk.dtype == jnp.dtype(idd)
    assert p2.config.value_dtype == vd and p2.config.index_dtype == idd
    assert p2.layout == layout and p2.shape == p.shape
    # bit-identical execution from the deserialized plan
    assert np.array_equal(np.asarray(p.spmm(x)), np.asarray(p2.spmm(x)))
    # deserialized plans carry no schedule: cost()/shard() refuse cleanly
    with pytest.raises(ValueError):
        p2.cost()


def test_stack_equalizes_and_stacks_leaves():
    rng = np.random.default_rng(7)
    plans = [
        plan(random_dense(rng, 32, 32, d), PlanConfig(l=8, layout="padded"),
             cache=None)
        for d in (0.1, 0.4)
    ]
    stacked = GustPlan.stack(plans)
    c_pad = max(p.artifact.c_pad for p in plans)
    assert stacked["leaves"]["m_blk"].shape[0] == 2
    assert stacked["meta"][2] == c_pad
    # one layer's slice rebuilds through from_spec
    sl = {k: v[0] for k, v in stacked["leaves"].items()}
    p0 = GustPlan.from_spec({"leaves": sl, "meta": stacked["meta"]})
    x = jnp.asarray(rng.standard_normal((32, 2)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(p0.spmm(x)), np.asarray(plans[0].spmm(x)),
        rtol=1e-5, atol=1e-5,
    )
    with pytest.raises(ValueError):
        GustPlan.stack(
            [plans[0],
             plan(random_dense(rng, 32, 32, 0.2),
                  PlanConfig(l=8, layout="ragged"), cache=None)]
        )


def test_spec_for_shapes():
    cfg = PlanConfig(l=16, layout="ragged", c_blk=8)
    p = GustPlan.spec_for(64, 128, cfg, colors=20.0)
    a = p.artifact
    assert isinstance(a, RaggedSchedule)
    assert a.num_blocks == (64 // 16) * 3  # ceil(20/8) = 3 blocks/window
    assert a.m_blk.shape == (a.num_blocks * 8, 16)
    pp = GustPlan.spec_for(64, 128, PlanConfig(l=16, layout="padded"), colors=20.0)
    assert pp.artifact.c_pad == 24


# ---------------------------------------------------------------------------
# transpose_io fast path (GustLinear's double-transpose removal)
# ---------------------------------------------------------------------------


def test_transpose_io_bit_identity():
    rng = np.random.default_rng(8)
    dense = random_dense(rng, 48, 64, 0.2)
    xb = jnp.asarray(rng.standard_normal((5, 64)).astype(np.float32))  # (B, n)
    for layout in ("padded", "ragged"):
        p = plan(dense, PlanConfig(l=8, layout=layout, backend="jnp"),
                 cache=None)
        legacy = np.asarray(
            execute_spmm(p.artifact, xb.T, use_kernel=False).T
        )
        fast = np.asarray(p.spmm(xb, transpose_io=True))
        assert np.array_equal(legacy, fast), layout


def test_gust_linear_uses_transpose_io_bit_identically():
    rng = np.random.default_rng(9)
    w = rng.standard_normal((48, 64)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((5, 64)).astype(np.float32))
    gl = GustLinear(w, config=PlanConfig(l=8, backend="jnp"), density=0.25)
    legacy = np.asarray(
        execute_spmm(gl.packed, x.T, use_kernel=False).T
    )
    assert np.array_equal(np.asarray(gl(x)), legacy)


# ---------------------------------------------------------------------------
# every legacy entry point routes through GustPlan (acceptance criterion)
# ---------------------------------------------------------------------------


def test_every_entry_point_routes_through_gust_plan(monkeypatch):
    calls = []
    orig_spmm, orig_spmv = GustPlan.spmm, GustPlan.spmv

    def counting_spmm(self, x, **kw):
        calls.append("spmm")
        return orig_spmm(self, x, **kw)

    def counting_spmv(self, v):
        calls.append("spmv")
        return orig_spmv(self, v)

    monkeypatch.setattr(GustPlan, "spmm", counting_spmm)
    monkeypatch.setattr(GustPlan, "spmv", counting_spmv)

    def hits(fn):
        calls.clear()
        fn()
        return set(calls)

    rng = np.random.default_rng(10)
    dense = random_dense(rng, 32, 32, 0.3)
    coo = coo_from_dense(dense)
    sched = schedule(coo, 8)
    v = jnp.asarray(rng.standard_normal(32).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((32, 2)).astype(np.float32))

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        assert "spmv" in hits(lambda: spmv_mod.spmv(coo, v, l=8))
        assert "spmm" in hits(
            lambda: gust_spmm_auto(sched, x, use_kernel=False)
        )
    assert "spmm" in hits(lambda: spmv_mod.spmm_scheduled(sched, x))
    assert "spmm" in hits(lambda: spmv_mod.spmm_ragged(pack_ragged(sched), x))
    assert "spmm" in hits(
        lambda: gust_spmm(pack_schedule(sched), x, use_kernel=False)
    )

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    assert "spmv" in hits(
        lambda: spmv_mod.distributed_spmv(sched, v, mesh, axis="data")
    )

    w = rng.standard_normal((16, 32)).astype(np.float32)
    gl = GustLinear(w, config=PlanConfig(l=8, backend="jnp"), density=0.5)
    assert "spmm" in hits(lambda: gl(x.T))


def test_serving_decode_routes_through_gust_plan(monkeypatch):
    from repro.configs.base import get_arch
    from repro.models.model_zoo import build_model
    from repro.serving.gust_serve import (
        GustServeConfig,
        decode_step_gust,
        gustify,
    )

    calls = []
    orig_spmm = GustPlan.spmm

    def counting_spmm(self, x, **kw):
        calls.append("spmm")
        return orig_spmm(self, x, **kw)

    monkeypatch.setattr(GustPlan, "spmm", counting_spmm)

    cfg = get_arch("yi_6b").reduced()
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    gcfg = GustServeConfig(density=0.5, gust_length=16)
    gust = gustify(lm, params, gcfg)
    caches = lm.init_caches(1, 8, jnp.float32)
    tok = jnp.zeros((1, 1), jnp.int32)
    calls.clear()
    logits, _ = decode_step_gust(
        lm, params, gust, caches, tok, jnp.int32(0), cfg=gcfg,
        dtype=jnp.float32,
    )
    assert "spmm" in calls, "serving decode bypassed GustPlan"
    assert np.all(np.isfinite(np.asarray(logits)))


# ---------------------------------------------------------------------------
# sharded execution through the plan
# ---------------------------------------------------------------------------


def test_plan_shard_single_device_matches_dense():
    rng = np.random.default_rng(11)
    dense = random_dense(rng, 64, 32, 0.2)
    v = jnp.asarray(rng.standard_normal(32).astype(np.float32))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    p = plan(dense, PlanConfig(l=8, backend="jnp"), cache=ScheduleCache())
    y = np.asarray(p.shard(mesh, "data").spmv(v))
    np.testing.assert_allclose(y, dense @ v, rtol=1e-4, atol=1e-4)
    with pytest.raises(NotImplementedError):
        p.shard(mesh, "data").spmm(jnp.zeros((32, 2), jnp.float32))


def test_plan_shard_four_devices_explicit_mesh_assembles_on_one_device():
    # jax.make_mesh gives explicit axis types; the sharded SpMV must equal
    # the one-device SpMV bitwise and leave y on one device, not replicated
    out = run_spmd_subprocess(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.core.plan import PlanConfig, plan
rng = np.random.default_rng(3)
dense = (rng.random((200, 120)) < 0.1) * rng.standard_normal((200, 120))
v = jnp.asarray(rng.standard_normal(120).astype(np.float32))
p = plan(dense.astype(np.float32), PlanConfig(l=8, layout="ragged", backend="jnp"))
mesh = jax.make_mesh((4,), ("x",))
y = p.shard(mesh, "x").spmv(v)
assert len(y.sharding.device_set) == 1, y.sharding
assert np.array_equal(np.asarray(y), np.asarray(p.spmv(v)))
print("OK")
""",
        devices=4,
    )
    assert out.strip().endswith("OK")


# ---------------------------------------------------------------------------
# cost
# ---------------------------------------------------------------------------


def test_plan_cost_fields():
    rng = np.random.default_rng(12)
    dense = random_dense(rng, 64, 64, 0.2)
    p = plan(dense, PlanConfig(l=8), cache=None)
    c = p.cost()
    assert c.cycles == p.sched.cycles
    assert 0 < c.utilization <= 1
    assert c.waste_ratio >= 1.0
    assert c.layout in ("padded", "ragged")
    assert c.streamed_slots > 0 and c.stream_bytes > 0
    assert c.expected_cycles > 0 and 0 < c.expected_utilization <= 1
    assert c.to_dict()["density"] == pytest.approx(
        p.sched.nnz / dense.size
    )


@pytest.mark.parametrize("l,gathers", [(8, 8), (128, 8), (256, 32),
                                       (512, 128)])
def test_plan_cost_route_lane_gathers(l, gathers):
    """The crossbar takes one lane gather per cycle row, 128-lane output
    chunk and source chunk: c_blk below 128 lanes, c_blk * (l/128)**2
    from there (32 at the benchmark's l = 256)."""
    rng = np.random.default_rng(l)
    p = plan(random_dense(rng, l, 16, 0.05), PlanConfig(l=l), cache=None)
    assert p.config.c_blk == 8
    assert p.cost().route_lane_gathers == gathers
    assert p.cost().to_dict()["route_lane_gathers"] == gathers


# ---------------------------------------------------------------------------
# deprecated spellings warn with the new one
# ---------------------------------------------------------------------------


def test_legacy_kwarg_shims_warn():
    rng = np.random.default_rng(13)
    dense = random_dense(rng, 16, 16, 0.3)
    coo = coo_from_dense(dense)
    sched = schedule(coo, 8)
    v = jnp.asarray(rng.standard_normal(16).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((16, 2)).astype(np.float32))
    with pytest.warns(DeprecationWarning, match="colorer"):
        spmv_mod.spmv(coo, v, l=8, method="fast")
    with pytest.warns(DeprecationWarning, match="layout='auto'"):
        gust_spmm_auto(sched, x, use_kernel=False)
    with pytest.warns(DeprecationWarning, match="gust_length"):
        SparsityConfig(enable=True, gust_length=8)
