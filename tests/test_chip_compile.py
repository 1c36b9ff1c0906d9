"""The main-path Pallas kernels compile for a TPU v5e at yi_6b widths.

Each test compiles one kernel builder for a v5e chip that is described,
not attached (the TPU compiler is installed with jax), and asserts that
the compiled program holds the Mosaic kernel (``tpu_custom_call``) under
the kernel's family name, the name a device trace shows it by.  This
catches what interpret mode cannot: unlowerable primitives, block shapes
off the (8, 128) tiling, and VMEM overruns.

Shapes are those of yi_6b's MLP matrices pruned to density 0.1 and
planned with l=256 (w_up/w_gate: 11008 x 4096, w_down: 4096 x 11008),
decoded at batch 4; stream lengths are the planner's at that density.
The padded resident kernels also compile at the Table-3 ``mouse_gene``
plan (45,101 square, l=256) at batch 1, the widest resident walk the
benchmark runs, at yi_6b's w_down at batch 8, and at Mellum2's expert
matrices at batch 64.
"""

import os
import re

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

#: (num_windows, seg_count, c_pad, s_blk, ragged num_blocks) per matrix.
SHAPES = {
    "w_up": (43, 16, 552, 16, 2685),
    "w_down": (16, 43, 1408, 43, 2640),
}
DTYPES = {
    "f32": ("float32", "int32"),
    "compact": ("bfloat16", "int16"),
    "int8": ("int8", "int16"),
}
KERNELS = [
    (layout, gather, pipeline)
    for layout in ("padded", "ragged")
    for gather in ("resident", "local")
    for pipeline in ("single", "double")
]
L, B, C_BLK = 256, 4, 8
#: mouse_gene at l=256: (num_windows, seg_count, c_pad), batch 1.
MOUSEGENE = (177, 177, 888)
#: Mellum2's expert matrices at l=256: (num_windows, seg_count, c_pad).
MELLUM_EXPERTS = {"w_up": (4, 9, 296), "w_down": (9, 4, 152)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text, family):
    """The compiled text holds a Mosaic custom call named ``family``."""
    calls = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls, "no tpu_custom_call in the compiled program"
    names = [c.split(" = ", 1)[0] for c in calls]
    assert any(re.fullmatch(rf"%{family}(\.\d+)?", n) for n in names), names


@pytest.mark.parametrize("dtypes", sorted(DTYPES))
@pytest.mark.parametrize("mat", sorted(SHAPES))
@pytest.mark.parametrize("layout,gather,pipeline", KERNELS)
def test_spmv_kernel_compiles_for_v5e(one_chip, layout, gather, pipeline,
                                      mat, dtypes):
    from repro.kernels import gust_spmv as K
    from repro.kernels import gust_spmv_ragged as R

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    w, seg, c_pad, s_blk, t_ragged = SHAPES[mat]
    vdt, idt = DTYPES[dtypes]
    quant = vdt == "int8"
    t_blk = t_ragged if layout == "ragged" else w * c_pad // C_BLK
    x_shape = ((seg, 8, L) if gather == "local"
               else (B, K._resident_x_rows(seg), L))
    stream = (spec((t_blk * C_BLK, L), vdt), spec((t_blk * C_BLK, L), idt),
              spec((t_blk * C_BLK, L), idt), spec(x_shape, "float32"))
    scale = (spec((t_blk,), "float32"),) if quant else ()
    seg_flat = (spec((t_blk * s_blk,), "int32"),)
    kw = dict(c_blk=C_BLK, interpret=False, quantized=quant)
    dkw = dict(value_dtype=vdt, index_dtype=idt)
    double = pipeline == "double"
    family = f"gust_spmv_{layout}_{gather}" + ("_db" if double else "")
    if layout == "padded":
        if gather == "local":
            build = K.make_gust_spmv_local_db if double else K.make_gust_spmv_local
            fn, pre = build(w, c_pad, L, s_blk, B, **kw), seg_flat
        elif double:
            fn, pre = K.make_gust_spmv_db(w, c_pad, L, seg, B, **kw, **dkw), ()
        else:
            fn, pre = K.make_gust_spmv(w, c_pad, L, seg, B, **kw), ()
    else:
        steer = (spec((t_blk,), "int32"), spec((w + 1,), "int32"))
        if gather == "local":
            build = (R.make_gust_spmv_ragged_local_db if double
                     else R.make_gust_spmv_ragged_local)
            fn = build(t_blk, w, L, s_blk, B, **kw)
            pre = steer + seg_flat
        elif double:
            fn = R.make_gust_spmv_ragged_db(t_blk, w, L, seg, B, **kw, **dkw)
            pre = steer[1:]
        else:
            fn, pre = R.make_gust_spmv_ragged(t_blk, w, L, seg, B, **kw), steer
    _assert_kernel(_compile_text(fn, pre + scale + stream), family)


@pytest.mark.parametrize("pipeline", ["single", "double"])
def test_resident_spmv_compiles_at_mousegene(one_chip, pipeline):
    """The widest resident walk the benchmark runs: 177 segments, 23
    groups of eight, batch 1, f32 stream."""
    from repro.kernels import gust_spmv as K

    w, seg, c_pad = MOUSEGENE
    rows = w * c_pad

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    args = (spec((rows, L), "float32"), spec((rows, L), "int32"),
            spec((rows, L), "int32"),
            spec((1, K._resident_x_rows(seg), L), "float32"))
    kw = dict(c_blk=C_BLK, interpret=False)
    if pipeline == "double":
        fn, family = K.make_gust_spmv_db(w, c_pad, L, seg, 1, **kw), \
            "gust_spmv_padded_resident_db"
    else:
        fn, family = K.make_gust_spmv(w, c_pad, L, seg, 1, **kw), \
            "gust_spmv_padded_resident"
    _assert_kernel(_compile_text(fn, args), family)


@pytest.mark.parametrize("mat", sorted(MELLUM_EXPERTS))
def test_resident_spmv_compiles_at_mellum_experts(one_chip, mat):
    """Mellum2's expert matrices (896 x 2304 and 2304 x 896 at density
    0.1, l=256, c_pad as the planner packs them), decoded at batch 64: the
    double-buffered resident kernel the served expert layers run."""
    from repro.kernels import gust_spmv as K

    w, seg, c_pad = MELLUM_EXPERTS[mat]
    rows, b = w * c_pad, 64

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    args = (spec((rows, L), "float32"), spec((rows, L), "int32"),
            spec((rows, L), "int32"),
            spec((b, K._resident_x_rows(seg), L), "float32"))
    fn = K.make_gust_spmv_db(w, c_pad, L, seg, b, c_blk=C_BLK,
                             interpret=False)
    _assert_kernel(_compile_text(fn, args), "gust_spmv_padded_resident_db")


def test_resident_db_compiles_at_yi_decode_batch(one_chip):
    """yi_6b's w_down (16 windows, 43 segments) at batch 8, the chat
    cell's decode batch: one full sublane tile of batch through the
    double-buffered resident kernel's lane-gather route."""
    from repro.kernels import gust_spmv as K

    w, seg, c_pad, _, _ = SHAPES["w_down"]
    rows, b = w * c_pad, 8

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    args = (spec((rows, L), "float32"), spec((rows, L), "int32"),
            spec((rows, L), "int32"),
            spec((b, K._resident_x_rows(seg), L), "float32"))
    fn = K.make_gust_spmv_db(w, c_pad, L, seg, b, c_blk=C_BLK,
                             interpret=False)
    _assert_kernel(_compile_text(fn, args), "gust_spmv_padded_resident_db")


@pytest.mark.parametrize("mat", sorted(SHAPES))
def test_gather_fill_compiles_for_v5e(one_chip, mat):
    from repro.kernels.gather_fill import make_gather_fill
    from repro.kernels.gust_spmv import _resident_x_rows

    w, seg, c_pad, _, _ = SHAPES[mat]
    rows = w * c_pad
    fn = make_gather_fill(rows, L, seg, B, c_blk=C_BLK, interpret=False)
    args = (jax.ShapeDtypeStruct((rows, L), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((B, _resident_x_rows(seg), L), jnp.float32,
                                 sharding=one_chip))
    _assert_kernel(_compile_text(fn, args), "gust_gather_fill")


def test_spgemm_compiles_for_v5e(one_chip):
    """SpGEMM at l=256 with a 256-row condensed B of 8 pairs a row and 256
    output columns (wider outputs need the tiled accumulator, ROADMAP R7)."""
    from repro.kernels.gust_spgemm import make_gust_spgemm

    t_blk, w, r_rows, k_max, n_out = 64, 8, 256, 8, 256
    fn = make_gust_spgemm(t_blk, w, L, r_rows, k_max, n_out, c_blk=C_BLK,
                          interpret=False)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((t_blk,), jnp.int32), spec((w + 1,), jnp.int32),
            spec((t_blk * C_BLK, L), jnp.float32),
            spec((t_blk * C_BLK, L), jnp.int32),
            spec((t_blk * C_BLK, L), jnp.int32),
            spec((r_rows, k_max), jnp.float32),
            spec((r_rows, k_max), jnp.int32))
    _assert_kernel(_compile_text(fn, args), "gust_spgemm")
