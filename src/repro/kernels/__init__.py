"""Pallas TPU kernels for the GUST hot path (compiled by Mosaic on a TPU,
interpreted elsewhere; ``tests/test_chip_compile.py`` compiles them for a
described v5e).

  gust_spmv.py        -- flagship: fused gather + lane-gather routing SpMV
                         over the padded (W, C_pad/c_blk) grid
  gust_spmv_ragged.py -- ragged color-block streaming variant: 1-D
                         scalar-prefetch grid over real blocks only
  gather_fill.py      -- standalone Buffer-Filler vector gather
  ops.py              -- jit'd public wrappers + padded/ragged dispatch
  ref.py              -- pure-jnp oracles (same block semantics, no Pallas)
"""

from .ops import (
    PackedSchedule,
    RaggedSchedule,
    pack_schedule,
    packed_spec,
    gust_spmm,
    gust_spmm_auto,
)
