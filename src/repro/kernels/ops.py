"""Execution layer of the GUST scheduled format + legacy entry shims.

The packed scheduled format itself lives in :mod:`repro.core.packing`;
the plan/execute API lives in :mod:`repro.core.plan` (one decision point
for layout/backend/shard choice).  This module owns only the jitted
executor, :func:`execute_spmm`, which runs ``y = M @ x`` from **either**
fixed-shape layout — a padded :class:`PackedSchedule` (dense
``(W, C_pad/c_blk)`` grid) or a ragged :class:`RaggedSchedule` block
stream (1-D scalar-prefetch grid over real blocks only) — through the
Pallas kernels (``use_kernel=True``) or the pure-XLA segment-sum path
(identical math; the kernel oracle and the default off TPU).

``gust_spmm`` / ``gust_spmm_auto`` remain as thin compatibility shims
that construct a :class:`~repro.core.plan.GustPlan` and delegate — new
code should call ``repro.plan(...).spmm(x)`` directly.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.formats import GustSchedule
from repro.core.packing import (
    PackedSchedule,
    RaggedSchedule,
    default_cache,
    pack_schedule,
    packed_spec,
    resolve_gather,
)
from repro.resilience import faults

from .gust_spmv import (
    _batch_pad,
    _resident_x_rows,
    make_gust_spmv,
    make_gust_spmv_db,
    make_gust_spmv_local,
    make_gust_spmv_local_db,
)
from .gust_spmv_ragged import (
    make_gust_spmv_ragged,
    make_gust_spmv_ragged_db,
    make_gust_spmv_ragged_local,
    make_gust_spmv_ragged_local_db,
)
from .ref import (
    gust_spmv_local_ref,
    gust_spmv_ragged_local_ref,
    gust_spmv_ragged_ref,
    gust_spmv_ref,
)

__all__ = [
    "PackedSchedule",
    "RaggedSchedule",
    "pack_schedule",
    "execute_spmm",
    "gust_spmm",
    "gust_spmm_auto",
    "packed_spec",
    "normalize_choice",
]

#: Legal values of every string knob the executor (and PlanConfig)
#: accepts — the one place rejection messages are defined.
EXECUTE_CHOICES = {
    "gather": ("resident", "local", "auto"),
    "backend": ("pallas", "jnp"),
    "layout": ("padded", "ragged", "auto"),
    "pipeline": ("single", "double", "auto"),
}


def normalize_choice(name: str, value: str, allowed: Tuple[str, ...] = None):
    """Validate a string knob against its allowed values, raising the one
    normalized rejection message every caller shares::

        unknown <name> 'x'; expected one of: 'a', 'b'

    Returns the value unchanged so call sites can validate inline.  The
    old failure mode for a typo'd ``gather``/``backend``/``layout`` was a
    late, opaque kernel- or trace-time error; this fails fast at the API
    edge instead."""
    if allowed is None:
        allowed = EXECUTE_CHOICES[name]
    if value not in allowed:
        raise ValueError(
            f"unknown {name} {value!r}; expected one of: "
            + ", ".join(repr(a) for a in allowed)
        )
    return value


def _prep_x(x: jnp.ndarray, n: int, l: int, *, resident: bool) -> jnp.ndarray:
    """Zero-pad x (n, B) f32 into a kernel layout, the hardware length on
    the lanes.  Local kernels stream segment tiles ``(S, B_pad, l)``, the
    batch on whole sublane groups; resident kernels hold ``(B, S8, l)``,
    the ``S`` segments padded to groups of eight for the sublane gather.
    The lane-reversed copy the gather selects against is derived
    in-kernel, so only one copy of x crosses HBM->VMEM."""
    seg_count = -(-n // l)
    b = x.shape[1]
    x = x.astype(jnp.float32)
    if resident:
        rows = _resident_x_rows(seg_count)
        return jnp.pad(x, ((0, rows * l - n), (0, 0))).T.reshape(b, rows, l)
    xp = jnp.pad(x, ((0, seg_count * l - n), (0, _batch_pad(b) - b)))
    return xp.reshape(seg_count, l, -1).transpose(0, 2, 1)


def _seg_flat(packed) -> jnp.ndarray:
    """The pack-time segment table flattened to (T_blk * S_blk,) int32 —
    the scalar-prefetch operand steering the local kernels' x-tile
    pipeline."""
    return jnp.asarray(packed.seg_blk, jnp.int32).reshape(-1)


def execute_spmm(
    packed: Union[PackedSchedule, RaggedSchedule],
    x: jnp.ndarray,
    *,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
    c_blk: int = 8,
    transpose_io: bool = False,
    gather: str = "auto",
    pipeline: str = "auto",
    backend: str = None,
    layout: str = "auto",
) -> jnp.ndarray:
    """``y = M @ x`` — host-side dispatch wrapper around the jitted
    executor core.

    The wrapper exists so the resilience fault sites (``kernel.execute``
    tagged with the effective backend, and ``gather.local`` when the
    resolved Buffer-Filler mode is local — ROADMAP §Resilience
    invariants) fire on every *call*, not once per trace: a Python-level
    trip inside the jitted body would only ever fire at trace time.
    With no FaultPlan installed the extra cost is one module-global
    check; all math, validation, and dispatch live in the core (see its
    docstring for the knob semantics)."""
    if faults.enabled():
        eff_kernel = use_kernel if backend is None else backend == "pallas"
        faults.trip("kernel.execute", tag="pallas" if eff_kernel else "jnp")
        eff_gather = gather
        if eff_gather == "auto":
            eff_gather = resolve_gather(packed.s_blk, packed.seg_count)
        if eff_gather == "local":
            faults.trip("gather.local")
    return _execute_spmm_impl(
        packed,
        x,
        use_kernel=use_kernel,
        interpret=interpret,
        c_blk=c_blk,
        transpose_io=transpose_io,
        gather=gather,
        pipeline=pipeline,
        backend=backend,
        layout=layout,
    )


@functools.partial(
    jax.jit,
    static_argnames=("use_kernel", "interpret", "c_blk", "transpose_io",
                     "gather", "pipeline", "backend", "layout"),
)
def _execute_spmm_impl(
    packed: Union[PackedSchedule, RaggedSchedule],
    x: jnp.ndarray,
    *,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
    c_blk: int = 8,
    transpose_io: bool = False,
    gather: str = "auto",
    pipeline: str = "auto",
    backend: str = None,
    layout: str = "auto",
) -> jnp.ndarray:
    """``y = M @ x`` from either fixed-shape scheduled layout;
    x (n, B) -> y (m, B).

    ``c_blk`` only applies to the padded layout (a ragged stream's block
    height is baked in at pack time), and there only to the
    *unquantized resident* path — the local path runs at the pack-time
    block height its gather tables were built for, and a quantized
    stream's scales are per pack-time block; both raise ``ValueError``
    on a mismatched override instead of silently ignoring it.
    ``transpose_io=True`` takes and returns batch-major arrays instead —
    x (B, n) -> y (B, m) — with both transposes inside this jit (XLA
    fuses them into the gather/scatter), so batch-major callers never
    materialize a transposed copy.

    ``gather`` selects the Buffer-Filler mode: ``"resident"`` (x whole in
    VMEM, a walk over every group of eight column segments), ``"local"``
    (stream only the ``S_blk`` x tiles each block references via the
    pack-time segment table — O(S_blk) gather work per slot, no whole-x
    VMEM residency), or ``"auto"`` (the
    :func:`~repro.core.packing.resolve_gather` locality-ratio decision).
    Both modes are bit-identical.

    ``pipeline`` selects the kernel fetch pipeline: ``"single"`` (one
    tile in flight, the reduction as extra grid dimensions) or
    ``"double"`` (two-slot ping/pong async copies overlapping the fetch
    of tile ``i+1`` with the math of tile ``i``, the reduction as an
    in-kernel loop).  ``"auto"`` means double on the kernel path.  The
    two are bit-identical; the jnp path ignores the knob.

    ``backend`` optionally overrides ``use_kernel`` with the plan-level
    spelling: ``"pallas"`` / ``"jnp"`` (``None`` keeps ``use_kernel``).
    ``layout`` is an assertion, not a choice — the layout is carried by
    the artifact's type; naming the wrong one raises instead of silently
    running the other stream.  Unknown ``gather``/``pipeline``/
    ``backend``/``layout`` strings raise the normalized
    :func:`normalize_choice` rejection."""
    normalize_choice("gather", gather)
    normalize_choice("pipeline", pipeline)
    normalize_choice("layout", layout)
    if backend is not None:
        normalize_choice("backend", backend)
        use_kernel = backend == "pallas"
    actual_layout = (
        "ragged" if isinstance(packed, RaggedSchedule) else "padded"
    )
    if layout not in ("auto", actual_layout):
        raise ValueError(
            f"layout={layout!r} requested but the packed artifact is "
            f"{actual_layout} (the layout is decided at pack time)"
        )
    m, n = packed.shape
    if transpose_io:
        if x.ndim != 2 or x.shape[1] != n:
            raise ValueError(
                f"expected batch-major x of shape (B, {n}) with "
                f"transpose_io=True, got {x.shape}"
            )
        x = x.T
    elif x.ndim != 2 or x.shape[0] != n:
        raise ValueError(f"expected x of shape ({n}, B), got {x.shape}")
    l, W = packed.l, packed.num_windows
    b = x.shape[1]
    ragged = isinstance(packed, RaggedSchedule)
    quant = packed.scale_blk is not None
    if gather == "auto":
        gather = resolve_gather(packed.s_blk, packed.seg_count)
    if not ragged and c_blk != packed.c_blk:
        if gather == "local":
            raise ValueError(
                f"c_blk={c_blk} override on the padded local path is not "
                f"executable: the pack-time gather tables were built at "
                f"c_blk={packed.c_blk} (re-pack at the desired block "
                f"height, or use gather='resident')"
            )
        if quant:
            raise ValueError(
                f"c_blk={c_blk} override on a quantized stream is not "
                f"executable: the per-block scales are aligned to the "
                f"pack-time c_blk={packed.c_blk} blocks (re-pack at the "
                f"desired block height)"
            )

    if use_kernel and packed.fusable:
        double = pipeline != "single"
        x2d = _prep_x(x, n, l, resident=gather != "local")
        vdt, idt = str(packed.m_blk.dtype), str(packed.col_blk.dtype)
        # the per-block scales ride as the last scalar-prefetch operand
        scale = (jnp.asarray(packed.scale_blk, jnp.float32),) if quant else ()
        kw = dict(c_blk=packed.c_blk, interpret=interpret, quantized=quant)
        # the kernels route by the source-lane table, not the adder index
        stream = (packed.m_blk, packed.col_blk, packed.src_blk, x2d)
        stream_loc = (packed.m_blk, packed.col_loc, packed.src_blk, x2d)
        if ragged:
            steer = (packed.block_window, packed.block_starts)
            if gather == "local":
                build = (make_gust_spmv_ragged_local_db if double
                         else make_gust_spmv_ragged_local)
                fn = build(packed.num_blocks, W, l, packed.s_blk, b, **kw)
                y_win = fn(*steer, _seg_flat(packed), *scale, *stream_loc)
            elif double:
                fn = make_gust_spmv_ragged_db(
                    packed.num_blocks, W, l, packed.seg_count, b,
                    value_dtype=vdt, index_dtype=idt, **kw,
                )
                y_win = fn(packed.block_starts, *scale, *stream)
            else:
                fn = make_gust_spmv_ragged(
                    packed.num_blocks, W, l, packed.seg_count, b, **kw
                )
                y_win = fn(*steer, *scale, *stream)
        elif gather == "local":
            build = make_gust_spmv_local_db if double else make_gust_spmv_local
            fn = build(W, packed.c_pad, l, packed.s_blk, b, **kw)
            y_win = fn(_seg_flat(packed), *scale, *stream_loc)
        else:
            if not quant:
                kw["c_blk"] = c_blk
            if double:
                fn = make_gust_spmv_db(
                    W, packed.c_pad, l, packed.seg_count, b,
                    value_dtype=vdt, index_dtype=idt, **kw,
                )
            else:
                fn = make_gust_spmv(W, packed.c_pad, l, packed.seg_count, b,
                                    **kw)
            y_win = fn(*scale, *stream)
        # (W, B_pad, l) kernel accumulators -> (W, l, B)
        y_win = y_win[:, :b, :].transpose(0, 2, 1)
    else:
        seg_count = -(-n // l)
        xp = jnp.pad(x, ((0, seg_count * l - n), (0, 0)))
        scale_kw = {"scale_blk": packed.scale_blk} if quant else {}
        if ragged:
            if gather == "local":
                y_win = gust_spmv_ragged_local_ref(
                    packed.m_blk,
                    packed.col_loc,
                    packed.row_blk,
                    packed.seg_blk,
                    packed.block_window,
                    xp,
                    num_windows=W,
                    l=l,
                    c_blk=packed.c_blk,
                    **scale_kw,
                )
            else:
                y_win = gust_spmv_ragged_ref(
                    packed.m_blk,
                    packed.col_blk,
                    packed.row_blk,
                    packed.block_window,
                    xp,
                    num_windows=W,
                    l=l,
                    c_blk=packed.c_blk,
                    **scale_kw,
                )
        elif gather == "local":
            y_win = gust_spmv_local_ref(
                packed.m_blk,
                packed.col_loc,
                packed.row_blk,
                packed.seg_blk,
                xp,
                num_windows=W,
                l=l,
                c_blk=packed.c_blk,
                **scale_kw,
            )
        else:
            y_win = gust_spmv_ref(
                packed.m_blk,
                packed.col_blk,
                packed.row_blk,
                xp,
                num_windows=W,
                l=l,
                c_blk=packed.c_blk,
                **scale_kw,
            )
    y_sorted = y_win.reshape(W * l, b)
    if packed.identity_perm:
        # load_balance=False packs carry the identity permutation: the
        # scheduled row order IS the output order, so skip the scatter
        # (bit-identical: zeros.at[arange].set(y) == y)
        y = y_sorted[:m].astype(x.dtype)
    else:
        out = jnp.zeros((max(m, W * l), b), jnp.float32)
        out = out.at[packed.row_perm].set(y_sorted)
        y = out[:m].astype(x.dtype)
    return y.T if transpose_io else y


def gust_spmm(
    packed: Union[PackedSchedule, RaggedSchedule],
    x: jnp.ndarray,
    *,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
    c_blk: int = 8,
) -> jnp.ndarray:
    """Legacy packed-entry shim: ``y = M @ x``, x (n, B) -> y (m, B).

    Routes through :class:`~repro.core.plan.GustPlan` (the single
    execution path); prefer ``repro.plan(matrix, ...).spmm(x)``."""
    from repro.core.plan import GustPlan

    return GustPlan.from_artifact(
        packed,
        backend="pallas" if use_kernel else "jnp",
        interpret=interpret,
        c_blk=c_blk,
    ).spmm(x)


def gust_spmm_auto(
    sched: GustSchedule,
    x: jnp.ndarray,
    *,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
    c_blk: int = 8,
    waste_threshold: float = None,
    cache=default_cache,
) -> jnp.ndarray:
    """Deprecated schedule-level shim: auto-select ragged vs padded by the
    measured waste ratio, pack through the content-keyed cache, execute.

    Use ``repro.plan(schedule, PlanConfig(layout="auto", ...)).spmm(x)``
    instead — the plan owns the one layout/backend decision point."""
    warnings.warn(
        "gust_spmm_auto(sched, x, use_kernel=...) is deprecated; use "
        "repro.plan(sched, PlanConfig(layout='auto', backend='pallas'|'jnp'"
        ", c_blk=...)).spmm(x)",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.core.plan import PlanConfig, plan

    p = plan(
        sched,
        PlanConfig(
            l=sched.l,
            layout="auto",
            backend="pallas" if use_kernel else "jnp",
            interpret=interpret,
            c_blk=c_blk,
            waste_threshold=waste_threshold,
        ),
        cache=cache,
    )
    return p.spmm(x)
