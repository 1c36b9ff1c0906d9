"""GUST SpMV execution (JAX): the pure-jnp oracle + legacy entry shims.

The scheduled format turns SpMV into three dense streaming steps — exactly
the paper's three hardware levels:

  1. multiply   : ``P = M_sch * v[Col_sch]``          (the l multipliers)
  2. route      : partial product (c, j) goes to adder ``Row_sch[c, j]``
                  of its window                        (the crossbar)
  3. accumulate : adders integrate per window, dump at window end.

:func:`spmv_scheduled` is the raw-schedule oracle the kernel tests
compare against.  Every other entry point here (``spmv``,
``spmm_scheduled``, ``spmm_ragged``, ``distributed_spmv``) is a legacy
shim that constructs a :class:`~repro.core.plan.GustPlan` and delegates —
new code should call ``repro.plan(matrix, config).spmv(v)`` / ``.spmm(x)``
/ ``.shard(mesh)`` directly.
"""

from __future__ import annotations

import functools
import warnings
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .formats import COOMatrix, GustSchedule
from .packing import RaggedSchedule, window_ids

__all__ = [
    "spmv_dense_ref",
    "spmv_scheduled",
    "spmv",
    "spmm_scheduled",
    "spmm_ragged",
    "distributed_spmv",
]


def spmv_dense_ref(dense: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Oracle: plain dense matvec."""
    return dense @ v


@functools.partial(jax.jit, static_argnames=("m", "l", "num_windows"))
def _spmv_scheduled_impl(
    m_sch: jnp.ndarray,
    row_sch: jnp.ndarray,
    col_sch: jnp.ndarray,
    window_of_cycle: jnp.ndarray,
    row_perm: jnp.ndarray,
    v: jnp.ndarray,
    *,
    m: int,
    l: int,
    num_windows: int,
) -> jnp.ndarray:
    # Level 1: the multipliers.  Buffer Filler == gather by Col_sch.
    v_sch = jnp.take(v, col_sch, axis=0, mode="clip")  # (C_total, l)
    partial = m_sch.astype(jnp.float32) * v_sch.astype(jnp.float32)
    # Levels 2+3: crossbar route + accumulate.  Global adder id is
    # window*l + row_sch; windows never share adders, so one segment-sum
    # implements every window's accumulate/dump.
    adder = window_of_cycle[:, None] * l + row_sch  # (C_total, l)
    y_sorted = jax.ops.segment_sum(
        partial.reshape(-1), adder.reshape(-1), num_segments=num_windows * l
    )
    # Undo the load-balancing row sort: scheduled row s is original row
    # row_perm[s].
    return jnp.zeros((m,), jnp.float32).at[row_perm].set(y_sorted[:m])


def spmv_scheduled(sched: GustSchedule, v: jnp.ndarray) -> jnp.ndarray:
    """SpMV from the *raw* (unpacked) scheduled format — the pure-jnp
    oracle the kernel and plan paths are validated against."""
    m, n = sched.shape
    if v.shape != (n,):
        raise ValueError(f"vector shape {v.shape} != ({n},)")
    return _spmv_scheduled_impl(
        jnp.asarray(sched.m_sch),
        jnp.asarray(sched.row_sch),
        jnp.asarray(sched.col_sch),
        jnp.asarray(window_ids(sched)),
        jnp.asarray(sched.row_perm),
        v,
        m=m,
        l=sched.l,
        num_windows=sched.num_windows,
    )


#: Identity-keyed LRU of shim plans: repeated ``spmm_scheduled`` calls on
#: the same schedule object reuse one plan (and its pack) without paying
#: the ScheduleCache's O(nnz) content hash per call.  Entries hold the
#: schedule strongly (via plan.sched), so an id can never be recycled
#: while its entry is alive; the identity re-check below makes a stale
#: hit impossible even after eviction.
_SHIM_PLANS: "OrderedDict[int, object]" = OrderedDict()
_SHIM_PLANS_MAX = 64


def spmm_scheduled(sched: GustSchedule, x: jnp.ndarray) -> jnp.ndarray:
    """Legacy shim: multi-vector SpMV, ``x`` (n, B) -> (m, B).

    Routes through a padded-layout :class:`~repro.core.plan.GustPlan`
    (paper §3.3: the schedule is reused for any vector); prefer
    ``repro.plan(sched, backend=...).spmm(x)``."""
    from .plan import PlanConfig, plan

    p = _SHIM_PLANS.get(id(sched))
    if p is None or p.sched is not sched:
        p = plan(
            sched, PlanConfig(l=sched.l, layout="padded", backend="jnp"),
            cache=None,
        )
        _SHIM_PLANS[id(sched)] = p
        while len(_SHIM_PLANS) > _SHIM_PLANS_MAX:
            _SHIM_PLANS.popitem(last=False)
    else:
        _SHIM_PLANS.move_to_end(id(sched))
    return p.spmm(x)


def spmm_ragged(ragged: RaggedSchedule, x: jnp.ndarray) -> jnp.ndarray:
    """Legacy shim: multi-vector SpMV from the ragged block stream,
    ``x`` (n, B) -> (m, B).  Streams ``T_blk * c_blk`` rows instead of the
    padded ``W * C_pad`` — on skewed matrices most of the padded stream is
    dead cycles.  Routes through :class:`~repro.core.plan.GustPlan`."""
    from .plan import GustPlan

    return GustPlan.from_artifact(ragged, backend="jnp").spmm(x)


def spmv(
    coo: COOMatrix,
    v: jnp.ndarray,
    l: int = 256,
    *,
    load_balance: bool = True,
    method: str = "fast",
) -> jnp.ndarray:
    """Deprecated convenience shim: schedule + execute in one call.

    Use ``repro.plan(coo, PlanConfig(l=..., colorer=...)).spmv(v)`` — the
    plan makes the schedule-once/execute-many contract explicit (and keeps
    the schedule resident in the content-keyed cache exactly as before;
    :func:`repro.core.packing.clear_cache` releases it)."""
    warnings.warn(
        "spmv(coo, v, l=..., method=...) is deprecated; use "
        "repro.plan(coo, PlanConfig(l=..., colorer=..., "
        "load_balance=...)).spmv(v) ('method' is spelled 'colorer', 'l' "
        "stays 'l')",
        DeprecationWarning,
        stacklevel=2,
    )
    from .plan import PlanConfig, plan

    return plan(
        coo,
        PlanConfig(l=l, colorer=method, load_balance=load_balance,
                   backend="jnp"),
    ).spmv(v)


def distributed_spmv(
    sched: GustSchedule,
    v: jnp.ndarray,
    mesh: jax.sharding.Mesh,
    axis: str = "data",
    *,
    c_blk: int = 1,
    cache="default",
):
    """Legacy shim for the paper's §5.5 "k parallel length-l GUSTs": shard
    row-windows across ``axis`` (contiguous window ranges balanced by
    ragged-stream block count; the schedule is untouched — paper: "the
    Edge-Coloring schedule would not need to change").  The vector is
    replicated; the outputs need no collective because windows own
    disjoint output rows, and are concatenated on the mesh's first device.

    Routes through ``repro.plan(sched, ...).shard(mesh, axis).spmv(v)`` —
    the plan owns the device-major layout memoization (``cache="default"``
    uses the process-global :class:`~repro.core.packing.ScheduleCache`,
    ``None`` re-packs every call)."""
    from .packing import default_cache
    from .plan import PlanConfig, plan

    if cache == "default":
        cache = default_cache
    p = plan(
        sched,
        PlanConfig(l=sched.l, layout="ragged", backend="jnp", c_blk=c_blk,
                   mesh_axis=axis),
        cache=cache,
    )
    return p.shard(mesh, axis).spmv(v)
