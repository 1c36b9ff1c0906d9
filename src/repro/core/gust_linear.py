"""GustLinear — the paper's technique as a first-class LM feature.

Decode-time LM inference is matvec-dominated: every projection computes
``W @ x`` for a handful of activation vectors.  ``GustLinear`` stores a
magnitude-pruned weight matrix as a :class:`~repro.core.plan.GustPlan`
(schedule computed once, at weight-load time — paper §3.3/§5.3
amortization) and executes the matvec through the plan's batch-major
``transpose_io`` fast path (no eager ``x.T``/``y.T`` round-trip).

Training and prefill stay dense (the paper defers SpMM to future work);
this module is wired into ``serving/`` via ``ArchConfig.sparsity``.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .formats import COOMatrix
from .packing import default_cache
from .plan import PlanConfig, plan as _plan

__all__ = ["SparsityConfig", "GustLinear", "prune_by_magnitude"]


@dataclasses.dataclass(frozen=True)
class SparsityConfig:
    """Deprecated serving-time weight-sparsity knobs.

    Use :class:`~repro.core.plan.PlanConfig` plus a ``density`` argument:
    ``gust_length`` is spelled ``PlanConfig.l``, ``method`` is
    ``PlanConfig.colorer``, ``use_kernel`` is ``PlanConfig.backend``
    (``"pallas"`` / ``"jnp"``).  Kept as a shim that normalizes to
    :attr:`plan_config`."""

    enable: bool = False
    density: float = 0.1  # fraction of weights kept after magnitude pruning
    gust_length: int = 256
    load_balance: bool = True
    method: str = "fast"  # edge-coloring method
    use_kernel: bool = False  # route through the Pallas kernel

    def __post_init__(self):
        warnings.warn(
            "SparsityConfig is deprecated; use GustLinear(w, "
            "config=PlanConfig(l=..., colorer=..., backend='pallas'|'jnp'), "
            "density=...) — 'gust_length' is spelled 'l', 'method' is "
            "'colorer', 'use_kernel' is backend='pallas'",
            DeprecationWarning,
            stacklevel=3,  # caller -> generated __init__ -> __post_init__
        )

    @property
    def plan_config(self) -> PlanConfig:
        """The normalized spelling of these knobs."""
        return PlanConfig(
            l=self.gust_length,
            colorer=self.method,
            load_balance=self.load_balance,
            layout="padded",
            backend="pallas" if self.use_kernel else "jnp",
        )


def prune_by_magnitude(w: np.ndarray, density: float) -> np.ndarray:
    """Keep the largest-|w| entries at the requested density."""
    if not 0.0 < density <= 1.0:
        raise ValueError("density must be in (0, 1]")
    k = max(int(round(w.size * density)), 1)
    thresh = np.partition(np.abs(w).ravel(), w.size - k)[w.size - k]
    out = np.where(np.abs(w) >= thresh, w, 0.0)
    return out


class GustLinear:
    """y = W_sparse @ x with W held as a :class:`GustPlan`.

    Not a pytree — this is a *serving* artifact built once from trained
    weights (analogous to a compiled engine).  ``__call__`` takes
    ``x: (B, n)`` and returns ``(B, m)``.

    Construction: ``GustLinear(w, config=PlanConfig(...), density=0.1)``.
    The legacy positional ``SparsityConfig`` is still accepted and
    normalized through :attr:`SparsityConfig.plan_config`.

    NOTE: construction goes through the process-global content-keyed
    :class:`~repro.core.packing.ScheduleCache`, so the schedule/packed
    arrays outlive this object (bounded by the cache's LRU size).
    Rebuilding a GustLinear over identical weights is then free; call
    :func:`repro.core.packing.clear_cache` to release the memory.
    """

    def __init__(
        self,
        w: np.ndarray,
        cfg: Optional[SparsityConfig] = None,
        *,
        config: Optional[PlanConfig] = None,
        density: Optional[float] = None,
        cache=default_cache,
    ):
        if w.ndim != 2:
            raise ValueError("GustLinear expects a 2-D weight matrix")
        if cfg is not None:
            if config is not None or density is not None:
                raise ValueError(
                    "pass either a legacy SparsityConfig or "
                    "config=PlanConfig(...) + density=..., not both"
                )
            config = cfg.plan_config
            density = cfg.density
        if config is None:
            config = PlanConfig(layout="padded", backend="jnp")
        if density is None:
            density = 0.1
        self.cfg = cfg  # legacy handle (None for plan-config construction)
        self.config = config
        self.density = density
        self.shape = w.shape
        w_pruned = prune_by_magnitude(np.asarray(w, np.float32), density)
        rows, cols = np.nonzero(w_pruned)
        coo = COOMatrix(
            w.shape,
            rows.astype(np.int64),
            cols.astype(np.int64),
            w_pruned[rows, cols].astype(np.float32),
        )
        self.nnz = coo.nnz
        # Plan once, at construction (content-keyed cache: rebuilding a
        # GustLinear over identical weights is free).  Touching .artifact
        # packs eagerly — both execution paths consume the packed form.
        self.plan = _plan(coo, config, cache=cache)
        self.sched = self.plan.sched
        self.packed = self.plan.artifact

    @property
    def cycles(self) -> int:
        return self.sched.cycles

    @property
    def hardware_utilization(self) -> float:
        return self.sched.hardware_utilization

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if x.ndim == 1:
            x = x[None, :]
            squeeze = True
        else:
            squeeze = False
        # batch-major fast path: both transposes live inside the jitted
        # executor instead of materializing (n, B)/(B, m) copies here
        y = self.plan.spmm(x, transpose_io=True)
        return y[0] if squeeze else y
