"""GustPlan — the one plan/execute API for every schedule→pack→run path.

The paper's amortization story (§3.3/§5.3) is an FFTW-style *plan*: at
matrix-load time you pay once for the edge-coloring schedule and the
packed execution layout, then execute against any number of vectors.
Before this module that contract was implicit and scattered across seven
entry points (``spmv``, ``spmm_scheduled``, ``spmm_ragged``,
``distributed_spmv``, ``gust_spmm``/``gust_spmm_auto``, ``GustLinear``,
serving), each re-threading its own copy of the layout/backend knobs.
Here it is explicit in the type system:

    >>> import repro
    >>> p = repro.plan(matrix, repro.PlanConfig(l=256, layout="auto"))
    >>> y = p.spmv(v)            # execute many times against one plan
    >>> Y = p.spmm(X)            # multi-vector (decode-batch) execution
    >>> p.shard(mesh).spmv(v)    # k parallel length-l GUSTs (paper §5.5)
    >>> p.cost()                 # measured + Eq. 9-11 predicted cost
    >>> spec = p.to_spec()       # leaves/meta wire format (serving stacks)

Decision points owned by the plan (and nowhere else):

  * **layout** — ``padded`` (dense ``(W, C_pad)`` grid), ``ragged`` (block
    stream of only real cycle blocks), or ``auto`` (pick by the measured
    padding-waste ratio, :data:`~repro.core.packing.DEFAULT_WASTE_THRESHOLD`).
  * **backend** — ``jnp`` (pure-XLA segment-sum), ``pallas`` (fused TPU
    kernel), or ``auto`` (Pallas on TPU when the schedule is fusable).
  * **dtype policy** — value/index leaf dtypes (``bfloat16``/``int16``
    halve the streamed bytes, the paper's packed-word analogue).
  * **sharding** — :meth:`GustPlan.shard` owns the device-major layout
    memoization that ``distributed_spmv`` used to hand-roll.

Packing is lazy: a plan schedules eagerly (the expensive, cache-shared
step) and materializes its packed artifact on first execution, so
schedule-only consumers (cycle models, cost estimates) never pay for
blocks they don't stream.  All caching is content-keyed through
:class:`~repro.core.packing.ScheduleCache`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .formats import COOMatrix, GustSchedule, coo_from_dense
from .scheduler import sched_counters
from .spans import Span
from .packing import (
    PackedSchedule,
    RaggedSchedule,
    ScheduleCache,
    default_cache,
    pack_ragged,
    pack_schedule,
    resolve_gather,
    resolve_tuning,
    packed_from_leaves,
    packed_leaves,
    packed_meta,
    packed_spec,
    ragged_from_leaves,
    ragged_leaves,
    ragged_meta,
    ragged_spec,
    ragged_waste_ratio,
    resolve_layout,
    splice_ragged_blocks,
)
from repro.resilience import faults
from repro.resilience.fallback import record_fallback, resolve_fallback

__all__ = [
    "PlanConfig",
    "PlanCost",
    "TuneResult",
    "GustPlan",
    "plan",
    "reschedule",
    "RescheduleResult",
]

_LAYOUTS = ("padded", "ragged", "auto")
_BACKENDS = ("jnp", "pallas", "auto")
_COLORERS = ("paper", "fast", "exact")
_GATHERS = ("resident", "local", "auto")
_PIPELINES = ("single", "double", "auto")


@dataclasses.dataclass(frozen=True)
class PlanConfig:
    """Every knob of the schedule→pack→execute pipeline, in one frozen type.

    Attributes:
      l:               GUST length (number of multipliers == adders).
      colorer:         edge-coloring method — ``paper`` (Listing 1 greedy),
                       ``fast`` (vectorized equivalent), ``exact`` (König
                       Δ-coloring).
      load_balance:    apply the §3.5 row/lane balancing permutations.
      c_blk:           cycle-block height (pack granularity and padded-
                       kernel VMEM blocking).
      layout:          ``padded`` | ``ragged`` | ``auto`` (measured waste).
      backend:         ``jnp`` | ``pallas`` | ``auto`` (Pallas on TPU when
                       the schedule is fusable).
      gather:          Buffer-Filler mode — ``resident`` (x whole in
                       VMEM, a walk over every column segment),
                       ``local`` (stream only the ``S_blk`` x tiles each
                       block references via the pack-time segment table),
                       or ``auto`` (segment-local when the measured
                       ``S_blk / seg_count`` locality ratio is low —
                       :func:`~repro.core.packing.resolve_gather`).
      pipeline:        VMEM streaming mode of the Pallas kernels —
                       ``single`` (one tile in flight), ``double``
                       (two-slot ping/pong scratch: the DMA fetching
                       tile ``s+1`` overlaps the accumulate of tile
                       ``s``), or ``auto`` (double on the kernel path).
                       Bit-identical either way; the jnp backend
                       ignores it.
      waste_threshold: padded/ragged stream ratio above which ``auto``
                       picks ragged; ``None`` = the shared default.
      value_dtype:     dtype name of the value leaves (``float32`` |
                       ``bfloat16`` | ``int8``).  ``int8`` turns on
                       pack-time per-block quantization: values are
                       stored int8 with one f32 scale per ``c_blk``
                       cycle block (``scale_blk``), dequantized in-kernel
                       with a single f32 multiply.  Because the scales
                       are aligned to the *pack-time* ``c_blk`` blocks,
                       an execute-time ``c_blk`` override is rejected on
                       quantized plans — re-pack instead.
      index_dtype:     dtype name of the index leaves (``int32`` |
                       ``int16``).
      interpret:       Pallas interpret mode; ``None`` = interpret off TPU.
      mesh_axis:       default mesh axis name for :meth:`GustPlan.shard`.
    """

    l: int = 256
    colorer: str = "fast"
    load_balance: bool = True
    c_blk: int = 8
    layout: str = "auto"
    backend: str = "auto"
    gather: str = "auto"
    pipeline: str = "auto"
    waste_threshold: Optional[float] = None
    value_dtype: str = "float32"
    index_dtype: str = "int32"
    interpret: Optional[bool] = None
    mesh_axis: str = "data"

    def __post_init__(self):
        if self.l < 1:
            raise ValueError(f"l must be >= 1, got {self.l}")
        if self.c_blk < 1:
            raise ValueError(f"c_blk must be >= 1, got {self.c_blk}")
        if self.layout not in _LAYOUTS:
            raise ValueError(f"layout must be one of {_LAYOUTS}, got {self.layout!r}")
        if self.backend not in _BACKENDS:
            raise ValueError(
                f"backend must be one of {_BACKENDS}, got {self.backend!r}"
            )
        if self.colorer not in _COLORERS:
            raise ValueError(
                f"colorer must be one of {_COLORERS}, got {self.colorer!r}"
            )
        if self.gather not in _GATHERS:
            raise ValueError(
                f"gather must be one of {_GATHERS}, got {self.gather!r}"
            )
        if self.pipeline not in _PIPELINES:
            raise ValueError(
                f"pipeline must be one of {_PIPELINES}, got {self.pipeline!r}"
            )
        # normalize dtypes to canonical names so configs hash/compare/
        # serialize stably whether built from strings or jnp dtypes
        object.__setattr__(self, "value_dtype", jnp.dtype(self.value_dtype).name)
        object.__setattr__(self, "index_dtype", jnp.dtype(self.index_dtype).name)

    @property
    def value_jnp(self):
        return jnp.dtype(self.value_dtype)

    @property
    def index_jnp(self):
        return jnp.dtype(self.index_dtype)

    def to_dict(self) -> Dict:
        """Plain-JSON form (the config part of :meth:`GustPlan.to_spec`)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict) -> "PlanConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


@dataclasses.dataclass(frozen=True)
class PlanCost:
    """Measured + predicted cost of one plan (wraps ``core.bounds``).

    ``cycles``/``utilization`` come from the *actual* schedule (the paper's
    own evaluation path); ``waste_ratio`` is the measured padded/ragged
    stream ratio that drives the ``auto`` layout choice; ``expected_*``
    are the Eq. 9-11 statistical bounds at the matrix's measured density.

    The gather-locality block (PR 5) quantifies both Buffer-Filler modes
    without executing — this is what ``dryrun`` reads to show the
    segment-local win:

    * ``s_blk`` / ``locality_ratio`` — measured per-block segment working
      set and its ratio to ``seg_count`` (the ``gather="auto"`` signal);
    * ``gather_flops_resident`` / ``gather_flops_local`` — fused-gather
      FLOPs per vector column: ``4 · slots · seg_count`` vs
      ``4 · slots · S_blk`` (two one-hot contractions, 2 flops/MAC);
    * ``gather_walk_steps`` — steps of the resident walk per color block,
      ``ceil(seg_count / 8)``: each step gathers one group of eight
      column segments with sublane gathers;
    * ``route_lane_gathers`` — lane gathers of the crossbar per color
      block and 8 batch rows, ``c_blk · (l / 128)²`` (``c_blk`` at
      l <= 128): each cycle row builds every 128-lane output chunk from
      one gather per source chunk (32 at l = 256), on every block of
      every call (plus ``(l / 128)²`` a block for the values);
    * ``x_vmem_bytes_resident`` / ``x_vmem_bytes_local`` — f32 x-tile
      VMEM residency per vector column: the whole padded vector
      (``seg_count · l · 4``) vs one block's tile working set
      (``S_blk · l · 4``) — the resident number is the width cap the
      local mode removes;
    * ``gather`` — the mode this plan resolves to.

    The observability block (PR 6) records *why a path was taken* so
    benchmarks and serving logs can report it without re-deriving the
    resolution logic:

    * ``backend`` / ``pipeline`` — the resolved (never ``auto``) execution
      choices next to the resolved ``layout``/``gather``;
    * ``cache_hits`` / ``cache_misses`` / ``cache_entries`` /
      ``cache_evictions`` — the plan's
      :class:`~repro.core.packing.ScheduleCache` counters at cost time
      (all zero for cache-less plans); evictions count LRU capacity drops
      (PR 7).
    * ``store_hits`` / ``store_misses`` — the plan's attached
      :class:`~repro.core.plan_store.PlanStore` counters (zero when the
      plan was built without ``store=``).

    The resilience block (PR 10) counts graceful-degradation downgrades
    applied on this plan's execution path, each routed through the
    single :func:`repro.resilience.resolve_fallback` decision point:

    * ``fallback_kernel`` — Pallas kernel failures retried on the jnp
      oracle (tolerance-identical);
    * ``fallback_gather`` — local-gather failures retried resident
      (bitwise-identical, PR 5);
    * ``fallback_store`` — store read failures (after jittered-backoff
      retries) served by a fresh pack (bitwise-identical, PR 7).
    """

    cycles: int
    utilization: float
    waste_ratio: float
    layout: str
    streamed_slots: int
    stream_bytes: int
    density: float
    expected_colors: float
    expected_cycles: float
    expected_utilization: float
    gather: str
    s_blk: int
    locality_ratio: float
    gather_flops_resident: int
    gather_flops_local: int
    gather_walk_steps: int
    route_lane_gathers: int
    x_vmem_bytes_resident: int
    x_vmem_bytes_local: int
    backend: str = "jnp"
    pipeline: str = "single"
    cache_hits: int = 0
    cache_misses: int = 0
    cache_entries: int = 0
    cache_evictions: int = 0
    store_hits: int = 0
    store_misses: int = 0
    fallback_kernel: int = 0
    fallback_gather: int = 0
    fallback_store: int = 0

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Record of one measured :meth:`GustPlan.tune` sweep.

    Candidate keys are ``(c_blk, l, layout, gather)`` tuples.  ``choice``
    is the winner picked by the single tuning decision point
    (:func:`~repro.core.packing.resolve_tuning`): the fastest measured
    candidate, unless it fails to beat ``baseline`` — the plan's static
    ``resolve_layout``/``resolve_gather`` resolution — by the margin, in
    which case the baseline stands.  ``cost_consistent`` validates the
    winner against the cost-model ordering: it streams no more bytes
    than the baseline predicted (a ``False`` here flags a measurement
    that contradicts the Eq. 9-11 story and is worth a look, not an
    error).  ``pruned`` lists candidates :class:`PlanCost` rejected
    before timing (predicted stream bytes beyond ``prune_ratio`` × the
    best prediction)."""

    choice: Tuple[int, int, str, str]
    baseline: Tuple[int, int, str, str]
    measurements: Dict[Tuple[int, int, str, str], float]
    predicted_bytes: Dict[Tuple[int, int, str, str], int]
    improvement: float
    cost_consistent: bool
    pruned: Tuple[Tuple[int, int, str, str], ...] = ()

    def to_dict(self) -> Dict:
        key = lambda k: f"c_blk={k[0]},l={k[1]},layout={k[2]},gather={k[3]}"
        return {
            "choice": key(self.choice),
            "baseline": key(self.baseline),
            "measurements": {key(k): v for k, v in self.measurements.items()},
            "predicted_bytes": {
                key(k): v for k, v in self.predicted_bytes.items()
            },
            "improvement": self.improvement,
            "cost_consistent": self.cost_consistent,
            "pruned": [key(k) for k in self.pruned],
        }

    @classmethod
    def from_dict(cls, d: Dict) -> "TuneResult":
        """Inverse of :meth:`to_dict` — how a PlanStore warm start revives
        the recorded sweep on the loaded plan."""

        def parse(s: str) -> Tuple[int, int, str, str]:
            kv = dict(part.split("=", 1) for part in s.split(","))
            return (int(kv["c_blk"]), int(kv["l"]), kv["layout"], kv["gather"])

        return cls(
            choice=parse(d["choice"]),
            baseline=parse(d["baseline"]),
            measurements={parse(k): v for k, v in d["measurements"].items()},
            predicted_bytes={
                parse(k): v for k, v in d["predicted_bytes"].items()
            },
            improvement=d["improvement"],
            cost_consistent=d["cost_consistent"],
            pruned=tuple(parse(k) for k in d.get("pruned", [])),
        )


def plan(
    matrix: Union[np.ndarray, COOMatrix, GustSchedule],
    config: Optional[PlanConfig] = None,
    *,
    cache: Optional[ScheduleCache] = default_cache,
    store=None,
    workers: Optional[int] = None,
    **overrides,
) -> "GustPlan":
    """Schedule ``matrix`` once and return an executable :class:`GustPlan`.

    ``matrix`` may be a dense 2-D array (numpy or jax), a
    :class:`COOMatrix`, or an already-built :class:`GustSchedule` (whose
    ``l`` wins over the config's).  Scheduling is served from ``cache`` (content-keyed; pass
    ``cache=None`` to bypass), so two plans over the same matrix schedule
    exactly once.  Keyword ``overrides`` are applied on top of ``config``:
    ``plan(m, l=64, layout="ragged")``.

    ``store`` (a :class:`~repro.core.plan_store.PlanStore`) extends the
    amortization across processes: on a hit the packed artifact is loaded
    straight off disk — zero coloring or packing work — and on a miss the
    fresh plan persists its artifact (plus any ``TuneResult``) the first
    time the pack materializes.  Store-loaded plans execute bit-
    identically but carry no schedule (``cost()``/``tune()``/``shard()``
    need a fresh plan).  ``workers`` forwards to the window-chunked
    parallel colorer (None = auto); it never affects plan content.
    """
    if config is None:
        config = PlanConfig()
    if overrides:
        config = dataclasses.replace(config, **overrides)

    if isinstance(matrix, GustSchedule):
        sched = matrix
        if sched.l != config.l:
            config = dataclasses.replace(config, l=sched.l)
        return GustPlan(config, sched=sched, cache=cache)

    _source = None

    if isinstance(matrix, (np.ndarray, jax.Array)):
        dense = np.asarray(matrix)
        if dense.ndim != 2:
            raise ValueError(f"dense matrix must be 2-D, got shape {dense.shape}")
        matrix = coo_from_dense(dense)
    if not isinstance(matrix, COOMatrix):
        raise TypeError(
            "plan() takes a dense (numpy or jax) array, a COOMatrix or a "
            f"GustSchedule; got {type(matrix).__name__}"
        )
    _source = matrix  # kept on the plan so tune() can sweep l

    store_key = None
    store_fallbacks = 0
    if store is not None:
        store_key = store.key(ScheduleCache.matrix_key(matrix), config)
        io0 = store.io_errors
        record = store.get(store_key)
        if record is None and store.io_errors > io0:
            # The read failed even after the store's jittered-backoff
            # retries: degrade stored -> fresh (bitwise-identical, the
            # PR 7 warm==cold gate) and count it on the fresh plan.
            record_fallback("store")
            store_fallbacks = 1
        if record is not None:
            spec = record["spec"]
            spec = dict(spec, leaves={
                k: jnp.asarray(v) for k, v in spec["leaves"].items()
            })
            p = GustPlan.from_spec(spec, config=config, cache=cache)
            p._source = matrix
            p._store = store
            p._store_key = store_key
            p._store_loaded = True
            if record.get("tuning"):
                p.tuning = TuneResult.from_dict(record["tuning"])
            p.summary = record.get("summary")
            return p

    with Span("build.colour", sched_counters, "colour_s"):
        if cache is None:
            from .scheduler import schedule as _schedule

            sched = _schedule(
                matrix, config.l, load_balance=config.load_balance,
                method=config.colorer, workers=workers,
            )
        else:
            sched = cache.schedule(
                matrix, config.l, load_balance=config.load_balance,
                method=config.colorer, workers=workers,
            )
    p = GustPlan(config, sched=sched, cache=cache, source=_source)
    p._store = store
    p._store_key = store_key
    p._fallbacks["store"] = store_fallbacks
    return p


class GustPlan:
    """Executable GUST artifact: schedule + packed layout + backend choice.

    Built by :func:`plan` (or :meth:`from_spec` / :meth:`from_artifact`).
    The plan owns the scheduled and packed artifacts for one matrix and is
    the single internal execution route — every legacy entry point
    (``spmv``, ``gust_spmm``, ``GustLinear``, serving, ...) constructs one
    and delegates to :meth:`spmv` / :meth:`spmm`.

    Not a pytree: like a compiled FFTW/cuDNN plan this is a host-side
    handle; its array leaves (``.artifact``) are the pytree that crosses
    into jit.
    """

    def __init__(
        self,
        config: PlanConfig,
        *,
        sched: Optional[GustSchedule] = None,
        artifact: Optional[Union[PackedSchedule, RaggedSchedule]] = None,
        cache: Optional[ScheduleCache] = None,
        mesh=None,
        axis: Optional[str] = None,
        source: Optional[COOMatrix] = None,
    ):
        if sched is None and artifact is None:
            raise ValueError("a GustPlan needs a schedule or a packed artifact")
        self.config = config
        self.sched = sched
        self.cache = cache
        self.mesh = mesh
        self.axis = axis
        self._artifact = artifact
        self._source = source  # COO kept (when known) so tune() can sweep l
        self.tuning: Optional[TuneResult] = None
        # PlanStore attachment (plan(..., store=...)): write-behind fires
        # when a fresh plan first materializes its pack; loaded plans
        # carry the stored schedule summary instead of a schedule.
        self._store = None
        self._store_key: Optional[str] = None
        self._store_loaded = False
        self.summary: Optional[Dict] = None
        # Graceful-degradation counters (PR 10): downgrades applied on
        # *this plan's* execution path, surfaced as PlanCost.fallback_*.
        # Keys mirror resilience.fallback's stages.
        self._fallbacks: Dict[str, int] = {"kernel": 0, "gather": 0, "store": 0}
        # Incremental rescheduling (reschedule()): per-window content
        # fingerprints of the source, and the last delta's stats.
        self._window_hashes: Optional[np.ndarray] = None
        self.resched: Optional["RescheduleResult"] = None

    # -- identity ----------------------------------------------------------

    @property
    def shape(self) -> Tuple[int, int]:
        src = self.sched if self.sched is not None else self._artifact
        return src.shape

    @property
    def l(self) -> int:
        return self.config.l

    @property
    def layout(self) -> str:
        """Resolved layout (``auto`` is decided at pack time)."""
        if self._artifact is not None:
            return (
                "ragged" if isinstance(self._artifact, RaggedSchedule) else "padded"
            )
        if self.config.layout != "auto":
            return self.config.layout
        return resolve_layout(
            self.sched, self.config.c_blk, self.config.waste_threshold
        )

    @property
    def artifact(self) -> Union[PackedSchedule, RaggedSchedule]:
        """The packed execution layout; materialized lazily on first use.
        A fresh plan with an attached store persists the artifact here
        (write-behind) — schedule-only consumers that never pack never
        write either."""
        if self._artifact is None:
            faults.trip("pack.materialize")
            with Span("build.pack", sched_counters, "pack_s"):
                self._artifact = self._pack()
            self._store_put()
        return self._artifact

    def verify(self):
        """Run the static artifact verifier over the packed leaves and
        return the list of :class:`~repro.analysis.verify.Finding`
        violations (empty on a healthy artifact).  Packs a lazy plan;
        pure numpy, never executes a kernel."""
        from repro.analysis.verify import verify as _verify

        return _verify(self.artifact)

    def _store_put(self) -> None:
        """Best-effort write-behind of the packed artifact (plus tuning
        and a schedule summary for loaded-plan observability).  Never
        raises: persistence must not break execution."""
        if self._store is None or self._store_key is None or self._store_loaded:
            return
        try:
            summary = None
            if self.sched is not None:
                summary = {
                    "cycles": int(self.sched.cycles),
                    "nnz": int(self.sched.nnz),
                    "utilization": float(self.sched.hardware_utilization),
                }
            self._store.put(
                self._store_key,
                self.to_spec(),
                tuning=self.tuning.to_dict() if self.tuning else None,
                summary=summary,
            )
        except Exception:
            pass

    @property
    def gather_mode(self) -> str:
        """Resolved Buffer-Filler gather mode (``auto`` is decided from
        the packed artifact's measured ``S_blk / seg_count`` locality —
        reading this packs a lazy plan)."""
        if self.config.gather != "auto":
            return self.config.gather
        a = self.artifact
        return resolve_gather(a.s_blk, a.seg_count)

    def _pack(self):
        c = self.config
        layout = self.layout  # resolves "auto" from the measured waste
        if self.cache is not None:
            route = (
                self.cache.ragged_for if layout == "ragged" else self.cache.pack_for
            )
            return route(
                self.sched, c_blk=c.c_blk, value_dtype=c.value_jnp,
                index_dtype=c.index_jnp,
            )
        fn = pack_ragged if layout == "ragged" else pack_schedule
        return fn(
            self.sched, c.c_blk, value_dtype=c.value_jnp, index_dtype=c.index_jnp
        )

    def _use_kernel(self) -> bool:
        if self.config.backend == "pallas":
            return True
        if self.config.backend == "jnp":
            return False
        return bool(self.artifact.fusable and jax.default_backend() == "tpu")

    def _interpret(self) -> bool:
        from repro.kernels.gust_spmv import _resolve_interpret

        return _resolve_interpret(self.config.interpret)

    def _pipeline(self) -> str:
        """Resolved streaming mode: the jnp backend has no tile pipeline
        (``single``); on the kernel path ``auto`` means double-buffered."""
        if not self._use_kernel():
            return "single"
        return "double" if self.config.pipeline == "auto" else self.config.pipeline

    # -- execution ---------------------------------------------------------

    def spmm(self, x: jnp.ndarray, *, transpose_io: bool = False) -> jnp.ndarray:
        """Multi-vector execution: ``x (n, B) -> y (m, B)``.

        With ``transpose_io=True`` the batch dimension leads instead —
        ``x (B, n) -> y (B, m)`` — and both transposes happen *inside* the
        jitted executor, where XLA fuses them into the gather/scatter.
        Callers that are batch-major (``GustLinear``, most LM decode
        paths) previously paid two eagerly-materialized ``.T`` copies per
        call; this fast path removes that round-trip bit-identically.

        Execution failures degrade through the single fallback decision
        point (:func:`repro.resilience.resolve_fallback`, ROADMAP
        §Resilience invariants): a failing ``gather="local"`` path
        retries resident (bitwise-identical, PR 5), then a failing
        Pallas backend retries the jnp oracle (tolerance-identical).
        Every applied downgrade is counted on ``cost().fallback_*``; a
        failure at the floor of the chain propagates to the serve-step
        containment layer.
        """
        if self.mesh is not None:
            raise NotImplementedError(
                "sharded plans execute single vectors; use .spmv(v) "
                "(the §5.5 row-window split concatenates per-device outputs)"
            )
        try:
            return self._execute(
                x, transpose_io, self.config.gather, self._use_kernel()
            )
        except Exception as err:
            return self._degraded_spmm(x, transpose_io, err)

    def _execute(
        self, x, transpose_io: bool, gather: str, use_kernel: bool
    ) -> jnp.ndarray:
        from repro.kernels.ops import execute_spmm

        return execute_spmm(
            self.artifact,
            x,
            use_kernel=use_kernel,
            interpret=self._interpret(),
            c_blk=self.config.c_blk,
            transpose_io=transpose_io,
            gather=gather,
            pipeline=self.config.pipeline,
        )

    def _degraded_spmm(
        self, x, transpose_io: bool, err: BaseException
    ) -> jnp.ndarray:
        """Sanctioned containment site for :meth:`spmm` (lint GUST-L07
        allowlist): walk the fallback chain one step at a time, counting
        each applied downgrade, and re-raise the original error when the
        chain is exhausted."""
        gather = self.config.gather
        if gather == "auto":
            a = self._artifact  # spmm already materialized it, or packing
            if a is None:  # itself failed -> nothing to degrade to
                raise err
            gather = resolve_gather(a.s_blk, a.seg_count)
        use_kernel = self._use_kernel()

        degraded_gather = resolve_fallback("gather", gather)
        if degraded_gather is not None:
            try:
                y = self._execute(x, transpose_io, degraded_gather, use_kernel)
            except Exception:
                pass  # fall through to the kernel leg with gather degraded
            else:
                record_fallback("gather")
                self._fallbacks["gather"] += 1
                return y
            gather = degraded_gather

        if use_kernel and resolve_fallback("kernel", "pallas") == "jnp":
            y = self._execute(x, transpose_io, gather, False)
            record_fallback("kernel")
            self._fallbacks["kernel"] += 1
            if degraded_gather is not None:
                record_fallback("gather")
                self._fallbacks["gather"] += 1
            return y
        raise err

    def spmv(self, v: jnp.ndarray) -> jnp.ndarray:
        """Single-vector execution: ``v (n,) -> y (m,)``.  On a sharded
        plan (:meth:`shard`) this runs k parallel length-l GUSTs over
        contiguous window ranges, with no collective, and concatenates
        their rows on the mesh's first device."""
        v = jnp.asarray(v)
        m, n = self.shape
        if v.shape != (n,):
            raise ValueError(f"vector shape {v.shape} != ({n},)")
        if self.mesh is not None:
            return self._spmv_sharded(v)
        return self.spmm(v[:, None])[:, 0]

    def spgemm(
        self,
        other,
        *,
        backend: Optional[str] = None,
        interpret: Optional[bool] = None,
    ) -> COOMatrix:
        """Sparse×sparse ``C = A @ B`` through this plan's color-block
        stream (``other``: COOMatrix, dense array, or another plan built
        from its source matrix).  Returns a deduplicated row-sorted
        :class:`COOMatrix` that can itself be ``repro.plan()``-ed —
        chained ``A·A`` analytics (:mod:`repro.graph`) run on the result
        directly.  See :mod:`repro.core.spgemm` for the condensed-B
        outer-product organization and the bit-identity contract
        (ROADMAP §SpGEMM invariants)."""
        from .spgemm import spgemm as _spgemm

        if self.mesh is not None:
            raise NotImplementedError(
                "spgemm on a sharded plan is not supported; call it on "
                "the unsharded plan"
            )
        return _spgemm(self, other, backend=backend, interpret=interpret)

    def spgemm_cost(self, other) -> "SpgemmCost":
        """Predicted cost of ``self @ other`` — output-nnz estimate,
        scratch bytes, merge ops, streamed-FLOP reduction vs dense —
        without packing or executing (the dryrun/roofline entry point
        for SpGEMM).  See :class:`repro.core.spgemm.SpgemmCost`."""
        from .spgemm import spgemm_cost as _spgemm_cost

        return _spgemm_cost(self, other)

    # -- distributed execution (absorbs distributed_spmv) --------------------

    def shard(self, mesh, axis: Optional[str] = None) -> "GustPlan":
        """Return a plan that executes as ``mesh.shape[axis]`` parallel
        length-l GUSTs (paper §5.5: "the Edge-Coloring schedule would not
        need to change").  Devices get contiguous window ranges balanced
        by ragged-stream *block count* (not window count — equal-window
        splits leave most devices idle on skewed matrices).

        The device-major layout (host assembly + upload) is memoized in
        the plan's :class:`ScheduleCache` next to the pack, so repeated
        executions only run the shard_map.  Sharding requires the ragged
        stream; a padded plan re-packs ragged through the cache.
        """
        axis = axis if axis is not None else self.config.mesh_axis
        ragged_art = (
            self._artifact
            if isinstance(self._artifact, RaggedSchedule)
            else None
        )
        if ragged_art is None and self.sched is None:
            raise ValueError(
                "cannot shard a padded spec-plan: the ragged stream needs "
                "the schedule (build the plan with plan(...) or a ragged "
                "artifact)"
            )
        # artifact stays lazy (None unless already ragged): when the
        # device-major layout below is served from the cache, the ragged
        # pack is never even materialized on this host
        return GustPlan(
            dataclasses.replace(self.config, layout="ragged", mesh_axis=axis),
            sched=self.sched,
            artifact=ragged_art,
            cache=self.cache,
            mesh=mesh,
            axis=axis,
        )

    def _spmv_sharded(self, v: jnp.ndarray) -> jnp.ndarray:
        c = self.config
        n_dev = self.mesh.shape[self.axis]
        if self.cache is not None and self.sched is not None:
            # one memo entry per (schedule content, c_blk, dtypes, n_dev);
            # the build closure touches .artifact, so a memo hit skips the
            # ragged pack entirely
            layout = self.cache.memo(
                ("shard_layout", self.cache.schedule_key(self.sched),
                 c.c_blk, c.value_dtype, c.index_dtype, n_dev),
                lambda: _shard_layout(self.artifact, n_dev),
            )
        else:
            layout = _shard_layout(self.artifact, n_dev)
        m_d, r_d, c_d, lw_d, w_max, idx = layout
        fn = _shard_spmv_fn(self.mesh, self.axis, c.l, c.c_blk, w_max)
        # Reassemble on the mesh's first device: each other device's rows
        # are copied there once (the shard_map program runs no
        # collective, and an eager gather from a sharded operand has no
        # unambiguous output sharding on an explicit-axis mesh).  Device
        # d's first w_cnt[d]*l rows are its window range in order; the
        # gather below concatenates them, then undoes the load-balancing
        # row sort.
        y_dev = jax.device_put(
            fn(m_d, r_d, c_d, lw_d, v),
            jax.sharding.SingleDeviceSharding(self.mesh.devices.flat[0]),
        )
        m = self.shape[0]
        if self.sched is not None:
            y_sorted = y_dev.reshape(-1)[idx][:m]
            return jnp.zeros((m,), jnp.float32).at[
                jnp.asarray(self.sched.row_perm)
            ].set(y_sorted)
        a = self.artifact
        y_all = y_dev.reshape(-1)[idx]
        out = jnp.zeros((max(m, a.num_windows * a.l),), jnp.float32)
        return out.at[jnp.asarray(a.row_perm)].set(y_all)[:m]

    # -- multi-layer serving -------------------------------------------------

    @staticmethod
    def stack(plans: Sequence["GustPlan"]) -> Dict:
        """Stack the packed artifacts of ``plans`` (one per layer) along a
        leading reps axis for the serving layer-scan: layers are equalized
        to a uniform stream length first (``repad_to`` / ``repad_to_blocks``
        preserve the padding invariants and leaf dtypes).  Returns the
        ``{"leaves", "meta"}`` wire format consumed by
        ``serving.gust_serve.decode_step_gust`` and :meth:`from_spec`."""
        arts = [p.artifact if isinstance(p, GustPlan) else p for p in plans]
        if not arts:
            raise ValueError("stack() needs at least one plan")
        ragged = isinstance(arts[0], RaggedSchedule)
        if any(isinstance(a, RaggedSchedule) != ragged for a in arts):
            raise ValueError("cannot stack mixed padded/ragged layouts")
        quant = arts[0].quantized
        if any(a.quantized != quant for a in arts):
            # the scale_blk leaf exists only on quantized artifacts, so a
            # mixed stack has no common pytree structure
            raise ValueError(
                "cannot stack mixed quantized/unquantized layers: pack "
                "every layer with the same value_dtype"
            )
        if ragged:
            t_uniform = max(a.num_blocks for a in arts)
            arts = [a.repad_to_blocks(t_uniform) for a in arts]
        else:
            c_uniform = max(a.c_pad for a in arts)
            arts = [a.repad_to(c_uniform) for a in arts]
        # equalize the gather-table width too (seg_blk must stack), and
        # make the shared static flags conservative: one meta tuple
        # describes every layer's slice, so identity_perm/fusable hold
        # only if they hold for ALL layers
        s_uniform = max(a.s_blk for a in arts)
        arts = [a.repad_seg_to(s_uniform) for a in arts]
        ident = all(a.identity_perm for a in arts)
        fusable = all(a.fusable for a in arts)
        arts = [
            dataclasses.replace(a, identity_perm=ident, fusable=fusable)
            for a in arts
        ]
        if ragged:
            leaf_fn, meta = ragged_leaves, ragged_meta(arts[0])
        else:
            leaf_fn, meta = packed_leaves, packed_meta(arts[0])
        leaves = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[leaf_fn(a) for a in arts]
        )
        return {"leaves": leaves, "meta": meta}

    # -- serialization (the leaves/meta codec) -------------------------------

    def to_spec(self) -> Dict:
        """``{"leaves", "meta", "config"}`` — the one wire format (shared
        with serving stacks and dry-run specs).  ``leaves`` are the array
        (or ShapeDtypeStruct) pytree at their exact dtypes; ``meta`` +
        ``config`` are static and JSON-able."""
        a = self.artifact
        if isinstance(a, RaggedSchedule):
            leaves, meta = ragged_leaves(a), ragged_meta(a)
        else:
            leaves, meta = packed_leaves(a), packed_meta(a)
        return {"leaves": leaves, "meta": meta, "config": self.config.to_dict()}

    @classmethod
    def from_spec(
        cls,
        spec: Dict,
        *,
        config: Optional[PlanConfig] = None,
        cache: Optional[ScheduleCache] = None,
    ) -> "GustPlan":
        """Rebuild a plan from :meth:`to_spec` output (or one layer's slice
        of a :meth:`stack`).  The schedule itself is not serialized — a
        deserialized plan executes but cannot re-pack or shard."""
        meta = tuple(spec["meta"])
        if meta and meta[0] == "ragged":
            artifact = ragged_from_leaves(spec["leaves"], meta)
        else:
            artifact = packed_from_leaves(spec["leaves"], meta)
        if config is None:
            cfg_dict = spec.get("config")
            config = (
                PlanConfig.from_dict(cfg_dict) if cfg_dict else PlanConfig()
            )
        return cls.from_artifact(artifact, config=config, cache=cache)

    @classmethod
    def from_artifact(
        cls,
        artifact: Union[PackedSchedule, RaggedSchedule],
        *,
        config: Optional[PlanConfig] = None,
        backend: Optional[str] = None,
        interpret: Optional[bool] = None,
        c_blk: Optional[int] = None,
        cache: Optional[ScheduleCache] = None,
        sched: Optional[GustSchedule] = None,
    ) -> "GustPlan":
        """Wrap an already-packed layout in a plan (the route every legacy
        packed-entry shim takes).  Layout/geometry/dtypes are read off the
        artifact; ``backend``/``interpret``/``c_blk`` override the config."""
        if config is None:
            config = PlanConfig()
        ragged = isinstance(artifact, RaggedSchedule)
        config = dataclasses.replace(
            config,
            l=artifact.l,
            layout="ragged" if ragged else "padded",
            # ragged streams and quantized streams (scales aligned to the
            # pack-time blocks) execute at their pack-time c_blk only
            c_blk=artifact.c_blk if (ragged or artifact.quantized) else (
                c_blk if c_blk is not None else config.c_blk
            ),
            backend=backend if backend is not None else config.backend,
            interpret=interpret if interpret is not None else config.interpret,
            value_dtype=jnp.dtype(artifact.m_blk.dtype).name,
            index_dtype=jnp.dtype(artifact.col_blk.dtype).name,
        )
        return cls(config, sched=sched, artifact=artifact, cache=cache)

    @classmethod
    def spec_for(
        cls, m: int, n: int, config: PlanConfig, *, colors: float
    ) -> "GustPlan":
        """Shape-only plan (ShapeDtypeStruct leaves, no allocation) with
        the scheduled stream sized from a per-window color-count estimate
        — typically the Eq. 9 bound.  This is how the multi-pod dry-run
        lowers the GUST decode path without running the scheduler."""
        c = config
        layout = "padded" if c.layout == "auto" else c.layout
        cpb = max(-(-int(np.ceil(colors)) // c.c_blk), 1)
        if layout == "ragged":
            num_blocks = max(-(-m // c.l), 1) * cpb
            artifact = ragged_spec(
                m, n, c.l, num_blocks, c_blk=c.c_blk,
                value_dtype=c.value_jnp, index_dtype=c.index_jnp,
            )
        else:
            artifact = packed_spec(
                m, n, c.l, cpb * c.c_blk, c_blk=c.c_blk,
                value_dtype=c.value_jnp, index_dtype=c.index_jnp,
            )
        return cls(
            dataclasses.replace(c, layout=layout), artifact=artifact
        )

    # -- measured autotuning -------------------------------------------------

    def tune(
        self,
        x_probe: jnp.ndarray,
        *,
        c_blks: Optional[Sequence[int]] = None,
        ls: Optional[Sequence[int]] = None,
        layouts: Sequence[str] = ("padded", "ragged"),
        gathers: Sequence[str] = ("resident", "local"),
        iters: int = 3,
        warmup: int = 1,
        min_improvement: Optional[float] = None,
        prune_ratio: float = 4.0,
    ) -> "GustPlan":
        """Measure ``(c_blk, l, layout, gather)`` candidates against
        ``x_probe`` and return a plan pinned to the winner.

        This is the plan-time analogue of FFTW's ``MEASURE`` mode: the
        sweep prices each candidate with :class:`PlanCost` first (anything
        predicted to stream more than ``prune_ratio`` × the best
        candidate's bytes is pruned untimed), times the surviving jitted
        executors (best-of-``iters`` after ``warmup`` untimed calls), and
        feeds the measurements through the one tuning decision point,
        :func:`~repro.core.packing.resolve_tuning` — the fastest candidate
        wins unless it fails to beat the static
        ``resolve_layout``/``resolve_gather`` baseline by the margin, in
        which case the baseline stands.  The returned plan carries the
        full :class:`TuneResult` on ``.tuning``; its config spells every
        swept knob explicitly (no ``auto``), so ``to_spec()`` round-trips
        the tuned choice.

        The winning choice is memoized content-keyed in the plan's
        :class:`~repro.core.packing.ScheduleCache`, so re-tuning the same
        matrix/probe reuses the recorded sweep instead of re-timing.

        ``ls`` defaults to the plan's own ``l`` (plus ``l/2`` when the
        plan still holds its source matrix — sweeping ``l`` means
        re-scheduling, which only :func:`plan`-built plans can do).
        """
        import time

        if self.sched is None:
            raise ValueError(
                "tune() needs the schedule; deserialized/spec plans carry "
                "only the packed artifact"
            )
        if self.mesh is not None:
            raise NotImplementedError("tune a plan before sharding it")
        x_probe = jnp.asarray(x_probe)
        if x_probe.ndim == 1:
            x_probe = x_probe[:, None]
        c = self.config
        if c_blks is None:
            c_blks = tuple(sorted({4, c.c_blk, 2 * c.c_blk}))
        if ls is None:
            ls = (
                tuple(sorted({c.l, max(c.l // 2, 1)}, reverse=True))
                if self._source is not None
                else (c.l,)
            )
        baseline = (c.c_blk, c.l, self.layout, self.gather_mode)

        def build(key: Tuple[int, int, str, str]) -> "GustPlan":
            cb, l, layout, gather = key
            cfg = dataclasses.replace(
                c, c_blk=cb, l=l, layout=layout, gather=gather
            )
            if l == c.l:
                return GustPlan(
                    cfg, sched=self.sched, cache=self.cache,
                    source=self._source,
                )
            return plan(self._source, cfg, cache=self.cache)

        candidates = {baseline}
        for cb in c_blks:
            for l in ls:
                if l != c.l and self._source is None:
                    continue
                for layout in layouts:
                    for gather in gathers:
                        candidates.add((int(cb), int(l), layout, gather))
        candidates = sorted(candidates)

        def sweep():
            predicted, plans = {}, {}
            for key in candidates:
                p = build(key)
                plans[key] = p
                predicted[key] = int(p.cost().stream_bytes)
            floor = min(predicted.values())
            pruned = tuple(
                k for k in candidates
                if k != baseline and predicted[k] > prune_ratio * floor
            )
            measurements = {}
            for key in candidates:
                if key in pruned:
                    continue
                run = plans[key].spmm
                for _ in range(max(warmup, 1)):
                    jax.block_until_ready(run(x_probe))
                best = float("inf")
                for _ in range(max(iters, 1)):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(x_probe))
                    best = min(best, time.perf_counter() - t0)
                measurements[key] = best
            choice = resolve_tuning(
                measurements, baseline, min_improvement=min_improvement
            )
            return TuneResult(
                choice=choice,
                baseline=baseline,
                measurements=measurements,
                predicted_bytes=predicted,
                improvement=measurements[baseline] / measurements[choice],
                cost_consistent=predicted[choice] <= predicted[baseline],
                pruned=pruned,
            )

        if self.cache is not None:
            memo_key = (
                "tune", self.cache.schedule_key(self.sched),
                tuple(candidates), tuple(x_probe.shape), str(x_probe.dtype),
                c.value_dtype, c.index_dtype, c.backend, self._interpret(),
                iters, warmup, min_improvement, prune_ratio,
            )
            result = self.cache.memo(memo_key, sweep)
        else:
            result = sweep()
        tuned = build(result.choice)
        tuned.tuning = result
        if self._store is not None and self._source is not None:
            # persist the tuned winner under the *tuned* config's key, so
            # a warm start revives both the artifact and the TuneResult
            tuned._store = self._store
            tuned._store_key = self._store.key(
                ScheduleCache.matrix_key(self._source), tuned.config
            )
        return tuned

    # -- cost ----------------------------------------------------------------

    def cost(self) -> PlanCost:
        """Measured schedule cost + Eq. 9-11 predictions for this plan."""
        from .bounds import (
            expected_colors_bound,
            expected_execution_cycles,
            expected_utilization,
        )
        from repro.kernels.gust_spmv import (
            _resident_walk_steps,
            _route_lane_gathers,
        )

        if self.sched is None:
            raise ValueError(
                "cost() needs the schedule; deserialized/spec plans carry "
                "only the packed artifact"
            )
        m, n = self.shape
        density = self.sched.nnz / float(m * n) if m and n else 0.0
        a = self.artifact
        streamed = (
            a.streamed_slots
            if isinstance(a, RaggedSchedule)
            else int(np.prod(a.m_blk.shape))
        )
        return PlanCost(
            cycles=self.sched.cycles,
            utilization=self.sched.hardware_utilization,
            waste_ratio=ragged_waste_ratio(self.sched, self.config.c_blk),
            layout=self.layout,
            streamed_slots=streamed,
            stream_bytes=a.stream_bytes,
            density=density,
            expected_colors=float(expected_colors_bound(n, density, self.l)),
            expected_cycles=float(expected_execution_cycles(n, density, self.l)),
            expected_utilization=float(expected_utilization(n, density, self.l)),
            gather=self.gather_mode,
            s_blk=a.s_blk,
            locality_ratio=a.s_blk / max(a.seg_count, 1),
            gather_flops_resident=4 * streamed * a.seg_count,
            gather_flops_local=4 * streamed * a.s_blk,
            gather_walk_steps=_resident_walk_steps(a.seg_count),
            route_lane_gathers=_route_lane_gathers(self.config.c_blk,
                                                   self.l),
            x_vmem_bytes_resident=a.seg_count * self.l * 4,
            x_vmem_bytes_local=a.s_blk * self.l * 4,
            backend="pallas" if self._use_kernel() else "jnp",
            pipeline=self._pipeline(),
            store_hits=self._store.hits if self._store is not None else 0,
            store_misses=self._store.misses if self._store is not None else 0,
            fallback_kernel=self._fallbacks["kernel"],
            fallback_gather=self._fallbacks["gather"],
            fallback_store=self._fallbacks["store"],
            **{
                f"cache_{k}": v
                for k, v in (
                    self.cache.stats() if self.cache is not None else {}
                ).items()
            },
        )

    def __repr__(self) -> str:
        m, n = self.shape
        packed = "lazy" if self._artifact is None else self.layout
        shard = f", sharded[{self.axis}]" if self.mesh is not None else ""
        return (
            f"GustPlan({m}x{n}, l={self.l}, layout={self.config.layout}"
            f"->{packed}, backend={self.config.backend}{shard})"
        )


# ---------------------------------------------------------------------------
# Distributed execution internals (owned by GustPlan.shard; formerly
# hand-rolled by core.spmv.distributed_spmv).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _shard_spmv_fn(mesh, axis: str, l: int, c_blk: int, w_max: int):
    """Jitted shard_map program for one (mesh, geometry) — memoized so
    repeated sharded executions reuse jax's trace/compile cache instead of
    paying a fresh closure trace every call."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.collectives import shard_map

    def local(m_blk, r_blk, c_blk_, lw, vec):
        # (1, B_max*cb, l) stream + (1, B_max) local window ids ->
        # per-window segment sum -> (1, W_max * l)
        p = m_blk[0].astype(jnp.float32) * jnp.take(
            vec, c_blk_[0], axis=0, mode="clip"
        )
        window = jnp.repeat(lw[0], c_blk)
        adder = window[:, None] * l + r_blk[0]
        return jax.ops.segment_sum(
            p.reshape(-1), adder.reshape(-1), num_segments=w_max * l
        )[None]

    spec_in = P(axis)  # shard the leading device dim
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(spec_in, spec_in, spec_in, spec_in, P()),
            out_specs=spec_in,
        )
    )


def _shard_layout(ragged: RaggedSchedule, n_dev: int):
    """Device-major execution layout of a ragged stream for ``n_dev``
    devices: contiguous window ranges balanced by block count, each
    device's blocks padded to the common max.

    Returns ``(m_d, r_d, c_d, lw_d, w_max, idx)`` — the four ``(n_dev,
    ...)`` device arrays for the shard_map, the padded per-device window
    count, and the gather index reassembling the per-device outputs into
    scheduled row order.  Everything here is a pure function of (ragged
    stream, n_dev); :meth:`GustPlan.shard` memoizes it in the
    :class:`ScheduleCache` so repeated executions skip both the host
    assembly and the host->device upload."""
    l, W, cb, t_blk = ragged.l, ragged.num_windows, ragged.c_blk, ragged.num_blocks
    block_starts = np.asarray(ragged.block_starts, np.int64)
    block_window = np.asarray(ragged.block_window, np.int64)

    # Contiguous window boundaries hitting equal block-count targets:
    # device d owns windows [w_bound[d], w_bound[d+1]).
    targets = (np.arange(1, n_dev) * t_blk) // n_dev
    w_bound = np.concatenate(
        [[0], np.searchsorted(block_starts, targets, side="left"), [W]]
    )
    w_bound = np.maximum.accumulate(np.minimum(w_bound, W))
    w_cnt = np.diff(w_bound)
    b_cnt = block_starts[w_bound[1:]] - block_starts[w_bound[:-1]]
    b_max = max(int(b_cnt.max()) if n_dev else 1, 1)
    w_max = max(int(w_cnt.max()) if n_dev else 1, 1)

    # Device-major padded streams; padding blocks keep the packed-format
    # invariants (values 0, columns gather the slot's lane, rows 0) and
    # route to local window 0 — value 0 contributes nothing.
    lane = np.arange(l, dtype=np.int32)
    m_d = np.zeros((n_dev, b_max * cb, l), np.float32)
    r_d = np.zeros((n_dev, b_max * cb, l), np.int32)
    c_d = np.broadcast_to(lane, (n_dev, b_max * cb, l)).copy()
    lw_d = np.zeros((n_dev, b_max), np.int32)
    m_src = np.asarray(ragged.m_blk, np.float32)
    r_src = np.asarray(ragged.row_blk, np.int32)
    c_src = np.asarray(ragged.col_blk, np.int32)
    for d in range(n_dev):
        g0, g1 = int(block_starts[w_bound[d]]), int(block_starts[w_bound[d + 1]])
        rows = (g1 - g0) * cb
        m_d[d, :rows] = m_src[g0 * cb: g1 * cb]
        r_d[d, :rows] = r_src[g0 * cb: g1 * cb]
        c_d[d, :rows] = c_src[g0 * cb: g1 * cb]
        lw_d[d, : g1 - g0] = block_window[g0:g1] - w_bound[d]

    idx = np.concatenate(
        [d * w_max * l + np.arange(w_cnt[d] * l) for d in range(n_dev)]
    ) if W else np.zeros(0, np.int64)
    return (
        jnp.asarray(m_d), jnp.asarray(r_d), jnp.asarray(c_d),
        jnp.asarray(lw_d), w_max, jnp.asarray(idx),
    )


# ---------------------------------------------------------------------------
# Incremental re-planning for drifting sparsity (prune masks, dynamic
# patterns): diff per-window content, recolor only dirty windows, splice
# their packed blocks into the existing stream.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RescheduleResult:
    """What one :func:`reschedule` delta did.

    ``full_fallback`` means the plan was rebuilt from scratch (load-
    balanced config, or no prior fingerprints/source to diff against);
    ``spliced`` means the packed ragged stream was updated in place via
    :func:`~repro.core.packing.splice_ragged_blocks` instead of a full
    repack."""

    windows: int
    dirty_windows: int
    reused_windows: int
    recolored_edges: int
    full_fallback: bool
    spliced: bool

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


def reschedule(
    base: GustPlan,
    matrix: Union[np.ndarray, COOMatrix],
    *,
    workers: Optional[int] = None,
    store=None,
) -> GustPlan:
    """Re-plan ``matrix`` incrementally against ``base`` (a plan over the
    previous version of the same matrix).

    Per-window content fingerprints are diffed; only dirty windows are
    recolored (through the same chunked colorer), and — when ``base`` has
    a materialized ragged artifact — only their packed blocks are
    rebuilt, with every clean window's blocks copied bitwise.  The result
    is **bit-identical** to ``plan(matrix, base.config)`` built fresh.

    Incremental reuse requires ``load_balance=False`` (row balancing is a
    global function of the matrix content, so any delta may reassign
    every window); load-balanced configs transparently fall back to a
    full fresh plan, reported via ``.resched.full_fallback``.  Shape
    changes are an error — build a fresh plan.

    The returned plan carries updated fingerprints, so chaining
    ``reschedule(p1, m2)`` → ``reschedule(p2, m3)`` never re-hashes the
    old side.  ``.resched`` holds the delta stats
    (:class:`RescheduleResult`); dirty/reused window totals also
    accumulate in :data:`repro.core.scheduler.sched_counters`."""
    from .scheduler import incremental_schedule, sched_counters

    if not isinstance(base, GustPlan):
        raise TypeError(f"reschedule() needs a GustPlan, got {type(base).__name__}")
    if base.sched is None:
        raise ValueError(
            "reschedule() needs the base plan's schedule; store-loaded/"
            "spec plans carry only the packed artifact — build fresh"
        )
    if isinstance(matrix, (np.ndarray, jax.Array)):
        dense = np.asarray(matrix)
        if dense.ndim != 2:
            raise ValueError(f"dense matrix must be 2-D, got shape {dense.shape}")
        matrix = coo_from_dense(dense)
    if not isinstance(matrix, COOMatrix):
        raise TypeError(
            f"reschedule() takes a dense array or COOMatrix, got "
            f"{type(matrix).__name__}"
        )
    if tuple(matrix.shape) != tuple(base.shape):
        raise ValueError(
            f"reschedule() cannot change the matrix shape "
            f"({tuple(base.shape)} -> {tuple(matrix.shape)}); build a fresh plan"
        )

    cfg = base.config
    W = base.sched.num_windows
    can_diff = base._window_hashes is not None or base._source is not None
    if cfg.load_balance or not can_diff:
        p = plan(matrix, cfg, cache=base.cache, store=store, workers=workers)
        p.resched = RescheduleResult(
            windows=W, dirty_windows=W, reused_windows=0,
            recolored_edges=p.sched.nnz if p.sched is not None else 0,
            full_fallback=True, spliced=False,
        )
        return p

    edges_before = sched_counters["colored_edges"]
    new_sched, dirty, new_hashes = incremental_schedule(
        base.sched,
        matrix,
        old_coo=base._source,
        old_hashes=base._window_hashes,
        method=cfg.colorer,
        workers=workers,
    )
    recolored_edges = sched_counters["colored_edges"] - edges_before

    p = GustPlan(cfg, sched=new_sched, cache=base.cache, source=matrix)
    p._window_hashes = new_hashes
    spliced = False
    if (
        isinstance(base._artifact, RaggedSchedule)
        and p.layout == "ragged"
    ):
        p._artifact = splice_ragged_blocks(
            base._artifact, new_sched, dirty,
            value_dtype=cfg.value_jnp, index_dtype=cfg.index_jnp,
        )
        spliced = True
    if store is not None:
        p._store = store
        p._store_key = store.key(ScheduleCache.matrix_key(matrix), cfg)
        if spliced:
            p._store_put()  # artifact already materialized: write now
    p.resched = RescheduleResult(
        windows=W,
        dirty_windows=int(dirty.size),
        reused_windows=W - int(dirty.size),
        recolored_edges=int(recolored_edges),
        full_fallback=False,
        spliced=spliced,
    )
    return p
