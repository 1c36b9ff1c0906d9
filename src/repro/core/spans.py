"""Program spans: named stretches of host time on the profiler's clock.

A :class:`Span` is a ``jax.profiler.TraceAnnotation``, so under
``jax.profiler`` it lands in the trace beside the device operations it
waited on or dispatched; with the profiler off it costs about a
microsecond.  Each span also adds its seconds to a counter dict that its
owner already exposes (``ServeLoop.stats``, ``sched_counters``,
``gustify``'s ``stats["build_s"]``), so the totals are readable without
a trace.  The span names in use:

- ``serve.step`` (a step annotation carrying the step number) and, inside
  it, ``serve.admit`` (attributes ``rid``, ``prompt_len``; children
  ``serve.prefill``, ``serve.insert``, ``serve.first_token``),
  ``serve.decode``, ``serve.wait`` and ``serve.retire``
  (``serving/serve_loop.py``; totals in ``ServeLoop.stats`` as
  ``serve.step_s``, ``serve.admit_s``, ...);
- ``build.colour`` and ``build.pack`` (``core/plan.py``; totals in
  ``sched_counters`` as ``colour_s`` and ``pack_s``), and ``build.prune``,
  ``build.stack`` and ``build.upload`` (``serving/gust_serve.gustify``).
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax

__all__ = ["Span"]


class Span:
    """``with Span(name, counters, key, **attrs):`` annotates the block as
    ``name`` (``attrs`` ride on the annotation) and adds its seconds to
    ``counters[key]`` (default ``name + "_s"``).  ``step`` makes it a
    ``StepTraceAnnotation`` carrying that step number."""

    __slots__ = ("_ann", "_counters", "_key", "_t0")

    def __init__(self, name: str, counters: Optional[Dict] = None,
                 key: Optional[str] = None, *, step: Optional[int] = None,
                 **attrs):
        if step is None:
            self._ann = jax.profiler.TraceAnnotation(name, **attrs)
        else:
            self._ann = jax.profiler.StepTraceAnnotation(
                name, step_num=step, **attrs)
        self._counters = counters
        self._key = key or name + "_s"

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._counters is not None:
            self._counters[self._key] = self._counters.get(self._key, 0) + dt
        self._ann.__exit__(*exc)
