"""The one traffic generator: reads a mix's parameter file and a seed.

A mix is a data file under ``bench/traffic/``; its ``kind`` names the
shape of the work, a module ``bench/lib/kinds/<kind>.py`` with
``generate(mix, seed, size)``, and the mix's other keys give its sizes.
The same seed gives the same work.  Every seed gives the same set of
sizes in another order, so seeds change the order of the work and not
its amount.

What a kind returns depends on the driver that reads it:

- served requests: ``{"streams": [[(prompt, max_new), ...] per client]}``
  for closed-loop callers, each of which sends its next request when the
  last one finishes, or ``{"arrivals": [(due_s, prompt, max_new), ...]}``
  for requests sent at fixed times after the window opens (open loop);
- a sparse library: ``{"x0": start vector or block}``.
"""

from __future__ import annotations

import math
import os

import numpy as np

KINDS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kinds")


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for one purpose (``stream``) of one seed.  Any whole
    seed works, also above 2**32."""
    return np.random.default_rng([stream, int(seed) % (1 << 64)])


def strata(dist: dict, k: int) -> list:
    """The midpoints of ``k`` equal-probability strata of the
    log-uniform range [``min``, ``max``], rounded to whole tokens."""
    lo, hi = dist["min"], dist["max"]
    if dist.get("dist", "log_uniform") != "log_uniform":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [int(round(math.exp(math.log(lo) + (i + 0.5) / k
                               * math.log(hi / lo)))) for i in range(k)]


def kind_module(kind: str, kinds_dir: str = KINDS):
    from .harness import RunFailure, load_module

    path = os.path.join(kinds_dir, kind + ".py")
    if not os.path.isfile(path):
        raise RunFailure(f"no traffic kind bench/lib/kinds/{kind}.py")
    return load_module(path, "kind_" + kind)


def generate(mix: dict, seed: int, size: int, kinds_dir: str = KINDS):
    """The work of ``mix`` for ``seed``; ``size`` is the vocabulary of a
    served model or the width of a matrix."""
    return kind_module(mix["kind"], kinds_dir).generate(mix, seed, size)
