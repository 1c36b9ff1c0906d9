"""``power_iteration``: repeated products x <- A x / ||A x|| on one plan
with ``batch`` vectors, starting from a standard normal x0 of the
matrix's width."""

import numpy as np

from lib import traffic


def generate(mix: dict, seed: int, width: int) -> dict:
    rng = traffic.rng_for(seed, 1)
    batch = int(mix["batch"])
    x0 = rng.standard_normal((width, batch)).astype(np.float32)
    return {"x0": x0 if batch > 1 else x0[:, 0]}
