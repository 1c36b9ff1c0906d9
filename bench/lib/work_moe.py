"""Work counts of the routed expert products of a served MoE model.

Counted from each held expert matrix's nonzeros and shape and from the
routing the window saw, never from a packed stream (``lib/work``): per
expert layer, held expert and matrix, with T the tokens routed to that
expert and S the decode steps in which it got at least one, the routed
work is 2 * nnz * T operations and at least 4 * nnz * S + 4 * (m + n) * T
bytes (each value read once a step the expert is used, and each routed
token's input and output once).  Columns the program computes for tokens
not routed to an expert are not work: the share of the roofline says
how much of the kernels' time the routed work needs.
"""

from __future__ import annotations

from . import work


def expert_dims(cfg: dict) -> dict:
    """(m, n) of each expert matrix as a GUST product y = M x."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"w_gate": (f, d), "w_up": (f, d), "w_down": (d, f)}


def routed_least_seconds(cfg: dict, nnz: dict, tokens, steps,
                         peak: dict) -> float:
    """Least chip time of the routed expert products: ``nnz[matrix]``,
    ``tokens`` and ``steps`` are [layer][held expert] lists."""
    total = 0.0
    for name, (m, n) in expert_dims(cfg).items():
        for nz_l, t_l, s_l in zip(nnz[name], tokens, steps):
            for nz, t, s in zip(nz_l, t_l, s_l):
                flops = 2 * nz * t
                nbytes = work.F32 * nz * s + work.F32 * (m + n) * t
                total += work.least_time(flops, nbytes, peak)["seconds"]
    return total
