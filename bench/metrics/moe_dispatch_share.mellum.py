"""moe_dispatch_share.mellum: the window's routed (token, held expert)
pairs over the expert columns the decode computed (ServeLoop.stats
``moe.routed_pairs`` / ``moe.computed_cols``, window deltas of the
program's device-side counters); about 1/8 while every held expert runs
on all 64 columns of the batch."""


def read(rec):
    st = rec.get("stats") or {}
    if not st.get("moe.computed_cols"):
        return None
    return st["moe.routed_pairs"] / st["moe.computed_cols"]
