"""spmv_ms: window seconds over the SpMV calls completed in it."""


def read(rec):
    if rec.get("kind") != "library" or not rec.get("calls"):
        return None
    return rec["window"]["seconds"] / rec["calls"] * 1e3
