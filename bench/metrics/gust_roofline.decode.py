"""gust_roofline.decode: least time of the GUST MLP products of the
traced window (each layer's three matrices per decode step, at the
active rows; lib/work) over the GUST kernels' summed device time, in %."""

from lib import records, work


def read(rec):
    tr = rec.get("trace")
    if rec.get("kind") != "serving" or not tr:
        return None
    cfg = rec["config"]
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    dims = {"w_gate": (f, d), "w_up": (f, d), "w_down": (d, f)}
    st = rec["stats"]
    steps = st["decode_steps"]
    rows = st["active_slot_steps"] / steps if steps else 0
    least = 0.0
    for name, per_layer in rec["mlp_nnz"].items():
        m, n = dims[name]
        for nnz in per_layer:
            w = work.gust_product(m, n, nnz, rows)
            least += work.least_time(w["flops"], w["bytes"], rec["peak"])["seconds"]
    return records.roofline_share(least * steps, tr)
