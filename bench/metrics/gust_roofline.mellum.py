"""gust_roofline.mellum: least time of the traced window's routed expert
products (each held expert's three matrices at the tokens routed to it,
ServeLoop.stats ``moe.expert_tokens`` and ``moe.expert_steps``;
lib/work_moe) over the GUST kernels' summed device time, in %.  The
kernels are the custom calls named by a GUST kernel family (``gust_``):
the prefill's grouped expert matmuls are custom calls too."""

from lib import records, work_moe


def _gust_seconds(trace: dict) -> float:
    return sum(op["seconds"] for name, op in trace.get("ops", {}).items()
               if not op.get("parent") and name.lstrip("%").startswith("gust_")
               and records.GUST_KERNEL.search(
                   " ".join([name] + list(op["stats"].values()))))


def read(rec):
    tr = rec.get("trace")
    st = rec.get("stats") or {}
    if rec.get("kind") != "serving" or not tr or "moe.expert_tokens" not in st:
        return None
    least = work_moe.routed_least_seconds(
        rec["config"], rec["expert_nnz"], st["moe.expert_tokens"],
        st["moe.expert_steps"], rec["peak"])
    t = _gust_seconds(tr)
    return 100.0 * least / t if t > 0 else None
