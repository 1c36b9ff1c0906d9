"""decode_mfu: operations the tokens served in the window required (MLP
at its nonzeros; lib/work) over the window seconds and the bf16 peak, in %."""

from lib import records


def read(rec):
    if rec.get("kind") != "serving":
        return None
    flops = records.served_flops(rec)
    if flops <= 0:
        return None
    return 100.0 * flops / rec["window"]["seconds"] / rec["peak"]["bf16_flops_per_s"]
