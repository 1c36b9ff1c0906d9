"""gust_roofline.spmv: least time of the traced window's SpMV calls
(lib/work, from the matrix's nonzeros and dimensions) over the GUST
kernels' summed device time, in %."""

from lib import records, work


def read(rec):
    tr = rec.get("trace")
    if rec.get("kind") != "library" or not tr:
        return None
    m, n = rec["shape"]
    w = work.gust_product(m, n, rec["nnz"], rec["batch"])
    least = work.least_time(w["flops"], w["bytes"], rec["peak"])["seconds"]
    return records.roofline_share(least * rec["calls"], tr)
