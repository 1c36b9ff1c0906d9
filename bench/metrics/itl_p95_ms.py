"""itl_p95_ms: 95th percentile of the gaps between consecutive output
tokens of every request, both ends in the window, host clock at the
return of the step that delivered each token."""

import numpy as np

from lib import records


def read(rec):
    if rec.get("kind") != "serving":
        return None
    gaps = records.token_gaps(rec)
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
