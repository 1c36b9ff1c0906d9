"""What the per-layer metrics read of the program's own names: the device
time of the operations under a named scope, and the host seconds of the
serve loop's spans (``ServeLoop.stats``).

``jax.named_scope`` reaches each device operation's op name, which a TPU
profile holds in the ``SCOPE_STAT`` statistic of the operation's event
metadata.  ``lib/trace.load`` keeps only the events' own statistics
(``ProfileData`` shows no metadata), so on a chip trace no operation has
it yet and ``scope_seconds`` finds nothing there."""

from __future__ import annotations

import re

#: the statistic of a device operation that holds its op name, the path
#: of named scopes it was traced under
SCOPE_STAT = "tf_op"


def scope_seconds(trace: dict, scope: str):
    """Summed device seconds of the window's operations under ``scope`` (a
    path component of their op name), enclosing operations left out;
    None when no operation is under it."""
    pat = re.compile(rf"(^|/){re.escape(scope)}/")
    total, found = 0.0, False
    for op in (trace or {}).get("ops", {}).values():
        if op.get("parent"):
            continue
        if pat.search(op["stats"].get(SCOPE_STAT, "")):
            total += op["seconds"]
            found = True
    return total if found else None


def per_ms(rec: dict, seconds, count_key: str):
    """``seconds`` over the window's change of ``stats[count_key]``, in
    ms; None without either."""
    n = (rec.get("stats") or {}).get(count_key)
    if rec.get("kind") != "serving" or seconds is None or not n:
        return None
    return seconds / n * 1e3
