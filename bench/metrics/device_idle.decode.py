"""device_idle.decode: 1 - union of device op intervals over the traced
window, in %."""

from lib import records


def read(rec):
    if rec.get("kind") != "serving":
        return None
    return records.idle_share(rec.get("trace"))
