"""prefill_ms.decode: device time per admission's prefill: the summed
device seconds of the traced window's operations under the program's
``prefill`` named scope over the window's prefills, in ms."""

from lib import scopes


def read(rec):
    return scopes.per_ms(rec, scopes.scope_seconds(rec.get("trace"),
                                                   "prefill"), "prefills")
