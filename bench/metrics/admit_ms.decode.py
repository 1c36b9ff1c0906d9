"""admit_ms.decode: host time per admission: the window's change of the
``serve.admit`` span's seconds (ServeLoop.stats ``serve.admit_s``: batch-1
prefill, slot insert, first token with its host sync) over its
prefills, in ms."""

from lib import scopes


def read(rec):
    return scopes.per_ms(rec, (rec.get("stats") or {}).get("serve.admit_s"),
                         "prefills")
