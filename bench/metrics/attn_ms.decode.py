"""attn_ms.decode: device time of decode attention (its KV update
included) per decode step: the summed device seconds of the traced
window's operations under the program's ``attn`` named scope over the
window's decode steps, in ms."""

from lib import scopes


def read(rec):
    return scopes.per_ms(rec, scopes.scope_seconds(rec.get("trace"), "attn"),
                         "decode_steps")
