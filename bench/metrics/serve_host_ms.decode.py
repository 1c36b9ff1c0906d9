"""serve_host_ms.decode: the serve loop's own host time per decode step,
with no decode queued on the device: the window's change of the spans'
``serve.step_s`` less ``serve.wait_s`` and ``serve.admit_s``
(ServeLoop.stats) over its decode steps, in ms."""

from lib import scopes


def read(rec):
    st = rec.get("stats") or {}
    if "serve.step_s" not in st:
        return None
    host = (st["serve.step_s"] - st.get("serve.wait_s", 0.0)
            - st.get("serve.admit_s", 0.0))
    return scopes.per_ms(rec, host, "decode_steps")
