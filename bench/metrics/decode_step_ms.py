"""decode_step_ms: window seconds over the decode steps taken in it."""


def read(rec):
    st = rec.get("stats") or {}
    if not st.get("decode_steps"):
        return None
    return rec["window"]["seconds"] / st["decode_steps"] * 1e3
