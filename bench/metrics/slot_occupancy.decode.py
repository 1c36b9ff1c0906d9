"""slot_occupancy.decode: live slot-steps over slot-steps, from the
window deltas of ServeLoop.stats."""


def read(rec):
    st = rec.get("stats") or {}
    if not st.get("decode_steps"):
        return None
    return st["active_slot_steps"] / (st["decode_steps"] * rec["batch"])
