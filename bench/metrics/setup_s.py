"""setup_s: process start to the start of the window (host clock)."""


def read(rec):
    return rec.get("setup_s")
