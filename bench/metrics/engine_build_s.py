"""engine_build_s: host clock around the ServeLoop construction (prune,
color, pack, stack, upload)."""


def read(rec):
    return rec.get("engine_build_s")
