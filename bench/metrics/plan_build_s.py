"""plan_build_s: host clock around repro.plan(...) until the packed plan
is on the device."""


def read(rec):
    return rec.get("plan_build_s")
