"""Device busy microseconds (the union of device-op intervals in the
profiler trace of the traced batch) per vote of that batch: the
slasher engine's per-chunk plane programs."""


def read(facts):
    t = facts.get("trace")
    if not t or not t.get("busy_s") or not facts.get("traced_votes"):
        return None
    return t["busy_s"] * 1e6 / facts["traced_votes"]
