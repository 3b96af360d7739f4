"""Device busy milliseconds (the union of device-op intervals in the
profiler trace of the traced calls) per 1,000 sets those calls
verified."""


def read(facts):
    t = facts.get("trace")
    if not t or not t.get("busy_s") or not facts.get("traced_sets"):
        return None
    return t["busy_s"] * 1e6 / facts["traced_sets"]
