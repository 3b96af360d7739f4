"""Share of the chip's int8 peak that the verification's needed work
fills while the device is busy: the textbook batch-verification work of
the traced calls' sets (harness/work.py: one verdict per call, one
hash-to-G2 per distinct message of a call) over the device busy time
of those calls times the peak. The work is a floor, so a low share is the finding."""

from harness import work


def read(facts):
    t = facts.get("trace")
    if not t or not t.get("busy_s") or not facts.get("traced_sets"):
        return None
    ops = work.int8_ops(facts["traced_sets"], facts["traced_calls"],
                        facts["traced_distinct_messages"])
    return 100.0 * ops / (t["busy_s"] * facts["peaks"]["int8_ops_per_s"])
