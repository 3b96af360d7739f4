"""Share of the chips' HBM bandwidth that the slasher engine's plane
program fills while the devices are busy: the bytes the traced batch
has to move (``harness/plane_bytes.py``: each distinct validator row of
the batch gathered and scattered once, in all three planes) over the
busy time per chip (profiler trace) times the chips times the peak
bytes per second of one."""

from harness import plane_bytes


def read(facts):
    t = facts.get("trace")
    if not t or not t.get("busy_s") or not facts.get("traced_rows"):
        return None
    moved = plane_bytes.plane_bytes(facts["traced_rows"],
                                    facts["history_length"])
    return 100.0 * moved / (t["busy_s"] * facts["chips"]
                            * facts["peaks"]["hbm_bytes_per_s"])
