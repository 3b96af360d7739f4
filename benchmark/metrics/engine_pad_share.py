"""Share of the vote slots the slasher engine's plane scans step through
that are padding: 1 minus the votes over the slots scanned, from the
``votes`` and ``slots`` of the ``slasher/batch`` spans of the window's
last three batches (``harness/engine_spans.py``)."""

from harness import engine_spans


def read(facts):
    s = engine_spans.summary(facts)
    if not s or not s["slots"]:
        return None
    return 100.0 * (1.0 - s["votes"] / s["slots"])
