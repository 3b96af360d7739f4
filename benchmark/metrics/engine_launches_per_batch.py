"""Device programs the slasher engine launches per batch: the
``slasher/launch`` spans over the ``slasher/batch`` spans of the
window's last three batches (``harness/engine_spans.py``): one per
validator chunk a batch touches."""

from harness import engine_spans


def read(facts):
    s = engine_spans.summary(facts)
    if not s:
        return None
    return s["count"].get("slasher/launch", 0) / s["batches"]
