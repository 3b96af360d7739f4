"""Share of the slasher engine's batch time in which the host is blocked
on the device's codes: the ``slasher/wait`` spans' seconds over the
``slasher/batch`` spans' seconds, for the window's last three batches
(``harness/engine_spans.py``)."""

from harness import engine_spans


def read(facts):
    s = engine_spans.summary(facts)
    if not s or not s["batch_s"]:
        return None
    return 100.0 * s["seconds"].get("slasher/wait", 0.0) / s["batch_s"]
