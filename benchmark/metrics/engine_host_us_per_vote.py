"""Host microseconds per vote that the slasher engine spends on its own
work, not waiting on the device: the ``slasher/batch`` spans' seconds
less their ``slasher/wait`` spans', over their votes, for the window's
last three batches (``harness/engine_spans.py``)."""

from harness import engine_spans


def read(facts):
    s = engine_spans.summary(facts)
    if not s or not s["votes"]:
        return None
    wait = s["seconds"].get("slasher/wait", 0.0)
    return (s["batch_s"] - wait) * 1e6 / s["votes"]
