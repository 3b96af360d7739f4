"""The slasher engine's own spans, as the program's tracer holds them
when a traced run reports (``lighthouse_tpu/common/tracing.py``).

A traced run turns the program's spans on from set-up onwards; the
tracer keeps the newest finished roots, bounded by their number of
spans. Each ``slasher/batch`` root (one ``SurroundEngine.process``
call) carries ``votes`` and ``slots``, the vote slots its plane scans
stepped through, padding included. Its children are ``slasher/group``
and, per validator chunk, ``slasher/prepare``, ``slasher/init_planes``
(a chunk's first votes only), ``slasher/launch`` and ``slasher/wait``
(the host blocked on the device's codes), or ``slasher/host_path``
after a fault.

The readers read a fixed sample: the last ``SAMPLE`` batches that made
no planes. The window runs after set-up and holds ~14 batches, so these
are the window's last; set-up's batches, which make every chunk's
planes and compile every pad, are never read, however many batches the
tracer holds.
"""

from __future__ import annotations

BATCH = "slasher/batch"
INIT = "slasher/init_planes"
SAMPLE = 3


def _program_roots() -> list:
    from lighthouse_tpu.common import tracing

    return tracing.roots()


def _spans(root):
    stack = list(root.children)
    while stack:
        sp = stack.pop()
        stack.extend(sp.children)
        yield sp


def summary(facts: dict) -> dict | None:
    """Totals over the sample: ``batches``, ``votes``, ``slots``,
    ``batch_s``, and per nested span name its ``count`` and
    ``seconds``. None outside a traced run (``facts`` without the run's
    trace) and where the tracer holds fewer than ``SAMPLE`` such
    batches."""
    if not facts.get("trace"):
        return None
    batches = [r for r in _program_roots()
               if r.name == BATCH and r.duration is not None
               and not any(sp.name == INIT for sp in _spans(r))]
    if len(batches) < SAMPLE:
        return None
    out = {"batches": SAMPLE, "votes": 0, "slots": 0,
           "batch_s": 0.0, "count": {}, "seconds": {}}
    for b in batches[-SAMPLE:]:
        out["votes"] += b.attrs.get("votes", 0)
        out["slots"] += b.attrs.get("slots", 0)
        out["batch_s"] += b.duration
        for sp in _spans(b):
            out["count"][sp.name] = out["count"].get(sp.name, 0) + 1
            out["seconds"][sp.name] = (out["seconds"].get(sp.name, 0.0)
                                       + (sp.duration or 0.0))
    return out
