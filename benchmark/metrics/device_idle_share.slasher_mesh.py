"""Share of the traced batch in which no operation ran on the device: 1
minus the union of device-op intervals over the batch, from the
profiler trace, averaged over the chips that hold the slasher's planes
(sharded slasher cell)."""

from harness import trace


def read(facts):
    return trace.idle_share_pct(facts.get("trace"))
