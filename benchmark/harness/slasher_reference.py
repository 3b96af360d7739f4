"""The plain reference for the slasher cell: what a slasher has to say of
each attester vote, from the validator's earlier votes alone.

A vote ``(validator, source, target)`` is classified against every
earlier vote of the same validator whose target lies within the last
``history`` epochs, counted back from the newest target seen so far
(the slasher's current epoch):

- surrounds (1): an earlier vote lies strictly inside it
  (earlier source later, earlier target earlier);
- surrounded (2): an earlier vote lies strictly around it;
- double (4): an earlier vote has the same target.

The bits are those of the slasher engine's codes, its public answer.
Nothing here imports JAX or the program.
"""

from __future__ import annotations

SURROUNDS, SURROUNDED, DOUBLE = 1, 2, 4


class Reference:
    def __init__(self, history: int):
        self.history = history
        self.now = 0          # the newest target seen
        self.seen: dict = {}  # validator -> [(source, target)]

    def classify(self, v: int, s: int, t: int) -> int:
        code = 0
        self.now = max(self.now, t)
        prior = self.seen.setdefault(v, [])
        for ps, pt in prior:
            if pt <= self.now - self.history:
                continue
            if ps > s and pt < t:
                code |= SURROUNDS
            if ps < s and pt > t:
                code |= SURROUNDED
            if pt == t:
                code |= DOUBLE
        prior.append((s, t))
        return code

    def process(self, votes) -> list:
        return [self.classify(int(v), int(s), int(t)) for v, s, t in votes]
