"""The plain reference for the slasher cells, in numpy: what a slasher has
to say of each attester vote, from the validator's earlier votes alone,
with the semantics of ``slasher_reference.Reference`` and fast enough
to replay tens of millions of votes.

A vote ``(validator, source, target)`` is classified against every
earlier vote of the same validator whose target lies within the last
``history`` epochs, counted back from the newest target seen so far,
the vote's own included (the slasher's current epoch):

- surrounds (1): an earlier vote lies strictly inside it
  (earlier source later, earlier target earlier);
- surrounded (2): an earlier vote lies strictly around it;
- double (4): an earlier vote has the same target.

The votes are laid end to end in the order they were given, each gets
its current epoch as the running maximum of the targets, and a stable
sort by validator puts each validator's votes together in that order.
Then for lag d = 1, 2, ... every vote is compared with its validator's
d-th earlier vote, all votes at once, until no validator has more than
d votes.

Nothing here imports JAX or the program.
"""

from __future__ import annotations

import numpy as np

SURROUNDS, SURROUNDED, DOUBLE = 1, 2, 4


def _code(ps, pt, cs, ct) -> np.ndarray:
    """The bits an earlier vote (ps, pt) gives a later one (cs, ct)."""
    return (((ps > cs) & (pt < ct)).view(np.uint8) * np.uint8(SURROUNDS)
            | ((ps < cs) & (pt > ct)).view(np.uint8) * np.uint8(SURROUNDED)
            | (pt == ct).view(np.uint8) * np.uint8(DOUBLE))


def _by_validator(v: np.ndarray) -> np.ndarray:
    """The stable sort of ``v``'s positions by value: one sort of
    (value, position) keys, faster than a stable argsort."""
    n = len(v)
    shift = max(1, int(n - 1).bit_length())
    if not n or v.min() < 0 or int(v.max()) >= 1 << (62 - shift):
        return np.argsort(v, kind="stable")
    keys = np.sort((v << shift) | np.arange(n))
    return keys & ((1 << shift) - 1)


def codes(votes: np.ndarray, history: int) -> np.ndarray:
    """The code of each row of ``votes`` (``int[n, 3]``, in the order
    given), as ``uint8[n]``."""
    votes = np.asarray(votes, np.int64).reshape(-1, 3)
    n = len(votes)
    order = _by_validator(votes[:, 0])
    v = votes[:, 0][order]
    s = votes[:, 1].astype(np.int32)[order]
    t = votes[:, 2].astype(np.int32)[order]
    # a prior vote counts while its target lies above this
    oldest = (np.maximum.accumulate(votes[:, 2]) - history).astype(
        np.int32)[order]
    got = np.zeros(n, np.uint8)
    d = 1
    # while most votes have a d-th earlier vote, compare whole shifted
    # arrays; then only the votes that have one
    while d < n:
        same = v[d:] == v[:-d]
        if np.count_nonzero(same) * 8 < n:
            break
        same &= t[:-d] > oldest[d:]
        got[d:] |= _code(s[:-d], t[:-d], s[d:], t[d:]) * same.view(np.uint8)
        d += 1
    live = np.flatnonzero(v[d:] == v[:-d]) + d if d < n else []
    while len(live):
        prior = live - d
        pt, ct = t[prior], t[live]
        code = _code(s[prior], pt, s[live], ct)
        got[live] |= code * (pt > oldest[live]).view(np.uint8)
        d += 1
        live = live[live >= d]
        live = live[v[live - d] == v[live]]
    out = np.empty(n, np.uint8)
    out[order] = got
    return out
