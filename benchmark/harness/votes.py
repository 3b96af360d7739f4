"""The general generator of attester-vote traffic for a slasher: makes a
configuration's votes from the seed alone, at the sizes the mix
(``benchmark/traffic/<name>.json``, ``kind: slasher``) states.

A vote is ``(validator, source epoch, target epoch)``. Every epoch each
validator of the registry votes once, ``(epoch - 1, epoch)``, in the
slot a seeded permutation of the registry gives it; a batch is one
slot's votes, what the slasher's queue holds after one update period.
Before the first epoch, ``old_per_chunk`` seeded validators of every
chunk of ``chunk`` validators hold one old wide vote, up to
``old_max_age_epochs`` back, so that the slasher holds state for every
chunk, as one that has run for a while does. Each batch also carries
slashable votes at seeded places: ``doubles`` repeats of a vote of the
batch (a double vote), and ``surrounds`` votes of validators with an
old vote, half around it (a surrounding vote) and half inside it (a
surrounded one). Every seed gets the same sizes: the same number of
votes a batch, of each kind, and of old votes.

Nothing here imports JAX or the program.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import traffic


@dataclass(frozen=True)
class Shape:
    """The sizes a configuration and a mix fix for vote traffic."""

    validators: int
    slots_per_epoch: int
    history_length: int
    chunk: int
    first_epoch: int
    old_per_chunk: int
    old_min_width: int
    old_max_width: int
    old_max_age_epochs: int
    doubles: int
    surrounds: int

    @classmethod
    def from_config(cls, cfg: dict, mix: dict) -> "Shape":
        return cls(int(cfg["validators"]), int(cfg["slots_per_epoch"]),
                   int(cfg["history_length"]),
                   int(cfg["validator_chunk_size"]), int(mix["first_epoch"]),
                   int(mix["old_per_chunk"]), int(mix["old_min_width"]),
                   int(mix["old_max_width"]), int(mix["old_max_age_epochs"]),
                   int(mix["doubles"]), int(mix["surrounds"]))

    @property
    def votes_per_slot(self) -> int:
        return self.validators // self.slots_per_epoch

    @property
    def batch_votes(self) -> int:
        return self.votes_per_slot + self.doubles + self.surrounds

    @property
    def n_old(self) -> int:
        return self.validators // self.chunk * self.old_per_chunk


class Votes:
    """The vote stream of one seed: the old votes, then batch after
    batch, each an ``int64[n, 3]`` array of (validator, source,
    target)."""

    def __init__(self, seed: int, shape: Shape):
        if shape.old_max_age_epochs + shape.old_max_width + 2 \
                >= shape.history_length:
            raise ValueError("old votes reach past the slasher's history")
        if shape.surrounds > shape.n_old or shape.doubles \
                > shape.votes_per_slot:
            raise ValueError("more slashable votes a batch than voters")
        self.seed, self.shape = seed, shape
        r = traffic.rng(seed, 11)
        n = shape.validators
        chunks = n // shape.chunk
        within = np.argsort(r.random((chunks, shape.chunk)), axis=1)
        self.old_validators = (
            np.arange(chunks)[:, None] * shape.chunk
            + within[:, :shape.old_per_chunk]).ravel()
        width = r.integers(shape.old_min_width, shape.old_max_width + 1,
                           shape.n_old)
        # the old vote ends at least two epochs before the first one
        age = r.integers(width + 2, shape.old_max_age_epochs + 1)
        self.old_target = shape.first_epoch - age + width
        self.old_source = self.old_target - width
        self._perm = {}
        self._walk = traffic.rng(seed, 13).permutation(shape.n_old)

    def old(self) -> np.ndarray:
        """The old votes, in a seeded order."""
        order = traffic.rng(self.seed, 12).permutation(self.shape.n_old)
        return np.stack([self.old_validators, self.old_source,
                         self.old_target], axis=1)[order].astype(np.int64)

    def _epoch_perm(self, epoch: int) -> np.ndarray:
        if epoch not in self._perm:
            self._perm.clear()
            self._perm[epoch] = traffic.rng(self.seed, 14, epoch).permutation(
                self.shape.validators)
        return self._perm[epoch]

    def batch(self, k: int) -> np.ndarray:
        """Batch ``k``: slot ``k % slots_per_epoch`` of epoch
        ``first_epoch + k // slots_per_epoch``."""
        s = self.shape
        epoch, slot = s.first_epoch + k // s.slots_per_epoch, \
            k % s.slots_per_epoch
        voters = self._epoch_perm(epoch)[slot * s.votes_per_slot:
                                         (slot + 1) * s.votes_per_slot]
        rows = [np.stack([voters, np.full_like(voters, epoch - 1),
                          np.full_like(voters, epoch)], axis=1)]
        r = traffic.rng(self.seed, 15, k)
        if s.doubles:
            rows.append(rows[0][r.choice(len(voters), s.doubles,
                                         replace=False)])
        if s.surrounds:
            at = np.arange(k * s.surrounds, (k + 1) * s.surrounds)
            pick = self._walk[at % s.n_old]
            src, tgt = self.old_source[pick], self.old_target[pick]
            inside = np.arange(s.surrounds) % 2 == 1
            rows.append(np.stack([
                self.old_validators[pick],
                np.where(inside, src + 1, src - 1),
                np.where(inside, tgt - 1, tgt + 1)], axis=1))
        out = np.concatenate(rows).astype(np.int64)
        return out[r.permutation(len(out))]


def pads(batch: np.ndarray, chunk: int, floor: int) -> set:
    """The padded vote counts of the per-chunk programs this batch
    makes: its votes grouped by validator chunk, each group rounded up
    to a power of two, at least ``floor``."""
    counts = np.bincount(batch[:, 0] // chunk)
    counts = counts[counts > 0]
    return {max(floor, 1 << int(c - 1).bit_length()) for c in counts}
