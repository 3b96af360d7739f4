"""The general traffic generator: makes a configuration's signed
attestation traffic from the seed alone. The mix's own parameters
(``benchmark/traffic/<name>.json``) are read by its kind's runner.

Nothing here imports JAX or the program. Signing runs in worker
processes (``Generator``), so it keeps ahead of the window without
taking the main process's interpreter lock.

The registry is the sequential-key registry the configuration states:
validator ``v`` holds secret key ``v + 1``. A slot of an epoch covers
``validators / slots_per_epoch`` validators, drawn from the registry by
a seeded permutation per epoch and split into the slot's committees.
Each committee signs one message per slot (its AttestationData signing
root: 32 bytes drawn from the seed).
"""

from __future__ import annotations

import hashlib
import time
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass
import multiprocessing

import numpy as np

from . import bls

_TAG = b"lighthouse-tpu benchmark traffic v1"


def rng(seed: int, *path: int) -> np.random.Generator:
    """A generator keyed by the seed and a path of small integers."""
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), *path])


@dataclass(frozen=True)
class Shape:
    """The sizes a configuration fixes for attestation traffic."""

    validators: int
    slots_per_epoch: int
    max_committees_per_slot: int
    target_committee_size: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        return cls(int(cfg["validators"]), int(cfg["slots_per_epoch"]),
                   int(cfg["max_committees_per_slot"]),
                   int(cfg["target_committee_size"]))

    @property
    def committees_per_slot(self) -> int:
        """The spec's get_committee_count_per_slot for an active set of
        ``validators``."""
        return max(1, min(self.max_committees_per_slot,
                          self.validators // self.slots_per_epoch
                          // self.target_committee_size))

    @property
    def sets_per_slot(self) -> int:
        return self.validators // self.slots_per_epoch


def message(seed: int, slot: int, committee: int) -> bytes:
    """The 32-byte signing root committee ``committee`` signs in
    ``slot``."""
    return hashlib.sha256(_TAG + int(seed).to_bytes(16, "big", signed=True)
                          + slot.to_bytes(8, "big")
                          + committee.to_bytes(8, "big")).digest()


@dataclass
class SlotPlan:
    """One slot's sets in arrival order: validator index, the id of the
    message it signs (an index into ``messages``) and the committee."""

    slot: int
    validators: np.ndarray   # int64[n]
    message_ids: np.ndarray  # int32[n]
    committees: np.ndarray   # int32[n]
    messages: list           # list[bytes]


def plan_slot(seed: int, slot: int, shape: Shape) -> SlotPlan:
    """Which validator signs which message, in which order, in ``slot``."""
    spe = shape.slots_per_epoch
    epoch, in_epoch = divmod(slot, spe)
    perm = rng(seed, 1, epoch).permutation(shape.validators)
    per_slot = shape.sets_per_slot
    members = perm[in_epoch * per_slot:(in_epoch + 1) * per_slot]
    n_comm = shape.committees_per_slot
    committees = np.repeat(np.arange(n_comm, dtype=np.int32),
                           [len(c) for c in np.array_split(members, n_comm)])
    order = rng(seed, 2, slot).permutation(per_slot)
    validators = members[order].astype(np.int64)
    committees = committees[order]
    msgs = [message(seed, slot, c) for c in range(n_comm)]
    return SlotPlan(slot, validators, committees.copy(), committees, msgs)


# --------------------------------------------------------------- signing

_G1_TABLE = None


def _g1_table(bits: int):
    global _G1_TABLE
    if _G1_TABLE is None or _G1_TABLE[0] != bits:
        _G1_TABLE = (bits, bls.FixedBase(bls.G1_GEN, bls.Fp, bits))
    return _G1_TABLE[1]


def sign_group(msg: bytes, validators: list, bits: int):
    """Signatures of ``msg`` by each validator (key v + 1) and their
    public keys: (sig bytes [n*192], pubkey bytes [n*96]). Runs in a
    worker process."""
    h = bls.hash_to_g2(msg)
    if len(validators) > 8:
        base = bls.FixedBase(h, bls.Fp2, bits)
        sig_j = [base.mul(v + 1) for v in validators]
    else:
        hj = bls.jac(h, bls.Fp2)
        sig_j = [bls.mul(hj, v + 1, bls.Fp2) for v in validators]
    g1 = _g1_table(bits)
    sigs = bls.batch_affine(sig_j, bls.Fp2)
    pks = bls.batch_affine([g1.mul(v + 1) for v in validators], bls.Fp)
    return (b"".join(bls.g2_bytes(s) for s in sigs),
            b"".join(bls.g1_bytes(p) for p in pks))


def _sign_groups(groups, bits):
    t = time.perf_counter()
    out = [sign_group(m, vs, bits) for m, vs in groups]
    return out, time.perf_counter() - t


@dataclass
class SlotSets:
    """A signed slot, in arrival order."""

    plan: SlotPlan
    sigs: np.ndarray  # uint8[n, 192]
    pks: np.ndarray   # uint8[n, 96]
    sign_s: float = 0.0  # the longest job's seconds: the part's wall time


def _jobs(plan: SlotPlan, parts: int):
    """Groups of (message, validators) split into about ``parts`` jobs,
    each a list of groups: the signing work of one slot."""
    groups = []
    for mid, msg in enumerate(plan.messages):
        rows = np.flatnonzero(plan.message_ids == mid)
        if len(rows):
            groups.append((mid, rows))
    groups.sort(key=lambda g: -len(g[1]))
    jobs = [[] for _ in range(max(1, parts))]
    load = [0] * len(jobs)
    for g in groups:
        j = load.index(min(load))
        jobs[j].append(g)
        load[j] += len(g[1]) + 250  # a hash costs about 250 signatures
    return [j for j in jobs if j]


def sign_slot(plan: SlotPlan, bits: int, submit=None, parts: int = 1):
    """Sign every set of ``plan``. ``submit(fn, *args)`` returns a
    future (a process pool's submit), or None to sign here."""
    jobs = _jobs(plan, parts)
    calls = []
    for job in jobs:
        args = ([(plan.messages[mid], plan.validators[rows].tolist())
                 for mid, rows in job], bits)
        calls.append(submit(_sign_groups, *args) if submit
                     else _Done(_sign_groups(*args)))

    def finish() -> SlotSets:
        n = len(plan.validators)
        sigs = np.zeros((n, 192), np.uint8)
        pks = np.zeros((n, 96), np.uint8)
        longest = 0.0
        for job, call in zip(jobs, calls):
            results, seconds = call.result()
            longest = max(longest, seconds)
            for (mid, rows), (sb, pb) in zip(job, results):
                sigs[rows] = np.frombuffer(sb, np.uint8).reshape(-1, 192)
                pks[rows] = np.frombuffer(pb, np.uint8).reshape(-1, 96)
        return SlotSets(plan, sigs, pks, longest)

    return finish


class _Done:
    def __init__(self, value):
        self._value = value

    def result(self):
        return self._value

    def done(self):
        return True


def plan_part(plan: SlotPlan, lo: int, hi: int) -> SlotPlan:
    """Sets ``lo`` to ``hi`` of a slot's arrival order."""
    return SlotPlan(plan.slot, plan.validators[lo:hi],
                    plan.message_ids[lo:hi], plan.committees[lo:hi],
                    plan.messages)


class Generator:
    """Signed parts of slots from the seed, made ahead of use by worker
    processes that never import JAX. A part is ``part_sets`` sets of a
    slot's arrival order, keyed (slot, part)."""

    def __init__(self, seed: int, shape: Shape, workers: int,
                 part_sets: int = 8192):
        self.seed, self.shape = seed, shape
        self.bits = int(shape.validators).bit_length()
        self.part_sets = part_sets
        self.workers = workers
        self._pool = None
        if workers > 0:
            ctx = multiprocessing.get_context("spawn")
            self._pool = ProcessPoolExecutor(workers, mp_context=ctx)
        self._pending: dict = {}
        self._plans: dict = {}

    def next_key(self, key):
        slot, part = key
        parts = -(-self.shape.sets_per_slot // self.part_sets)
        return (slot, part + 1) if part + 1 < parts else (slot + 1, 0)

    def _plan(self, slot: int) -> SlotPlan:
        if slot not in self._plans:
            if len(self._plans) > 4:
                self._plans.pop(next(iter(self._plans)))
            self._plans[slot] = plan_slot(self.seed, slot, self.shape)
        return self._plans[slot]

    def request(self, key) -> None:
        """Start signing part ``key`` = (slot, part) unless started."""
        if key not in self._pending:
            slot, part = key
            lo = part * self.part_sets
            hi = min(lo + self.part_sets, self.shape.sets_per_slot)
            plan = plan_part(self._plan(slot), lo, hi)
            submit = self._pool.submit if self._pool else None
            self._pending[key] = sign_slot(plan, self.bits, submit,
                                           max(1, self.workers))

    def get(self, key) -> SlotSets:
        self.request(key)
        return self._pending.pop(key)()

    def submit(self, fn, *args):
        if self._pool is None:
            return _Done(fn(*args))
        return self._pool.submit(fn, *args)

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True, cancel_futures=True)
            self._pool = None


