"""The traffic generator: no set twice, the slot turning every
validators / slots_per_epoch sets, and the seed as its only source of
randomness."""

import hashlib
import json
import os

import numpy as np
import pytest

from harness import spec, traffic
from kinds import gossip

CONFIG = os.path.join(spec.BENCH_DIR, "configs", "mainnet-1m-allsubnets.json")
SEED = 2_718_281_828_459  # larger than 32 bits


@pytest.fixture(scope="module")
def shape():
    with open(CONFIG) as f:
        return traffic.Shape.from_config(json.load(f))


class PlanOnly:
    """A generator that plans slots without signing them."""

    def __init__(self, seed, shape):
        self.seed, self.shape = seed, shape

    part_sets = 8192

    def request(self, key):
        pass

    def next_key(self, key):
        return traffic.Generator.next_key(self, key)

    def get(self, key):
        slot, part = key
        plan = traffic.plan_part(
            traffic.plan_slot(self.seed, slot, self.shape),
            part * self.part_sets, (part + 1) * self.part_sets)
        n = len(plan.validators)
        return traffic.SlotSets(plan, np.zeros((n, 0), np.uint8),
                                np.zeros((n, 0), np.uint8))


def test_mainnet_shape(shape):
    assert shape.committees_per_slot == 64
    assert shape.sets_per_slot == 32_768


def test_no_set_twice_and_slot_turns_every_32768(shape):
    stream = gossip.Stream(PlanOnly(SEED, shape), 0, 0)
    seen = set()
    slot_of = []
    for _ in range(70):  # 71,680 sets: into the third slot
        parts = stream.take(1024)
        call = gossip.Call(parts)
        for s, lo, hi in parts:
            slot_of += [s.plan.slot] * (hi - lo)
        for v, m in zip(call.validators.tolist(), call.messages):
            assert (v, m) not in seen
            seen.add((v, m))
    slot_of = np.asarray(slot_of)
    turns = np.flatnonzero(np.diff(slot_of)) + 1
    assert turns.tolist() == [32_768, 65_536]


def test_each_slot_is_64_committees_of_the_registry(shape):
    plan = traffic.plan_slot(SEED, 5, shape)
    assert len(set(plan.validators.tolist())) == 32_768
    assert plan.validators.min() >= 0 and plan.validators.max() < 1 << 20
    assert len(plan.messages) == 64
    sizes = np.bincount(plan.committees)
    assert sizes.min() == sizes.max() == 512


def _digest(seed, shape):
    gen = traffic.Generator(seed, shape, 0)
    plan = traffic.plan_slot(seed, 0, shape)
    part = traffic.SlotPlan(0, plan.validators[:48], plan.message_ids[:48],
                            plan.committees[:48], plan.messages)
    signed = traffic.sign_slot(part, gen.bits)()
    h = hashlib.sha256(plan.validators.tobytes())
    h.update(b"".join(plan.messages))
    h.update(signed.sigs.tobytes())
    return h.hexdigest()


def test_same_seed_same_traffic(shape):
    a = _digest(SEED, shape)
    assert a == _digest(SEED, shape)
    assert a != _digest(SEED + 1, shape)


def test_signed_sets_verify_under_the_reference():
    from harness import bls, reference

    shape = traffic.Shape(4096, 32, 64, 16)
    plan = traffic.plan_slot(SEED, 3, shape)
    signed = traffic.sign_slot(plan, 13)()
    for i in range(0, 128, 17):
        v = int(plan.validators[i])
        m = plan.messages[plan.message_ids[i]]
        assert reference.set_valid(v, m, signed.sigs[i].tobytes())
        assert not reference.set_valid(v + 1, m, signed.sigs[i].tobytes())
        pk = bls.to_affine(bls.mul(bls.jac(bls.G1_GEN, bls.Fp), v + 1,
                                   bls.Fp), bls.Fp)
        assert bls.g1_bytes(pk) == signed.pks[i].tobytes()
