"""Config #5 through the CHAIN (VERDICT r3 item 9): a registry-scale
slot driven through beacon_chain + processor batching — gossip-shaped
SignedAggregateAndProof in, fork-choice head effects out, signatures
batch-verified through the device backend. The CPU suite runs a small
registry; bench.py's slot-chain mode runs the same path at 1M."""

import pytest

from lighthouse_tpu.chain.scale import ScaleChain
from lighthouse_tpu.consensus.config import minimal_spec


@pytest.fixture(scope="module")
def scale_chain():
    sc = ScaleChain(64, minimal_spec())
    yield sc
    from lighthouse_tpu import blsrt

    blsrt.set_device_table(None)


def test_registry_and_lazy_cache(scale_chain):
    sc = scale_chain
    state = sc.chain.head().state
    assert len(state.validators) == 64
    # lazy cache materializes pubkeys on demand and they match the
    # registry's compressed bytes
    pk = sc.chain.pubkey_cache.get(7)
    assert pk.to_bytes() == bytes(sc.compressed[7].tobytes())
    assert bytes(state.validators[7].pubkey) == pk.to_bytes()
    # index lookup builds lazily
    assert sc.chain.pubkey_cache.get_index(pk.to_bytes()) == 7


def test_slot_of_aggregates_through_processor(scale_chain):
    sc = scale_chain
    sc.slot_clock.set_slot(1)
    sc.chain.per_slot_task()

    aggs = sc.make_slot_aggregates(1)
    assert len(aggs) >= 1  # every committee of the slot

    res = sc.drive_slot(aggs)
    assert res["attestations_rejected"] == 0
    assert res["aggregates_verified"] == len(aggs)

    # fork choice observed every attester in the slot's committees
    fc = sc.chain.fork_choice
    voted = sum(
        1 for v in fc.votes if v is not None and v.current_root != bytes(32)
    ) if hasattr(fc, "votes") else None
    attesters = sum(
        len(sa.message.aggregate.aggregation_bits) for sa in aggs
    )
    if voted is not None:
        assert voted == attesters

    # replays are deduped, not re-verified
    res2 = sc.drive_slot(aggs)
    assert res2["aggregates_verified"] == len(aggs)  # unchanged


def test_bulk_g2_mul_device_lanes_match_host():
    """The device G2 scalar-mul path bulk_g2_mul takes on the chip
    (interpret mode here): one fixed-width program, results equal to
    host multiplication, padding lanes dropped."""
    from lighthouse_tpu.chain.scale import BULK_LANES, _bulk_g2_mul_lanes
    from lighthouse_tpu.crypto.bls.curve import g2_generator

    G = g2_generator()
    ks = [3, 5, 123456789, 2**200 + 7]
    got = _bulk_g2_mul_lanes(G, ks)
    assert len(got) == len(ks) < BULK_LANES
    assert all(g == G.mul(k) for g, k in zip(got, ks))
