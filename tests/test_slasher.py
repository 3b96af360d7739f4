"""Slasher tests: double votes, surround/surrounded detection via the
chunked min/max target arrays, double proposals, chunk persistence, and
op-pool-ready slashing export (reference test model: slasher/tests)."""

import numpy as np
import pytest

from lighthouse_tpu.consensus.config import minimal_spec
from lighthouse_tpu.consensus.types import (
    BeaconBlockHeader,
    Checkpoint,
    SignedBeaconBlockHeader,
    spec_types,
)
from lighthouse_tpu.slasher import DeviceSlasher, Slasher, SlasherConfig
from lighthouse_tpu.slasher.arrays import (
    CODE_DOUBLE,
    CODE_SURROUNDED,
    CODE_SURROUNDS,
    MAX_DISTANCE,
    TargetArrays,
)
from lighthouse_tpu.store.kv import MemoryStore

SPEC = minimal_spec()
T = spec_types(SPEC.preset)


def _att(validators, source, target, beacon_root=b"\x01"):
    from lighthouse_tpu.consensus.types import AttestationData

    return T.IndexedAttestation(
        attesting_indices=list(validators),
        data=AttestationData(
            slot=target * SPEC.preset.SLOTS_PER_EPOCH,
            index=0,
            beacon_block_root=beacon_root.ljust(32, b"\x00"),
            source=Checkpoint(epoch=source, root=b"\x00" * 32),
            target=Checkpoint(epoch=target, root=b"\x00" * 32),
        ),
        signature=b"\xc0" + bytes(95),
    )


def _header(slot, proposer, state_root=b"\x00"):
    return SignedBeaconBlockHeader(
        message=BeaconBlockHeader(
            slot=slot,
            proposer_index=proposer,
            parent_root=b"\x00" * 32,
            state_root=state_root.ljust(32, b"\x00"),
            body_root=b"\x00" * 32,
        ),
        signature=b"\xc0" + bytes(95),
    )


class TestTargetArrays:
    def _arrays(self):
        return TargetArrays(MemoryStore(), 16, 256, 4096)

    def test_no_conflict_benign_sequence(self):
        a = self._arrays()
        for e in range(1, 10):
            assert a.check_surround(7, e - 1, e) is None
            a.apply(7, e - 1, e)

    def test_detects_surrounding_vote(self):
        a = self._arrays()
        a.apply(7, 3, 4)
        assert a.check_surround(7, 2, 5) == "surrounds"

    def test_detects_surrounded_vote(self):
        a = self._arrays()
        a.apply(7, 2, 7)
        assert a.check_surround(7, 3, 5) == "surrounded"

    def test_same_source_not_surround(self):
        a = self._arrays()
        a.apply(7, 3, 5)
        assert a.check_surround(7, 3, 7) is None  # same source: not slashable
        assert a.check_surround(7, 3, 4) is None

    def test_adjacent_targets_not_surround(self):
        a = self._arrays()
        a.apply(7, 2, 5)
        assert a.check_surround(7, 1, 5) is None  # equal target = double, not surround

    def test_per_validator_isolation(self):
        a = self._arrays()
        a.apply(7, 3, 4)
        assert a.check_surround(8, 2, 5) is None

    def test_chunk_roundtrip_through_db(self):
        db = MemoryStore()
        a = TargetArrays(db, 16, 256, 4096)
        a.apply(300, 3, 4)  # validator in the second chunk
        a.flush()
        b = TargetArrays(db, 16, 256, 4096)
        assert b.check_surround(300, 2, 5) == "surrounds"
        assert b.min_targets.get(1, 0) == MAX_DISTANCE  # untouched defaults


class TestSlasher:
    def test_double_vote_detected(self):
        s = Slasher(T)
        s.accept_attestation(_att([1, 2], 0, 1, beacon_root=b"\x01"))
        s.accept_attestation(_att([2, 3], 0, 1, beacon_root=b"\x02"))
        found = s.process_queued(current_epoch=1)
        assert len(found) == 1  # validator 2 only
        f = found[0]
        assert f.kind == "double" and f.validator_index == 2
        slashing = s.as_attester_slashing(f)
        # both sides decode + the conflicting data differ
        assert slashing.attestation_1.data.hash_tree_root() != (
            slashing.attestation_2.data.hash_tree_root()
        )

    def test_identical_attestation_not_slashable(self):
        s = Slasher(T)
        s.accept_attestation(_att([1], 0, 1))
        s.accept_attestation(_att([1], 0, 1))
        assert s.process_queued(1) == []

    def test_surround_detected_across_batches(self):
        s = Slasher(T)
        s.accept_attestation(_att([5], 3, 4))
        assert s.process_queued(4) == []
        s.accept_attestation(_att([5], 2, 6))
        found = s.process_queued(6)
        assert len(found) == 1
        f = found[0]
        assert f.kind == "surrounds"
        # attestation_1 surrounds attestation_2
        a1, a2 = f.attestation_1.data, f.attestation_2.data
        assert int(a1.source.epoch) < int(a2.source.epoch)
        assert int(a2.target.epoch) < int(a1.target.epoch)

    def test_surrounded_detected(self):
        s = Slasher(T)
        s.accept_attestation(_att([5], 1, 9))
        s.process_queued(9)
        s.accept_attestation(_att([5], 4, 6))
        found = s.process_queued(9)
        assert len(found) == 1
        assert found[0].kind == "surrounded"
        a1, a2 = found[0].attestation_1.data, found[0].attestation_2.data
        assert int(a1.source.epoch) < int(a2.source.epoch)
        assert int(a2.target.epoch) < int(a1.target.epoch)

    def test_double_proposal_detected(self):
        s = Slasher(T)
        s.accept_block(_header(9, 4, state_root=b"\x01"))
        s.accept_block(_header(9, 4, state_root=b"\x02"))
        found = s.process_queued(1)
        assert len(found) == 1
        slashing = s.as_proposer_slashing(found[0])
        assert int(slashing.signed_header_1.message.proposer_index) == 4
        h1 = slashing.signed_header_1.message.hash_tree_root()
        assert h1 != slashing.signed_header_2.message.hash_tree_root()

    def test_same_block_twice_benign(self):
        s = Slasher(T)
        s.accept_block(_header(9, 4))
        s.accept_block(_header(9, 4))
        assert s.process_queued(1) == []

    def test_full_block_accepted_as_header(self):
        """Slasher accepts full SignedBeaconBlocks too (the chain feeds
        it whatever it imports)."""
        block = T.SIGNED_BLOCK_BY_FORK["phase0"](
            message=T.BLOCK_BY_FORK["phase0"](slot=3, proposer_index=2)
        )
        other = T.SIGNED_BLOCK_BY_FORK["phase0"](
            message=T.BLOCK_BY_FORK["phase0"](
                slot=3, proposer_index=2, state_root=b"\x01" * 32
            )
        )
        s = Slasher(T)
        s.accept_block(block)
        s.accept_block(other)
        found = s.process_queued(1)
        assert len(found) == 1

    def test_slashing_feeds_op_pool(self):
        """End-to-end: a slasher verdict becomes a block-includable
        AttesterSlashing via the op pool (service/src/service.rs flow)."""
        from lighthouse_tpu.chain.harness import BeaconChainHarness

        h = BeaconChainHarness(validator_count=16)
        chain = h.chain
        s = Slasher(h.types)
        s.accept_attestation(_att([5], 0, 2))
        s.process_queued(2)
        s.accept_attestation(_att([5], 1, 3))  # fork: double-ish? no — surround-free
        s.accept_attestation(_att([5], 0, 3, beacon_root=b"\x09"))
        found = s.process_queued(3)
        # (0,2) vs (1,3): no surround; (1,3) vs (0,3): double at target 3
        kinds = {f.kind for f in found}
        assert "double" in kinds
        f = next(f for f in found if f.kind == "double")
        slashing = s.as_attester_slashing(f)
        from lighthouse_tpu.consensus.verify_operation import (
            SigVerifiedOp,
            slashable_indices,
        )

        st = chain.head().state
        idxs = slashable_indices(st, slashing, chain.spec)
        assert 5 in idxs
        chain.op_pool.insert_attester_slashing(
            SigVerifiedOp.new(slashing, st, [0, 3])
        )
        _, attester = chain.op_pool.get_slashings(st)
        assert len(attester) == 1


def _fingerprint(found):
    return [
        (
            f.kind,
            f.validator_index,
            bytes(f.attestation_1.hash_tree_root()).hex()[:8],
            bytes(f.attestation_2.hash_tree_root()).hex()[:8],
        )
        for f in found
    ]


def _small_config():
    # Tiny chunks so a 24-validator history spans several device chunks.
    return SlasherConfig(chunk_size=4, validator_chunk_size=8,
                         history_length=64)


def _adversarial_batches(seed, batches=4, per_batch=12):
    """Seeded mix of double / surround pairs / clean votes over a small
    validator set, dense enough that every batch collides somewhere."""
    import random

    rng = random.Random(seed)
    out = []
    for _ in range(batches):
        batch = []
        for _ in range(per_batch):
            v = rng.randrange(24)
            e0 = 1 + rng.randrange(20)
            shape = rng.random()
            if shape < 0.3:
                root = bytes([rng.randrange(1, 250)])
                batch.append(_att([v], e0, e0 + 1, beacon_root=root))
                batch.append(_att([v], e0, e0 + 1, beacon_root=b"\xfe"))
            elif shape < 0.6:
                batch.append(_att([v], e0 + 1, e0 + 2))
                batch.append(_att([v], e0, e0 + 3))
            else:
                batch.append(_att([v], e0, e0 + 1))
        out.append(batch)
    return out


class TestDeviceSlasher:
    """DeviceSlasher (slasher/arrays.py SurroundEngine) must be
    bit-exact with the host Slasher: same findings, same kinds, same
    attestation_1/attestation_2 ordering, batch by batch."""

    def _run(self, slasher_cls, batches):
        s = slasher_cls(T, config=_small_config())
        prints = []
        for batch in batches:
            for att in batch:
                s.accept_attestation(att)
            prints.append(_fingerprint(s.process_queued(64)))
        return s, prints

    def test_seeded_history_parity(self, monkeypatch):
        monkeypatch.setenv("LHTPU_SLASHER_DEVICE", "0")
        for seed in (1, 7, 42):
            batches = _adversarial_batches(seed)
            _, host = self._run(Slasher, batches)
            _, dev = self._run(DeviceSlasher, batches)
            assert dev == host
            assert any(host)  # seeds chosen to actually find offenses

    def test_crafted_case_parity(self, monkeypatch):
        monkeypatch.setenv("LHTPU_SLASHER_DEVICE", "0")
        crafted = [[
            _att([1], 0, 1, beacon_root=b"\x01"),
            _att([1], 0, 1, beacon_root=b"\x02"),  # double
            _att([2], 3, 4),
            _att([2], 2, 5),  # surrounds
            _att([3], 1, 9),
            _att([3], 4, 6),  # surrounded
            _att([4], 0, 1),  # clean
        ]]
        _, host = self._run(Slasher, crafted)
        _, dev = self._run(DeviceSlasher, crafted)
        assert dev == host
        kinds = sorted(k for (k, *_rest) in host[0])
        assert kinds == ["double", "surrounded", "surrounds"]

    def test_jax_device_mode_matches_host_mirror(self, monkeypatch):
        pytest.importorskip("jax")
        batches = _adversarial_batches(7)
        monkeypatch.setenv("LHTPU_SLASHER_DEVICE", "0")
        _, host_mode = self._run(DeviceSlasher, batches)
        monkeypatch.setenv("LHTPU_SLASHER_DEVICE", "1")
        s, dev_mode = self._run(DeviceSlasher, batches)
        assert dev_mode == host_mode
        rep = s.engine.report()
        assert rep["degraded"] is False and rep["fallbacks"] == 0

    def test_fault_degrades_with_identical_findings(self, monkeypatch):
        from lighthouse_tpu.common import resilience

        monkeypatch.setenv("LHTPU_SLASHER_DEVICE", "0")
        batches = _adversarial_batches(7)
        _, clean = self._run(DeviceSlasher, batches)
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "slasher:assert:1")
        resilience.rearm_faults()
        try:
            s, faulted = self._run(DeviceSlasher, batches)
        finally:
            monkeypatch.delenv("LHTPU_FAULT_INJECT")
            resilience.rearm_faults()
        assert faulted == clean  # fault-safe: no finding lost or changed
        rep = s.engine.report()
        assert rep["fallbacks"] >= 1
        assert rep["degraded"] is True
        assert rep["fault_kinds"]

    def test_four_devices_match_the_host_slasher(self, monkeypatch):
        pytest.importorskip("jax")
        monkeypatch.setenv("LHTPU_SLASHER_DEVICE", "1")
        cfg = SlasherConfig(chunk_size=4, validator_chunk_size=8,
                            history_length=64, devices=4)
        for seed in (1, 42):
            batches = _adversarial_batches(seed, batches=5)
            host, dev = Slasher(T, config=cfg), DeviceSlasher(T, config=cfg)
            for batch in batches:
                for att in batch:
                    host.accept_attestation(att)
                    dev.accept_attestation(att)
                assert (_fingerprint(dev.process_queued(64))
                        == _fingerprint(host.process_queued(64)))
            rep = dev.engine.report()
            assert rep["shards"] == 4 and rep["device"]
            assert rep["fallbacks"] == 0
            assert dev.engine._planes[0].shape[0] == 4


def _slot_votes(epoch, validators, extra=()):
    """One vote (epoch - 1 -> epoch) per validator, then ``extra``."""
    return [(v, epoch - 1, epoch) for v in validators] + list(extra)


def _engine_counts():
    from lighthouse_tpu.common import monitoring
    from lighthouse_tpu.slasher import arrays

    return {
        "votes": arrays.VOTES_TOTAL.value(),
        "launches": arrays.LAUNCHES_TOTAL.value(),
        "inits": arrays.PLANE_INITS_TOTAL.value(),
        "tiles": arrays.TILES_TOTAL.value(),
        "miss": monitoring.JIT_CACHE_EVENTS.value(fn="slasher_scan",
                                                  event="miss"),
        "hit": monitoring.JIT_CACHE_EVENTS.value(fn="slasher_scan",
                                                 event="hit"),
    }


def _delta(before, after):
    return {k: after[k] - before[k] for k in before}


def _engine(monkeypatch, device: str):
    """A tiny engine: chunk 8, history 64, tiles of 8 rows."""
    from lighthouse_tpu.slasher.arrays import SurroundEngine

    monkeypatch.setenv("LHTPU_SLASHER_DEVICE", device)
    return SurroundEngine(validator_chunk_size=8, history_length=64, tile=8)


class TestEngineTracing:
    """SurroundEngine's spans and counters, on a tiny engine (chunk 8,
    history 64, tiles of 8 rows): one program per batch."""

    @pytest.fixture
    def engine(self, monkeypatch):
        pytest.importorskip("jax")
        return _engine(monkeypatch, "1")

    @pytest.fixture
    def tracing_on(self):
        from lighthouse_tpu.common import tracing

        prev = tracing.set_enabled(True)
        tracing.clear()
        try:
            yield tracing
        finally:
            tracing.set_enabled(prev)
            tracing.clear()

    def test_one_batch_root_with_one_launch_and_wait(
            self, engine, tracing_on):
        # 33 votes over 4 chunks, validator 0 twice: round 0 is 4 tiles
        # of 8, round 1 one tile; then 16 votes over 2 known chunks, then
        # a vote in a new chunk, which grows the planes
        engine.process(_slot_votes(10, range(32), [(0, 8, 12)]))
        engine.process(_slot_votes(11, range(8, 24)))
        engine.process(_slot_votes(12, [40]))
        roots = [r for r in tracing_on.roots()
                 if r.name.startswith("slasher/")]
        assert [r.name for r in roots] == ["slasher/batch"] * 3
        one = {"shards": 1}
        assert [r.attrs for r in roots] == [
            {"votes": 33, "chunks": 4, "rounds": 2, "tiles": 5, "slots": 40,
             "shard_votes_max": 33, **one},
            {"votes": 16, "chunks": 2, "rounds": 1, "tiles": 2, "slots": 16,
             "shard_votes_max": 16, **one},
            {"votes": 1, "chunks": 1, "rounds": 1, "tiles": 1, "slots": 8,
             "shard_votes_max": 1, **one},
        ]
        for root, grows in zip(roots, (True, False, True)):
            assert [c.name for c in root.children] == (
                ["slasher/group", "slasher/prepare"]
                + ["slasher/init_planes"] * grows
                + ["slasher/launch", "slasher/wait"])
            assert root.size == 1 + sum(c.size for c in root.children)

    def test_only_the_batch_spans_feed_the_span_histogram(
            self, engine, tracing_on):
        # the batch's inner steps stay out of lhtpu_span_seconds, as
        # they did when they ran once per chunk
        def counts():
            return {key[0]: shard.count for key, shard in
                    tracing_on.SPAN_SECONDS._shards.items()
                    if key[0].startswith("slasher/")}

        before = counts()
        engine.process(_slot_votes(10, range(32)))
        after = counts()
        grew = {k for k in after if after[k] != before.get(k, 0)}
        assert grew == {"slasher/batch", "slasher/group"}
        (root,) = [r for r in tracing_on.roots() if r.name == "slasher/batch"]
        assert len(root.children) == 5

    def test_counters_advance_once_per_batch_with_tracing_off(self, engine):
        from lighthouse_tpu.common import tracing

        prev = tracing.set_enabled(False)
        try:
            before = _engine_counts()
            engine.process(_slot_votes(10, range(32), [(0, 8, 12)]))
            got = _delta(before, _engine_counts())
            assert {k: got[k] for k in ("votes", "launches", "inits",
                                        "tiles")} == {
                "votes": 33, "launches": 1, "inits": 4, "tiles": 5}
            before = _engine_counts()
            engine.process(_slot_votes(11, range(8, 24)))
            got = _delta(before, _engine_counts())
            assert {k: got[k] for k in ("votes", "launches", "inits",
                                        "tiles")} == {
                "votes": 16, "launches": 1, "inits": 0, "tiles": 2}
        finally:
            tracing.set_enabled(prev)

    def test_a_compile_is_counted_on_a_new_pad_only(self, engine):
        # the upload is a power of two of tiles: a new bucket compiles,
        # more rounds inside a known bucket do not (engines share the
        # program, so start from an empty cache)
        engine._program.clear_cache()
        before = _engine_counts()
        engine.process(_slot_votes(10, range(32)))       # 4 tiles: compiles
        got = _delta(before, _engine_counts())
        assert (got["miss"], got["hit"]) == (1, 0)
        engine.process(_slot_votes(11, range(32)))       # 4 tiles again
        got = _delta(before, _engine_counts())
        assert (got["miss"], got["hit"]) == (1, 1)
        engine.process(_slot_votes(12, range(32), [(0, 11, 13)]))  # 5 -> 8
        got = _delta(before, _engine_counts())
        assert (got["miss"], got["hit"]) == (2, 1)
        # three votes of validator 0: three rounds, 6 tiles, bucket 8
        engine.process(_slot_votes(13, range(32), [(0, 12, 14), (0, 9, 15)]))
        got = _delta(before, _engine_counts())
        assert (got["miss"], got["hit"]) == (2, 2)

    def test_codes_do_not_depend_on_tracing(self, monkeypatch):
        pytest.importorskip("jax")
        from lighthouse_tpu.common import tracing
        from lighthouse_tpu.slasher.arrays import SurroundEngine

        batches = [
            _slot_votes(10, range(32)),
            # a double vote, a surrounding and a surrounded one
            _slot_votes(11, range(8, 24), [(9, 10, 11), (3, 8, 12)]),
            _slot_votes(14, range(4), [(12, 12, 13), (20, 9, 15)]),
        ]
        codes = {}
        for device, on in (("1", False), ("1", True), ("0", False)):
            monkeypatch.setenv("LHTPU_SLASHER_DEVICE", device)
            prev = tracing.set_enabled(on)
            try:
                eng = SurroundEngine(validator_chunk_size=8,
                                     history_length=64)
                codes[device, on] = [eng.process(b) for b in batches]
            finally:
                tracing.set_enabled(prev)
        assert codes["1", True] == codes["1", False] == codes["0", False]
        assert any(any(c) for c in codes["1", True])

    def test_disabled_tracing_leaves_no_roots(self, engine):
        from lighthouse_tpu.common import tracing

        prev = tracing.set_enabled(False)
        tracing.clear()
        try:
            engine.process(_slot_votes(10, range(32)))
            assert tracing.roots() == []
        finally:
            tracing.set_enabled(prev)

    def test_fault_is_counted_and_traced_as_host_path(self, engine,
                                                      tracing_on,
                                                      monkeypatch):
        from lighthouse_tpu.common import resilience
        from lighthouse_tpu.slasher import arrays

        batches = [_slot_votes(10, range(32)),
                   _slot_votes(11, range(8, 24), [(9, 10, 11), (3, 8, 12)])]
        host = _engine(monkeypatch, "0")
        want = [host.process(b) for b in batches]
        monkeypatch.setenv("LHTPU_SLASHER_DEVICE", "1")
        before = arrays.FALLBACKS_TOTAL.items()
        got = [engine.process(batches[0])]
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "slasher:assert:1")
        resilience.rearm_faults()
        try:
            got.append(engine.process(batches[1]))
        finally:
            monkeypatch.delenv("LHTPU_FAULT_INJECT")
            resilience.rearm_faults()
        assert got == want and any(got[1])
        kinds = engine.report()["fault_kinds"]
        assert sum(kinds.values()) == 1 and engine.report()["degraded"]
        after = dict((lbl["kind"], v)
                     for lbl, v in arrays.FALLBACKS_TOTAL.items())
        was = dict((lbl["kind"], v) for lbl, v in before)
        (kind,) = kinds
        assert after[kind] - was.get(kind, 0) == 1
        roots = [r for r in tracing_on.roots() if r.name == "slasher/batch"]
        # the faulted batch runs whole on the host, from the vote log
        assert [c.name for c in roots[-1].children] == [
            "slasher/group", "slasher/host_path"]


class TestEngineRounds:
    """The device engine against its host path on a tiny engine, where
    the rounds and tiles of one program matter."""

    @pytest.fixture(autouse=True)
    def _jax(self):
        pytest.importorskip("jax")

    def _both(self, monkeypatch, batches):
        host = _engine(monkeypatch, "0")
        dev = _engine(monkeypatch, "1")
        want = [host.process(b) for b in batches]
        got = []
        for b in batches:
            old = dev._planes
            got.append(dev.process(b))
            # donated: the planes the program read are gone
            assert old is None or all(p.is_deleted() for p in old)
        assert got == want
        assert dev.report()["fallbacks"] == 0
        return dev, got

    def test_several_votes_of_one_validator_in_a_batch(self, monkeypatch):
        # validator 3 votes four times: each vote sees the ones before
        votes = _slot_votes(10, range(8), [
            (3, 20, 30), (3, 22, 26), (3, 18, 34), (3, 18, 34)])
        _, (codes,) = self._both(monkeypatch, [votes])
        assert codes[8:] == [0, CODE_SURROUNDED, CODE_SURROUNDS,
                             CODE_SURROUNDS | CODE_DOUBLE]

    def test_a_round_longer_than_a_tile(self, monkeypatch):
        # round 0 holds 20 validators (3 tiles of 8), round 1 holds 10
        # (2 tiles), each of them surrounding its round-0 vote
        votes = _slot_votes(10, range(20), [(v, 8, 12) for v in range(10)])
        _, (codes,) = self._both(monkeypatch, [votes])
        assert codes[20:] == [CODE_SURROUNDS] * 10

    def test_sparse_and_growing_chunks_keep_earlier_planes(self, monkeypatch):
        batches = [
            [(2, 20, 30), (75, 20, 30)],                 # chunks 0 and 9
            [(330, 20, 30), (2, 22, 26), (75, 18, 34)],  # chunk 41 grows
            [(40, 5, 6), (330, 21, 25), (75, 18, 34)],   # chunk 5 grows
        ]
        dev, codes = self._both(monkeypatch, batches)
        assert codes[1][1:] == [CODE_SURROUNDED, CODE_SURROUNDS]
        assert codes[2][1:] == [CODE_SURROUNDED,
                                CODE_SURROUNDS | CODE_DOUBLE]
        assert dev.report()["chunks"] == 4
        assert dev._planes[0].shape == (1, 4 * 8, 1, 64)

    def test_pad_rows_never_write_row_zero(self, monkeypatch):
        # validator 0 holds plane row 0; the next batch leaves it out and
        # pads 9 votes to 2 tiles of 8: a pad row that wrote would mark
        # target 0 (ring slot 0) in row 0, a double for target 64
        batches = [[(0, 62, 63)] + _slot_votes(63, range(1, 8)),
                   _slot_votes(63, range(8, 17)),
                   [(0, 63, 64)]]
        dev, codes = self._both(monkeypatch, batches)
        assert codes[2] == [0]
        occ = np.asarray(dev._planes[2]).reshape(-1, 64)
        assert occ[0].tolist() == [p in (0, 63) for p in range(64)]

    @pytest.mark.parametrize("shards", [1, 2])
    def test_tiles_hold_each_row_once_in_round_order(self, shards):
        from lighthouse_tpu.slasher.arrays import _tiles

        rows = np.array([5, 3, 5, 9, 5, 3, 1, 2, 4, 6, 7, 8, 0, 11, 13])
        shard = rows % shards
        dest, t_eff, tiles, rounds = _tiles(rows, 4, 2, shard, shards)
        # one shard: round 0 is 12 rows (3 tiles), then 2 and 1; two
        # shards: shard 1 holds 5 x3, 3 x2, 9, 1, 7, 11, 13, its round 0
        # 7 rows (2 tiles), then 2 and 1
        assert (t_eff, tiles, rounds) == (4, 5 if shards == 1 else 4, 3)
        for k in range(shards):
            mine = shard == k
            assert len(set(dest[mine].tolist())) == mine.sum()
            assert dest[mine].max() < tiles * t_eff
            for i in range(tiles):
                held = rows[mine & (dest // t_eff == i)]
                assert len(set(held.tolist())) == len(held)
        # each row's votes keep their order
        for r in set(rows.tolist()):
            assert list(dest[rows == r]) == sorted(dest[rows == r])

def _plain_codes(batches):
    """Each vote's code from every earlier vote of its validator, one
    vote at a time (the votes stay inside one history)."""
    seen: dict = {}
    out = []
    for batch in batches:
        codes = []
        for v, src, tgt in batch:
            code = 0
            for ps, pt in seen.get(v, ()):
                if ps > src and pt < tgt:
                    code |= CODE_SURROUNDS
                if ps < src and pt > tgt:
                    code |= CODE_SURROUNDED
                if pt == tgt:
                    code |= CODE_DOUBLE
            seen.setdefault(v, []).append((src, tgt))
            codes.append(code)
        out.append(codes)
    return out


def _seeded_batches(seed, batches=4, votes=150, validators=120):
    """Seeded votes inside a 64-epoch history: many validators vote two
    or three times in a batch (second and third rounds), and later
    batches bring validators of new chunks."""
    r = np.random.default_rng(seed)
    out = []
    for k in range(batches):
        v = r.integers(0, validators + 40 * k, votes)
        v[: votes // 10] = v[votes // 10: votes // 5]   # second rounds
        v[: votes // 30] = v[votes // 5: votes // 5 + votes // 30]
        src = r.integers(10, 40, votes)
        tgt = src + r.integers(1, 12, votes)
        out.append([tuple(x) for x in
                    np.stack([v, src, tgt], axis=1).tolist()])
    return out


class TestEngineShards:
    """The engine with its planes sharded over 1, 2 and 4 of the forced
    CPU devices: one program a batch across the shards, the same codes
    as the host path and a plain replay."""

    @pytest.fixture(autouse=True)
    def _devices(self, monkeypatch):
        jax = pytest.importorskip("jax")
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 forced host devices")
        monkeypatch.setenv("LHTPU_SLASHER_DEVICE", "1")

    @staticmethod
    def _engine(devices):
        from lighthouse_tpu.slasher.arrays import SurroundEngine

        return SurroundEngine(validator_chunk_size=8, history_length=64,
                              tile=8, devices=devices)

    @pytest.mark.parametrize("seed", [3, 2**31 + 25])
    def test_codes_equal_for_1_2_and_4_devices(self, monkeypatch, seed):
        batches = _seeded_batches(seed)
        want = _plain_codes(batches)
        assert any(c & CODE_DOUBLE for b in want for c in b)
        assert any(c & (CODE_SURROUNDS | CODE_SURROUNDED)
                   for b in want for c in b)
        monkeypatch.setenv("LHTPU_SLASHER_DEVICE", "0")
        host = self._engine(1)
        assert [host.process(b) for b in batches] == want
        monkeypatch.setenv("LHTPU_SLASHER_DEVICE", "1")
        for n in (1, 2, 4):
            eng = self._engine(n)
            assert [eng.process(b) for b in batches] == want, n
            rep = eng.report()
            assert rep["device"] and rep["fallbacks"] == 0
            assert rep["shards"] == n

    def test_planes_grow_per_shard_without_moving_rows(self):
        # chunks 0..4 then 5..9 then 10: slot k on shard k % 4; each
        # call that brings new chunks grows every shard's local rows
        eng = self._engine(4)
        batches = [
            [(8 * c + 1, 20, 30) for c in range(5)],
            [(8 * c + 1, 22, 26) for c in range(10)],
            [(81, 21, 25), (1, 18, 34), (41, 19, 31), (73, 22, 26)],
        ]
        got = [eng.process(b) for b in batches]
        assert got == _plain_codes(batches)
        assert got[1][:5] == [CODE_SURROUNDED] * 5
        min_plane = eng._planes[0]
        assert min_plane.shape == (4, 3 * 8, 1, 64)
        assert min_plane.sharding.spec[0] == "shard"
        assert len(min_plane.sharding.device_set) == 4
        # validator 41 (chunk 5, slot 5) lives on shard 1, local slot 1,
        # validator 9 (chunk 1, slot 1) on shard 1, local slot 0
        maxp = np.asarray(eng._planes[1])
        assert maxp[1, 8 + 1].reshape(-1)[30] == 1   # (19, 31) at 30
        assert maxp[1, 1].reshape(-1)[22] == 8       # (20, 30) at 22

    def test_a_fault_falls_back_with_the_same_codes(self, monkeypatch):
        from lighthouse_tpu.common import resilience

        batches = _seeded_batches(11)
        want = _plain_codes(batches)
        eng = self._engine(4)
        got = [eng.process(batches[0])]
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "slasher:assert:1")
        resilience.rearm_faults()
        try:
            got.append(eng.process(batches[1]))
        finally:
            monkeypatch.delenv("LHTPU_FAULT_INJECT")
            resilience.rearm_faults()
        got += [eng.process(b) for b in batches[2:]]
        assert got == want
        rep = eng.report()
        assert rep["degraded"] and rep["fallbacks"] == 1

    def test_one_launch_a_batch_across_the_shards(self):
        from lighthouse_tpu.common import tracing

        eng = self._engine(4)
        prev = tracing.set_enabled(False)
        try:
            for b in _seeded_batches(5):
                before = _engine_counts()
                eng.process(b)
                got = _delta(before, _engine_counts())
                assert got["launches"] == 1
                assert got["miss"] + got["hit"] == 1
                assert got["votes"] == len(b)
                assert got["tiles"] % 4 == 0
        finally:
            tracing.set_enabled(prev)

    def test_shard_counter_and_batch_attributes(self):
        from lighthouse_tpu.common import monitoring, tracing

        def shard_votes():
            return {k: monitoring.SLASHER_SHARD_VOTES.value(shard=str(k))
                    for k in range(4)}

        eng = self._engine(4)
        # chunks 0..3 get slots 0..3, one per shard; then 3 votes in
        # chunk 0, one in chunk 2 (validator 17 twice: two rounds)
        first = [(8 * c, 20, 21) for c in range(4)]
        second = [(1, 21, 22), (2, 21, 22), (3, 21, 22), (17, 21, 22),
                  (17, 20, 23)]
        for on in (False, True):
            prev = tracing.set_enabled(on)
            tracing.clear()
            try:
                before = shard_votes()
                eng.process(first if not on else
                            [(v, s + 10, t + 10) for v, s, t in first])
                eng.process(second if not on else
                            [(v, s + 10, t + 10) for v, s, t in second])
                after = shard_votes()
                roots = [r for r in tracing.roots()
                         if r.name == "slasher/batch"]
            finally:
                tracing.set_enabled(prev)
                tracing.clear()
            assert {k: after[k] - before[k] for k in after} == {
                0: 4, 1: 1, 2: 3, 3: 1}
        assert [(r.attrs["shards"], r.attrs["shard_votes_max"],
                 r.attrs["rounds"], r.attrs["tiles"], r.attrs["slots"])
                for r in roots] == [(4, 1, 1, 1, 32), (4, 3, 2, 2, 64)]
        group = roots[1].children[0]
        assert group.name == "slasher/group"
        assert [c.name for c in group.children] == ["slasher/route"]

    def test_more_devices_than_jax_sees_is_an_error(self):
        import jax

        with pytest.raises(ValueError, match="devices"):
            self._engine(len(jax.devices()) + 1)
        with pytest.raises(ValueError):
            self._engine(0)
