"""Slasher core (reference: slasher/src/slasher.rs + database.rs +
attestation_queue.rs / block_queue.rs + service/src/service.rs).

Ingest (``accept_attestation:69`` / ``accept_block``) queues records;
``process_queued:79`` drains them in validator-chunk groups (the
reference batches by chunk to touch each compressed chunk once),
checking:

* double votes      — (validator, target) → attestation-data root map;
* surround votes    — the min/max TargetArrays;
* double proposals  — (slot, proposer) → header signing-root map.

Verdicts come back as the spec slashing containers (AttesterSlashing /
ProposerSlashing built from the two conflicting messages) so a service
can drop them straight into the operation pool.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field

from ..store.kv import MemoryStore
from .arrays import (
    CODE_DOUBLE,
    CODE_SURROUNDED,
    CODE_SURROUNDS,
    SurroundEngine,
    TargetArrays,
)

COL_ATT_BY_TARGET = b"slasher/att_by_target"   # (validator,target) -> data root
COL_ATT_RECORDS = b"slasher/att_records"        # data_root -> ssz IndexedAttestation
COL_PROPOSALS = b"slasher/proposals"            # (slot,proposer) -> signing root ++ ssz header


@dataclass
class SlasherConfig:
    chunk_size: int = 16
    validator_chunk_size: int = 256
    history_length: int = 4096
    slot_offset: float = 0.5
    #: chips holding DeviceSlasher's planes, each a 1/devices share of
    #: the validator chunks
    devices: int = 1


@dataclass
class AttesterSlashingFound:
    kind: str                   # "double" | "surrounds" | "surrounded"
    validator_index: int
    attestation_1: object       # the earlier IndexedAttestation
    attestation_2: object       # the offending one


@dataclass
class ProposerSlashingFound:
    proposer_index: int
    header_1: object
    header_2: object


class Slasher:
    def __init__(self, types, config: SlasherConfig | None = None, db=None):
        self.types = types
        self.config = config or SlasherConfig()
        self.db = db if db is not None else MemoryStore()
        self.arrays = TargetArrays(
            self.db,
            self.config.chunk_size,
            self.config.validator_chunk_size,
            self.config.history_length,
        )
        self._att_queue: list = []
        self._block_queue: list = []
        self.stats = {"attestations": 0, "blocks": 0, "slashings": 0}

    # ---------------------------------------------------------------- ingest
    def accept_attestation(self, indexed_attestation) -> None:
        """Queue an IndexedAttestation (slasher.rs:69)."""
        self._att_queue.append(indexed_attestation)

    def accept_block(self, signed_header_or_block) -> None:
        """Queue a signed block / header (block_queue.rs)."""
        self._block_queue.append(signed_header_or_block)

    # --------------------------------------------------------------- process
    def process_queued(self, current_epoch: int) -> list:
        """Drain queues; returns all slashings found
        (slasher.rs:79 process_queued → process_attestations grouped by
        validator chunk :189-190)."""
        found: list = []
        atts, self._att_queue = self._att_queue, []
        blocks, self._block_queue = self._block_queue, []

        # group attestation work by validator chunk so each compressed
        # chunk row is loaded/stored once per batch
        by_chunk: dict[int, list[tuple[int, object]]] = defaultdict(list)
        for att in atts:
            self.stats["attestations"] += 1
            for vi in att.attesting_indices:
                by_chunk[int(vi) // self.config.validator_chunk_size].append(
                    (int(vi), att)
                )
        for chunk_index in sorted(by_chunk):
            for vi, att in by_chunk[chunk_index]:
                found.extend(self._process_attestation(vi, att))
        self.arrays.flush()

        for block in blocks:
            self.stats["blocks"] += 1
            found.extend(self._process_block(block))

        self.stats["slashings"] += len(found)
        return found

    # ----------------------------------------------------- attestation checks
    def _att_key(self, validator: int, target: int) -> bytes:
        return validator.to_bytes(8, "big") + target.to_bytes(8, "big")

    def _store_attestation(self, att) -> bytes:
        root = att.hash_tree_root()
        if self.db.get(COL_ATT_RECORDS, root) is None:
            self.db.put(COL_ATT_RECORDS, root, att.encode())
        return root

    def _load_attestation(self, root: bytes):
        raw = self.db.get(COL_ATT_RECORDS, root)
        return self.types.IndexedAttestation.decode(raw) if raw is not None else None

    def _process_attestation(self, validator: int, att) -> list:
        source = int(att.data.source.epoch)
        target = int(att.data.target.epoch)
        out = []

        # 1. double vote
        key = self._att_key(validator, target)
        prev_root = self.db.get(COL_ATT_BY_TARGET, key)
        data_root = att.data.hash_tree_root()
        if prev_root is not None:
            prev = self._load_attestation(prev_root)
            if prev is not None and prev.data.hash_tree_root() != data_root:
                out.append(
                    AttesterSlashingFound("double", validator, prev, att)
                )
        # 2. surround votes
        verdict = self.arrays.check_surround(validator, source, target)
        if verdict is not None:
            prior = self._find_conflicting(validator, source, target, verdict)
            if prior is not None:
                a1, a2 = (att, prior) if verdict == "surrounds" else (prior, att)
                out.append(
                    AttesterSlashingFound(verdict, validator, a1, a2)
                )

        # record
        root = self._store_attestation(att)
        if prev_root is None:
            self.db.put(COL_ATT_BY_TARGET, key, root)
        self.arrays.apply(validator, source, target)
        return out

    def _find_conflicting(self, validator: int, source: int, target: int,
                          verdict: str):
        """Locate a stored attestation forming the surround pair (the
        reference walks the indexed-attestation DB by target; we scan
        the validator's recorded targets)."""
        for t in range(self.config.history_length):
            root = self.db.get(COL_ATT_BY_TARGET, self._att_key(validator, t))
            if root is None:
                continue
            prior = self._load_attestation(root)
            if prior is None:
                continue
            ps, pt = int(prior.data.source.epoch), int(prior.data.target.epoch)
            if verdict == "surrounds" and source < ps and pt < target:
                return prior
            if verdict == "surrounded" and ps < source and target < pt:
                return prior
        return None

    # ---------------------------------------------------------- block checks
    def _header_of(self, signed) -> tuple:
        """Accepts SignedBeaconBlock or SignedBeaconBlockHeader; returns
        (slot, proposer, canonical root, header container)."""
        from ..consensus.types import BeaconBlockHeader, SignedBeaconBlockHeader

        msg = signed.message
        if hasattr(msg, "body"):
            header = BeaconBlockHeader(
                slot=int(msg.slot),
                proposer_index=int(msg.proposer_index),
                parent_root=bytes(msg.parent_root),
                state_root=bytes(msg.state_root),
                body_root=msg.body.hash_tree_root(),
            )
        else:
            header = msg
        signed_header = SignedBeaconBlockHeader(
            message=header, signature=bytes(signed.signature)
        )
        return int(header.slot), int(header.proposer_index), header.hash_tree_root(), signed_header

    def _process_block(self, signed) -> list:
        from ..consensus.types import SignedBeaconBlockHeader

        slot, proposer, root, signed_header = self._header_of(signed)
        key = slot.to_bytes(8, "big") + proposer.to_bytes(8, "big")
        prev = self.db.get(COL_PROPOSALS, key)
        if prev is not None:
            prev_root, prev_raw = prev[:32], prev[32:]
            if prev_root != root:
                prev_header = SignedBeaconBlockHeader.decode(prev_raw)
                return [
                    ProposerSlashingFound(proposer, prev_header, signed_header)
                ]
            return []
        self.db.put(COL_PROPOSALS, key, root + signed_header.encode())
        return []

    # ---------------------------------------------------------------- export
    def as_attester_slashing(self, found: AttesterSlashingFound):
        return self.types.AttesterSlashing(
            attestation_1=found.attestation_1,
            attestation_2=found.attestation_2,
        )

    def as_proposer_slashing(self, found: ProposerSlashingFound):
        from ..consensus.types import ProposerSlashing

        return ProposerSlashing(
            signed_header_1=found.header_1,
            signed_header_2=found.header_2,
        )


class DeviceSlasher(Slasher):
    """Slasher whose surround/double-vote detection runs on the
    SurroundEngine device planes (ISSUE 17).

    The KV side — attestation records, the (validator, target) root
    map, proposal keys, slashing containers — is inherited unchanged;
    only the per-vote plane scan moves to device. The engine holds the
    planes across ``config.devices`` chips, sharded by validator chunk:
    a chunk's slot ``k`` lives on chip ``k % devices``, and each batch
    runs as one program on every chip, each chip stepping the votes
    routed to its own chunks. Findings are
    materialized in the host's exact per-vote order (double before
    surround, map written only when absent), so the output is
    bit-identical to the host ``Slasher`` oracle on any input, and the
    engine's sticky host fallback keeps that true through faults.
    """

    def __init__(self, types, config: SlasherConfig | None = None,
                 db=None, engine: SurroundEngine | None = None):
        super().__init__(types, config, db)
        self.engine = engine or SurroundEngine(
            validator_chunk_size=self.config.validator_chunk_size,
            history_length=self.config.history_length,
            devices=self.config.devices,
        )

    def process_queued(self, current_epoch: int) -> list:
        found: list = []
        atts, self._att_queue = self._att_queue, []
        blocks, self._block_queue = self._block_queue, []

        by_chunk: dict[int, list[tuple[int, object]]] = defaultdict(list)
        for att in atts:
            self.stats["attestations"] += 1
            for vi in att.attesting_indices:
                by_chunk[int(vi) // self.config.validator_chunk_size].append(
                    (int(vi), att)
                )
        ordered = [
            pair for ci in sorted(by_chunk) for pair in by_chunk[ci]
        ]
        codes = self.engine.process(
            [
                (vi, int(att.data.source.epoch), int(att.data.target.epoch))
                for vi, att in ordered
            ]
        )
        for (vi, att), code in zip(ordered, codes):
            found.extend(self._materialize(vi, att, code))

        for block in blocks:
            self.stats["blocks"] += 1
            found.extend(self._process_block(block))

        self.stats["slashings"] += len(found)
        return found

    def _materialize(self, validator: int, att, code: int) -> list:
        """Turn an engine code into findings with the host's exact
        semantics and ordering, then record the vote in the KV maps
        (plane updates already happened inside the engine)."""
        source = int(att.data.source.epoch)
        target = int(att.data.target.epoch)
        out = []

        key = self._att_key(validator, target)
        prev_root = self.db.get(COL_ATT_BY_TARGET, key)
        if code & CODE_DOUBLE and prev_root is not None:
            prev = self._load_attestation(prev_root)
            data_root = att.data.hash_tree_root()
            if prev is not None and prev.data.hash_tree_root() != data_root:
                out.append(
                    AttesterSlashingFound("double", validator, prev, att)
                )
        # surrounded wins when both bits fire — check_surround returns
        # early on "surrounded", and the host emits at most one
        verdict = None
        if code & CODE_SURROUNDED:
            verdict = "surrounded"
        elif code & CODE_SURROUNDS:
            verdict = "surrounds"
        if verdict is not None:
            prior = self._find_conflicting(validator, source, target, verdict)
            if prior is not None:
                a1, a2 = (att, prior) if verdict == "surrounds" else (prior, att)
                out.append(AttesterSlashingFound(verdict, validator, a1, a2))

        root = self._store_attestation(att)
        if prev_root is None:
            self.db.put(COL_ATT_BY_TARGET, key, root)
        return out
