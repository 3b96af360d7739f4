"""BeaconChain — the chain core (reference: beacon_node/beacon_chain).

Owns the store, op pool, fork choice, caches and the BLS backend choice,
and exposes the block/attestation pipelines
(beacon_chain.rs, block_verification.rs, attestation_verification.rs):

* block import as the typestate chain GossipVerifiedBlock →
  SignatureVerifiedBlock → ExecutionPendingBlock → import_block
  (block_verification.rs:567-596, beacon_chain.rs:2363,2511);
* attestation verification (single + batch with poisoning fallback, the
  north-star TPU workload — attestation_verification/batch.rs);
* block/attestation production for validators
  (produce_block_on_state:3144, produce_unaggregated_attestation);
* head tracking via fork choice (canonical_head.rs recompute_head_at_slot)
  with snapshot/shuffling/proposer caches and observed-* gossip guards;
* finalization side effects: store migration, cache pruning, fork-choice
  pruning (migrate.rs).
"""

from __future__ import annotations

from dataclasses import dataclass

from ..consensus import helpers as h
from ..consensus import signature_sets as sigs
from ..consensus.config import ChainSpec, compute_signing_root
from ..consensus.transition.advance import complete_state_advance
from ..consensus.transition.block import (
    BlockProcessingError,
    SignatureStrategy,
    per_block_processing,
)
from ..consensus.transition.slot import process_slots
from ..consensus.types import (
    Checkpoint,
    spec_types,
    state_fork_name,
)
from ..crypto.bls import api as bls_api
from ..crypto.bls.api import AggregateSignature, SignatureSet, verify_signature_sets
from ..forkchoice import ExecutionStatus, ForkChoice
from ..oppool import OperationPool
from ..store.hot_cold import HotColdDB
from .caches import (
    BeaconProposerCache,
    NaiveAggregationPool,
    NaiveSyncAggregationPool,
    ObservedAggregates,
    ObservedAttesters,
    ObservedBlockProducers,
    ShufflingCache,
    SnapshotCache,
)
from .pubkey_cache import ValidatorPubkeyCache

ZERO_ROOT = b"\x00" * 32

# Gossip clock tolerance (reference: MAXIMUM_GOSSIP_CLOCK_DISPARITY 500ms,
# expressed here in slots for the deterministic clock).
FUTURE_SLOT_TOLERANCE = 1


class BlockError(ValueError):
    """(reference: block_verification.rs BlockError)"""


class AttestationError(ValueError):
    """(reference: attestation_verification.rs Error)"""


@dataclass
class HeadInfo:
    root: bytes
    block: object
    state: object


class BeaconChain:
    def __init__(
        self,
        spec: ChainSpec,
        store: HotColdDB,
        slot_clock,
        genesis_state,
        genesis_block,
        genesis_block_root: bytes,
        backend: str | None = None,
        pubkey_cache: ValidatorPubkeyCache | None = None,
    ):
        self.spec = spec
        self.store = store
        self.slot_clock = slot_clock
        self.backend = backend
        self.types = spec_types(spec.preset)
        # optional ExecutionLayer handle (reference: beacon_chain.execution_layer)
        self.execution_layer = None
        # validator_index -> fee-recipient hex, from the VC's
        # PreparationService (execution_layer proposer_preparation_data)
        self.proposer_preparations: dict[int, str] = {}
        from .validator_monitor import ValidatorMonitor

        self.validator_monitor = ValidatorMonitor()
        from ..consensus.cached_tree_hash import StateRootCache

        # incremental merkleization for the per-block state-root check
        # (reference: the state's tree_hash_cache)
        self.state_root_cache = StateRootCache()

        self.genesis_block_root = genesis_block_root
        self.genesis_validators_root = bytes(genesis_state.genesis_validators_root)

        self.op_pool = OperationPool(spec)
        # injectable for registry-scale startup (a device-table-backed
        # LAZY cache skips 1M host decompressions; pubkey_cache.py)
        self.pubkey_cache = (
            pubkey_cache if pubkey_cache is not None
            else ValidatorPubkeyCache.from_state(genesis_state, store=store.db)
        )
        self.shuffling_cache = ShufflingCache()
        self.snapshot_cache = SnapshotCache()
        self.proposer_cache = BeaconProposerCache()
        self.observed_attesters = ObservedAttesters()
        self.observed_aggregates = ObservedAggregates()
        self.observed_block_producers = ObservedBlockProducers()
        self.naive_aggregation_pool = NaiveAggregationPool()
        from ..consensus.config import SYNC_COMMITTEE_SUBNET_COUNT

        self.naive_sync_pool = NaiveSyncAggregationPool(
            spec.preset.SYNC_COMMITTEE_SIZE // SYNC_COMMITTEE_SUBNET_COUNT
        )
        # (slot, …) keyed observation sets, pruned per slot
        self.observed_sync_contributions: set = set()
        self.observed_sync_contributors: set = set()
        # sync-committee membership, cached per sync-committee period
        self._sync_members_cache: tuple[int, list[int]] | None = None

        self.fork_choice = ForkChoice.from_anchor(
            genesis_state,
            genesis_block_root,
            spec,
            balances_fn=self._justified_balances,
        )
        self._head = HeadInfo(genesis_block_root, genesis_block, genesis_state)
        self._finalized_checkpoint = (0, genesis_block_root)

    # ------------------------------------------------------------- factories
    @classmethod
    def from_genesis(
        cls, store: HotColdDB, genesis_state, spec: ChainSpec, slot_clock,
        backend=None, pubkey_cache=None,
    ) -> "BeaconChain":
        t = spec_types(spec.preset)
        fork = state_fork_name(genesis_state)
        state_root = genesis_state.hash_tree_root()
        block = t.BLOCK_BY_FORK[fork](state_root=state_root)
        signed = t.SIGNED_BLOCK_BY_FORK[fork](message=block)
        block_root = block.hash_tree_root()
        store.put_state(state_root, genesis_state)
        store.put_block(block_root, signed)
        store.set_genesis_block_root(block_root)
        chain = cls(
            spec, store, slot_clock, genesis_state, signed, block_root,
            backend, pubkey_cache=pubkey_cache,
        )
        chain.snapshot_cache.insert(block_root, genesis_state.copy())
        return chain

    # --------------------------------------------------------------- queries
    def current_slot(self) -> int:
        slot = self.slot_clock.now()
        return slot if slot is not None else 0

    def head(self) -> HeadInfo:
        return self._head

    def head_state_copy(self):
        return self._head.state.copy()

    def finalized_checkpoint(self) -> tuple[int, bytes]:
        return self._finalized_checkpoint

    def get_block(self, root: bytes):
        return self.store.get_block(root)

    def _justified_balances(self, checkpoint):
        """balances_fn for the fork-choice store: effective balances of
        active validators at the justified checkpoint's state."""
        epoch, root = checkpoint
        state = self._state_for_block_root(root)
        if state is None:
            state = self._head.state
        return [
            int(v.effective_balance) if h.is_active_validator(v, epoch) else 0
            for v in state.validators
        ]

    def _state_for_block_root(self, block_root: bytes):
        if block_root == self._head.root:
            return self._head.state
        snap = self.snapshot_cache.get_cloned(block_root)
        if snap is not None:
            return snap
        block = self.store.get_block(block_root)
        if block is None:
            return None
        return self.store.get_state(bytes(block.message.state_root))

    # ========================================================== block import
    def process_block(self, signed_block, *, block_delay_seconds=None) -> bytes:
        """Full import pipeline; returns the block root
        (reference: process_block:2363 → import_block:2511). Re-importing
        a known block is a benign no-op (BlockIsAlreadyKnown)."""
        block_root = signed_block.message.hash_tree_root()
        if self.fork_choice.contains_block(block_root):
            return block_root
        gossip = GossipVerifiedBlock(self, signed_block, block_root)
        pending = ExecutionPendingBlock(self, gossip)
        return self._import_block(pending, block_delay_seconds)

    def process_chain_segment(self, blocks) -> list[bytes]:
        """Import an ordered segment (reference: process_chain_segment:2215)."""
        return [self.process_block(b) for b in blocks]

    def _import_block(self, pending: "ExecutionPendingBlock", block_delay_seconds):
        signed_block = pending.signed_block
        block = signed_block.message
        block_root = pending.block_root
        state = pending.post_state

        state_root = bytes(block.state_root)
        ops_slot = self.current_slot()
        # Slot-lateness of the import relative to the block's own slot
        # (slot_clock_lateness_seconds{event="block_import"}).
        self.slot_clock.record_lateness("block_import", int(block.slot))
        self.fork_choice.on_block(
            max(ops_slot, int(block.slot)),
            block,
            block_root,
            state,
            block_delay_seconds=block_delay_seconds,
            execution_status=pending.execution_status,
            execution_block_hash=pending.execution_block_hash,
        )
        # only a fully-verified block claims its (slot, proposer) pair
        self.observed_block_producers.observe(
            int(block.slot), int(block.proposer_index)
        )
        self.store.put_block(block_root, signed_block)
        self.store.put_state(state_root, state)
        self.pubkey_cache.import_new_pubkeys(state)
        self.snapshot_cache.insert(block_root, state.copy())

        # feed fork choice the block's own attestations (on_attestation
        # with is_from_block, reference: import_block)
        from ..forkchoice.fork_choice import ForkChoiceError

        for att in block.body.attestations:
            try:
                indexed = h.get_indexed_attestation(state, att, self.spec)
                self.fork_choice.on_attestation(
                    max(ops_slot, int(block.slot)), indexed, is_from_block=True
                )
                self.validator_monitor.observe_block_attestation_indices(
                    att, indexed.attesting_indices, int(block.slot)
                )
            except (ValueError, ForkChoiceError):
                continue

        self.validator_monitor.observe_block(block, block_root, self.spec)
        self.recompute_head()
        return block_root

    def recompute_head(self) -> bytes:
        """(reference: canonical_head.rs recompute_head_at_slot:431)"""
        slot = max(self.current_slot(), int(self._head.state.slot))
        head_root = self.fork_choice.get_head(slot)
        if head_root != self._head.root:
            block = self.store.get_block(head_root)
            state = self._state_for_block_root(head_root)
            if state is None:
                raise BlockError("head state missing from store")
            if int(state.slot) < int(block.message.slot):
                raise BlockError("head state behind head block")
            self._head = HeadInfo(head_root, block, state)
            self._notify_forkchoice_updated()
        self._check_finalization()
        return self._head.root

    def _notify_forkchoice_updated(self) -> None:
        """forkchoiceUpdated to the engine on head change
        (canonical_head.rs → execution_layer)."""
        el = self.execution_layer
        if el is None:
            return
        body = self._head.block.message.body
        payload = getattr(body, "execution_payload", None)
        if payload is None or bytes(payload.block_hash) == ZERO_ROOT:
            return
        _, finalized_root = self._finalized_checkpoint
        finalized_hash = b"\x00" * 32
        fin_block = self.store.get_block(finalized_root)
        if fin_block is not None:
            fin_payload = getattr(
                fin_block.message.body, "execution_payload", None
            )
            if fin_payload is not None:
                finalized_hash = bytes(fin_payload.block_hash)
        try:
            status, _ = el.notify_forkchoice_updated(
                bytes(payload.block_hash), finalized_hash
            )
            if status == ExecutionStatus.VALID:
                self.fork_choice.on_valid_execution_payload(self._head.root)
        except Exception:  # lhtpu: ignore[LH502] -- execution engine offline is an expected steady state; chain stays optimistic
            pass  # engine offline: stay optimistic (engines.rs fallback)

    def _check_finalization(self) -> None:
        finalized = self.fork_choice.store.finalized_checkpoint
        if finalized[0] > self._finalized_checkpoint[0]:
            self._finalized_checkpoint = finalized
            p = self.spec.preset
            finalized_epoch, finalized_root = finalized
            # prune gossip observation sets + fork choice + op pool
            self.observed_attesters.prune(finalized_epoch)
            self.observed_aggregates.prune(finalized_epoch)
            self.observed_block_producers.prune(finalized_epoch * p.SLOTS_PER_EPOCH)
            self.validator_monitor.prune(finalized_epoch)
            self.fork_choice.prune()
            self.op_pool.prune(self._head.state)
            # migrate finalized history into the freezer
            block = self.store.get_block(finalized_root)
            if block is not None:
                state = self._state_for_block_root(finalized_root)
                if state is not None:
                    target = finalized_epoch * p.SLOTS_PER_EPOCH
                    if int(state.slot) < target:
                        state = complete_state_advance(
                            state.copy(), None, target, self.spec
                        )
                    if int(state.slot) % p.SLOTS_PER_EPOCH == 0:
                        try:
                            self.store.migrate(state, finalized_root)
                        except Exception:  # lhtpu: ignore[LH502] -- freezer migration is best-effort background work; hot store remains authoritative
                            pass  # migration is best-effort background work

    # ====================================================== block production
    def produce_block(
        self, randao_reveal: bytes, slot: int | None = None, graffiti: bytes = b""
    ):
        """Build an unsigned block on the head state
        (reference: produce_block_on_state:3144)."""
        t = self.types
        p = self.spec.preset
        head = self._head
        slot = slot if slot is not None else self.current_slot()
        state = head.state.copy()
        if int(state.slot) < slot:
            state = complete_state_advance(state, None, slot, self.spec)
        elif int(state.slot) > slot:
            raise BlockError("cannot produce a block behind the head state")

        fork = state_fork_name(state)
        proposer_index = h.get_beacon_proposer_index(state, self.spec)
        caches: dict = {}
        attestations = self.op_pool.get_attestations(state, caches)
        proposer_slashings, attester_slashings = self.op_pool.get_slashings(state)
        voluntary_exits = self.op_pool.get_voluntary_exits(state)

        body_kwargs = dict(
            randao_reveal=randao_reveal,
            eth1_data=state.eth1_data,
            graffiti=graffiti.ljust(32, b"\x00")[:32],
            proposer_slashings=proposer_slashings,
            attester_slashings=attester_slashings,
            attestations=attestations,
            deposits=[],
            voluntary_exits=voluntary_exits,
        )
        if fork in ("altair", "bellatrix"):
            body_kwargs["sync_aggregate"] = self.op_pool.get_sync_aggregate(
                slot - 1, head.root
            )
        if fork == "bellatrix":
            body_kwargs["execution_payload"] = self._produce_execution_payload(
                state, slot
            )
        body = t.BODY_BY_FORK[fork](**body_kwargs)

        block = t.BLOCK_BY_FORK[fork](
            slot=slot,
            proposer_index=proposer_index,
            parent_root=head.root,
            state_root=ZERO_ROOT,
            body=body,
        )
        # dry-run the transition to fill in the state root
        trial = t.SIGNED_BLOCK_BY_FORK[fork](message=block)
        per_block_processing(
            state,
            trial,
            self.spec,
            strategy=SignatureStrategy.NO_VERIFICATION,
            get_pubkey=self.pubkey_cache.as_getter(),
            caches=caches,
        )
        block.state_root = self.state_root_cache.state_root(state)
        return block, state

    def _produce_execution_payload(self, state, slot: int):
        """Real payload via the engine when the merge is complete, else
        the empty pre-transition payload (execution_payload.rs
        get_execution_payload)."""
        t = self.types
        el = self.execution_layer
        from ..consensus.transition.block import is_merge_transition_complete

        if el is None or not is_merge_transition_complete(state, self.spec):
            return t.ExecutionPayload()
        from ..consensus import helpers as h2
        from ..execution.execution_layer import engine_json_to_payload

        parent_hash = bytes(state.latest_execution_payload_header.block_hash)
        epoch = slot // self.spec.preset.SLOTS_PER_EPOCH
        proposer = h2.get_beacon_proposer_index(state, self.spec)
        fee_recipient = self.proposer_preparations.get(
            proposer, "0x" + "00" * 20
        )
        attributes = {
            "timestamp": hex(
                int(state.genesis_time) + slot * self.spec.SECONDS_PER_SLOT
            ),
            "prevRandao": "0x" + bytes(
                h2.get_randao_mix(state, epoch, self.spec)
            ).hex(),
            "suggestedFeeRecipient": fee_recipient,
        }
        _, finalized_root = self._finalized_checkpoint
        finalized_hash = b"\x00" * 32
        fin_block = self.store.get_block(finalized_root)
        if fin_block is not None:
            fin_payload = getattr(fin_block.message.body, "execution_payload", None)
            if fin_payload is not None:
                finalized_hash = bytes(fin_payload.block_hash)
        _, payload_id = el.notify_forkchoice_updated(
            parent_hash, finalized_hash, payload_attributes=attributes
        )
        if payload_id is None:
            raise BlockError("engine did not return a payload id")
        return engine_json_to_payload(t, el.get_payload(payload_id))

    # ================================================ attestation production
    def produce_unaggregated_attestation(self, slot: int, committee_index: int):
        """(reference: produce_unaggregated_attestation, served from the
        attester caches)"""
        t = self.types
        p = self.spec.preset
        head = self._head
        state = head.state
        epoch = slot // p.SLOTS_PER_EPOCH
        # Advance only across an epoch boundary: within the head state's
        # epoch, slot processing changes neither the checkpoints nor the
        # shuffling, and a target at or after the head's slot is the head
        # root — the same data without a state copy, which costs seconds
        # at 1M validators (reference: attestation production reads the
        # head state unless the request is for a later epoch).
        if int(state.slot) // p.SLOTS_PER_EPOCH < epoch:
            state = complete_state_advance(state.copy(), None, slot, self.spec)
        committee = self._committee_at(state, slot, committee_index, epoch)

        # Target = block root at the epoch-start slot. When attesting AT
        # the boundary slot the state hasn't recorded that root yet — the
        # head block is the boundary block (or latest before a skip).
        target_slot = epoch * p.SLOTS_PER_EPOCH
        if target_slot >= int(state.slot):
            target_root = head.root
        else:
            target_root = bytes(h.get_block_root_at_slot(state, target_slot, self.spec))
        from ..consensus.types import AttestationData

        data = AttestationData(
            slot=slot,
            index=committee_index,
            beacon_block_root=head.root,
            source=state.current_justified_checkpoint,
            target=Checkpoint(epoch=epoch, root=target_root),
        )
        return t.Attestation(
            aggregation_bits=[False] * len(committee),
            data=data,
            signature=b"\xc0" + bytes(95),
        )

    def _committee_at(self, state, slot: int, index: int, epoch: int):
        cache = self.shuffling_cache.get_or_init(
            state, epoch, self._shuffling_decision_root(epoch), self.spec
        )
        return cache.get_beacon_committee(slot, index)

    def _shuffling_decision_root(self, epoch: int) -> bytes:
        """Attester shuffling for ``epoch`` is decided by the block at the
        last slot of ``epoch - 2`` on the head chain (reference:
        BeaconState::attester_shuffling_decision_root)."""
        p = self.spec.preset
        decision_slot = max(epoch - 1, 0) * p.SLOTS_PER_EPOCH - 1
        if decision_slot < 0:
            return self.genesis_block_root
        root = self.fork_choice.proto.ancestor_at_slot(self._head.root, decision_slot)
        return root if root is not None else self.genesis_block_root

    # ================================================ attestation verification
    def verify_unaggregated_attestation_for_gossip(self, attestation):
        """(reference: attestation_verification.rs
        IndexedUnaggregatedAttestation::verify + signature check)"""
        indexed, committee = self._gossip_attestation_checks(attestation)
        if sum(attestation.aggregation_bits) != 1:
            raise AttestationError("unaggregated attestation must set one bit")
        validator_index = int(indexed.attesting_indices[0])
        epoch = int(attestation.data.target.epoch)
        if self.observed_attesters.is_known(epoch, validator_index):
            raise AttestationError("duplicate attestation (prior seen)")

        sig_set = sigs.indexed_attestation_signature_set(
            self._head.state,
            self.pubkey_cache.as_getter(),
            attestation.signature,
            indexed,
            self.spec,
        )
        if not verify_signature_sets([sig_set], backend=self.backend):
            raise AttestationError("invalid attestation signature")
        self.observed_attesters.observe(epoch, validator_index)
        return VerifiedAttestation(attestation, indexed)

    def batch_verify_unaggregated_attestations_for_gossip(self, attestations):
        """Batch path with poisoning fallback — the TPU hot loop
        (reference: batch_verify_unaggregated_attestations, batch.rs:130-210)."""
        candidates = []
        # Timed read lock over batch assembly (reference: batch.rs:63-66
        # VALIDATOR_PUBKEY_CACHE_LOCK_TIMEOUT): registry imports on the
        # block-import path cannot silently stall gossip verification. A
        # timeout fails the BATCH (each attestation gets a retryable
        # error, mirroring the reference's BeaconChainError), never the
        # caller's drive loop.
        from ..common.timeout_lock import LockTimeout

        try:
            lock_ctx = self.pubkey_cache.lock.read()
            lock_ctx.__enter__()
        except LockTimeout:
            err = AttestationError("pubkey cache lock timeout")
            return [err for _ in attestations]
        try:
            for att in attestations:
                try:
                    indexed, _ = self._gossip_attestation_checks(att)
                    if sum(att.aggregation_bits) != 1:
                        raise AttestationError("unaggregated attestation must set one bit")
                    vi = int(indexed.attesting_indices[0])
                    epoch = int(att.data.target.epoch)
                    if self.observed_attesters.is_known(epoch, vi):
                        raise AttestationError("duplicate attestation (prior seen)")
                    sig_set = sigs.indexed_attestation_signature_set(
                        self._head.state,
                        self.pubkey_cache.as_getter(),
                        att.signature,
                        indexed,
                        self.spec,
                    )
                    candidates.append((att, indexed, vi, epoch, sig_set, None))
                except (AttestationError, ValueError) as e:
                    candidates.append((att, None, None, None, None, e))
        finally:
            lock_ctx.__exit__(None, None, None)

        sets = [c[4] for c in candidates if c[4] is not None]
        oks = self._bisect_verify(sets)
        results = []
        it = iter(oks)
        for att, indexed, vi, epoch, sig_set, err in candidates:
            if err is not None:
                results.append(err)
                continue
            if next(it):
                # Dedup AFTER verification (exactly like the sequential
                # path): the first VERIFIED attestation per (epoch,
                # attester) wins; later intra-batch duplicates or
                # equivocations are rejected, and an earlier
                # invalid-signature copy cannot censor a valid one.
                if self.observed_attesters.is_known(epoch, vi):
                    results.append(
                        AttestationError("duplicate attestation (prior seen)")
                    )
                    continue
                self.observed_attesters.observe(epoch, vi)
                results.append(VerifiedAttestation(att, indexed))
            else:
                results.append(AttestationError("invalid attestation signature"))
        return results

    def batch_verify_aggregated_attestations_for_gossip(
        self, signed_aggregates
    ):
        """Batch path for SignedAggregateAndProof gossip: every
        aggregate's THREE signature sets (selection proof, aggregator,
        aggregate) ride one device batch with poisoning bisection —
        the aggregate twin of the unaggregated batch pipeline
        (reference: attestation_verification/batch.rs:36-128
        batch_verify_aggregated_attestations). Pre-verification checks
        (dedup roots/aggregators, is_aggregator) keep the sequential
        path's semantics exactly; an aggregate passes only if all three
        of its sets verify."""
        from ..common.timeout_lock import LockTimeout

        candidates = []
        try:
            lock_ctx = self.pubkey_cache.lock.read()
            lock_ctx.__enter__()
        except LockTimeout:
            err = AttestationError("pubkey cache lock timeout")
            return [err for _ in signed_aggregates]
        try:
            state = self._head.state
            get_pubkey = self.pubkey_cache.as_getter()
            for sa in signed_aggregates:
                try:
                    message = sa.message
                    aggregate = message.aggregate
                    indexed, committee = self._gossip_attestation_checks(
                        aggregate
                    )
                    epoch = int(aggregate.data.target.epoch)
                    att_root = aggregate.hash_tree_root()
                    # CHECK-only here; recording happens after the batch
                    # verifies (like the unaggregated path) so an
                    # invalid-signature copy cannot censor the valid
                    # aggregate from an honest aggregator.
                    if self.observed_aggregates.is_known_root(epoch, att_root):
                        raise AttestationError("aggregate already known")
                    aggregator_index = int(message.aggregator_index)
                    if self.observed_aggregates.is_known_aggregator(
                        epoch, aggregator_index
                    ):
                        raise AttestationError(
                            "aggregator already seen this epoch"
                        )
                    if not self._is_aggregator(
                        int(aggregate.data.slot),
                        len(committee),
                        bytes(message.selection_proof),
                    ):
                        raise AttestationError("validator is not an aggregator")
                    three = [
                        sigs.signed_aggregate_selection_proof_signature_set(
                            state, get_pubkey, sa, self.spec
                        ),
                        sigs.signed_aggregate_signature_set(
                            state, get_pubkey, sa, self.spec
                        ),
                        sigs.indexed_attestation_signature_set(
                            state, get_pubkey, aggregate.signature, indexed,
                            self.spec,
                        ),
                    ]
                    candidates.append(
                        (aggregate, indexed, three, epoch, att_root,
                         aggregator_index, None)
                    )
                except (AttestationError, ValueError) as e:
                    candidates.append((None, None, None, None, None, None, e))
        finally:
            lock_ctx.__exit__(None, None, None)

        sets = [s for c in candidates if c[2] is not None for s in c[2]]
        oks = iter(self._bisect_verify(sets))
        results = []
        for (aggregate, indexed, three, epoch, att_root, agg_idx,
             err) in candidates:
            if err is not None:
                results.append(err)
                continue
            ok = all([next(oks), next(oks), next(oks)])  # no short-circuit:
            # the iterator must advance exactly 3 per aggregate
            if not ok:
                results.append(
                    AttestationError("invalid aggregate signature(s)")
                )
                continue
            # Dedup AFTER verification (first VERIFIED copy wins —
            # covers intra-batch duplicates too).
            if self.observed_aggregates.observe_root(epoch, att_root):
                results.append(AttestationError("aggregate already known"))
                continue
            if self.observed_aggregates.observe_aggregator(epoch, agg_idx):
                results.append(
                    AttestationError("aggregator already seen this epoch")
                )
                continue
            results.append(VerifiedAttestation(aggregate, indexed))
        return results

    # Host-bisection policy constants, kept as aliases of the hoisted
    # crypto/bls/api values (ISSUE 5 moved the budgeted bisection there
    # so the backend's degraded-triage route shares it).
    _BISECT_LINEAR_CUTOFF = bls_api.BISECT_LINEAR_CUTOFF
    _BISECT_WORK_BUDGET = bls_api.BISECT_WORK_BUDGET

    def _bisect_verify(self, sets) -> list[bool]:
        """Per-set verdicts for a poisoned batch (SURVEY §7.1 hard part
        #3). ISSUE 5: routes through verify_signature_sets_triaged —
        backends with grouped device verdicts (jax) isolate the invalid
        sets by slicing already-packed device inputs in O(log_G
        poisoned-groups) dispatches; backends without the capability
        (python/fake/native) fall back to the budgeted halving bisection
        (api.bisect_verify_sets), the pre-triage strategy, with k
        poisoned lanes costing O(k·log n) verifier calls instead of the
        reference's n individual re-verifications
        (attestation_verification/batch.rs falls back to per-set)."""
        return bls_api.verify_signature_sets_triaged(
            sets, backend=self.backend
        )

    def _bisect_verify_budgeted(self, sets, budget) -> list[bool]:
        """Budgeted halving bisection (compatibility wrapper over the
        hoisted api.bisect_verify_sets — same verdicts, same call
        structure)."""
        return bls_api.bisect_verify_sets(
            sets, backend=self.backend, budget=budget
        )

    def _gossip_attestation_checks(self, attestation):
        data = attestation.data
        p = self.spec.preset
        current_slot = self.current_slot()
        if int(data.slot) > current_slot + FUTURE_SLOT_TOLERANCE:
            raise AttestationError("attestation from the future")
        if int(data.slot) + p.SLOTS_PER_EPOCH < current_slot:
            raise AttestationError("attestation too old")
        if int(data.target.epoch) != int(data.slot) // p.SLOTS_PER_EPOCH:
            raise AttestationError("target epoch does not match slot")
        if not self.fork_choice.contains_block(bytes(data.beacon_block_root)):
            raise AttestationError("unknown head block")
        if not self.fork_choice.contains_block(bytes(data.target.root)):
            raise AttestationError("unknown target block")

        state = self._head.state
        epoch = int(data.target.epoch)
        committee = self._committee_at(state, int(data.slot), int(data.index), epoch)
        if len(attestation.aggregation_bits) != len(committee):
            raise AttestationError("bitfield/committee length mismatch")
        indexed = self.types.IndexedAttestation(
            attesting_indices=sorted(
                int(v)
                for v, bit in zip(committee, attestation.aggregation_bits)
                if bit
            ),
            data=data,
            signature=attestation.signature,
        )
        return indexed, committee

    def verify_aggregated_attestation_for_gossip(self, signed_aggregate):
        """Three signature sets: selection proof, aggregator, aggregate
        (reference: attestation_verification.rs aggregate flow)."""
        message = signed_aggregate.message
        aggregate = message.aggregate
        indexed, committee = self._gossip_attestation_checks(aggregate)
        epoch = int(aggregate.data.target.epoch)
        att_root = aggregate.hash_tree_root()
        if self.observed_aggregates.observe_root(epoch, att_root):
            raise AttestationError("aggregate already known")
        aggregator_index = int(message.aggregator_index)
        if self.observed_aggregates.observe_aggregator(epoch, aggregator_index):
            raise AttestationError("aggregator already seen this epoch")
        if not self._is_aggregator(
            int(aggregate.data.slot),
            len(committee),
            bytes(message.selection_proof),
        ):
            raise AttestationError("validator is not an aggregator")

        state = self._head.state
        get_pubkey = self.pubkey_cache.as_getter()
        sets = [
            sigs.signed_aggregate_selection_proof_signature_set(
                state, get_pubkey, signed_aggregate, self.spec
            ),
            sigs.signed_aggregate_signature_set(
                state, get_pubkey, signed_aggregate, self.spec
            ),
            sigs.indexed_attestation_signature_set(
                state, get_pubkey, aggregate.signature, indexed, self.spec
            ),
        ]
        if not verify_signature_sets(sets, backend=self.backend):
            raise AttestationError("invalid aggregate signature(s)")
        return VerifiedAttestation(aggregate, indexed)

    def _is_aggregator(self, slot, committee_len, selection_proof: bytes) -> bool:
        return h.is_aggregator(committee_len, selection_proof, self.spec)

    def apply_attestation_to_fork_choice(self, verified: "VerifiedAttestation"):
        self.fork_choice.on_attestation(
            self.current_slot(), verified.indexed, is_from_block=False
        )
        self.validator_monitor.observe_gossip_attestation(
            verified.indexed, self.current_slot(), self.spec
        )

    def add_to_naive_aggregation_pool(self, verified: "VerifiedAttestation"):
        self.naive_aggregation_pool.insert(verified.attestation)

    def add_to_operation_pool(self, verified: "VerifiedAttestation"):
        self.op_pool.insert_attestation(verified.attestation)

    # ====================================== sync committee verification
    def _sync_committee_members(self, state) -> list[int]:
        """Cached current-sync-committee validator indices: the
        committee is stable for EPOCHS_PER_SYNC_COMMITTEE_PERIOD, so
        resolve the O(registry) pubkey mapping once per period."""
        p = self.spec.preset
        period = h.get_current_epoch(state, self.spec) // (
            p.EPOCHS_PER_SYNC_COMMITTEE_PERIOD
        )
        cached = self._sync_members_cache
        if cached is not None and cached[0] == period:
            return cached[1]
        members = h.current_sync_committee_indices(state, self.spec)
        self._sync_members_cache = (period, members)
        return members

    def verify_sync_committee_message_for_gossip(self, message):
        """(reference: sync_committee_verification.rs
        verify_sync_committee_message_for_gossip)"""
        state = self._head.state
        if state_fork_name(state) == "phase0":
            raise AttestationError("sync committees require altair")
        slot = int(message.slot)
        current = self.current_slot()
        if not (current - 1 <= slot <= current + FUTURE_SLOT_TOLERANCE):
            raise AttestationError("sync message outside the current slot window")
        vi = int(message.validator_index)
        members = self._sync_committee_members(state)
        if vi not in members:
            raise AttestationError("validator not in the current sync committee")
        key = (slot, vi)
        if key in self.observed_sync_contributors:
            raise AttestationError("duplicate sync message for slot")
        sig_set = sigs.sync_committee_message_set(
            state, self.pubkey_cache.as_getter(), message, self.spec
        )
        if not verify_signature_sets([sig_set], backend=self.backend):
            raise AttestationError("invalid sync message signature")
        self.observed_sync_contributors.add(key)
        return message

    def sync_subnets_for_validator(self, validator_index: int) -> set[int]:
        """Subnets this committee member's positions map onto (the
        gossip topic routing for its messages)."""
        from ..consensus.config import SYNC_COMMITTEE_SUBNET_COUNT

        members = self._sync_committee_members(self._head.state)
        size = self.spec.preset.SYNC_COMMITTEE_SIZE // SYNC_COMMITTEE_SUBNET_COUNT
        return {
            position // size
            for position, member in enumerate(members)
            if member == int(validator_index)
        }

    def add_to_naive_sync_pool(self, message) -> None:
        from ..consensus.config import SYNC_COMMITTEE_SUBNET_COUNT

        state = self._head.state
        members = self._sync_committee_members(state)
        size = self.spec.preset.SYNC_COMMITTEE_SIZE // SYNC_COMMITTEE_SUBNET_COUNT
        vi = int(message.validator_index)
        for position, member in enumerate(members):
            if member == vi:
                self.naive_sync_pool.insert(
                    int(message.slot),
                    bytes(message.beacon_block_root),
                    position // size,
                    position % size,
                    bytes(message.signature),
                )

    def produce_sync_contribution(self, slot: int, block_root: bytes,
                                  subcommittee_index: int):
        entry = self.naive_sync_pool.get(slot, block_root, subcommittee_index)
        if entry is None:
            return None
        bits, sig = entry
        return self.types.SyncCommitteeContribution(
            slot=slot,
            beacon_block_root=bytes(block_root),
            subcommittee_index=subcommittee_index,
            aggregation_bits=list(bits),
            signature=sig.to_bytes(),
        )

    def verify_sync_contribution_for_gossip(self, signed_contribution):
        """Three sets: selection proof, aggregator signature, contribution
        aggregate (reference: sync_committee_verification.rs:618 batch)."""
        message = signed_contribution.message
        contribution = message.contribution
        state = self._head.state
        if state_fork_name(state) == "phase0":
            raise AttestationError("sync committees require altair")
        slot = int(contribution.slot)
        current = self.current_slot()
        if not (current - 1 <= slot <= current + FUTURE_SLOT_TOLERANCE):
            raise AttestationError("contribution outside the slot window")
        # the aggregator must itself sit in the target subcommittee
        # (reference: AggregatorNotInCommittee)
        if int(contribution.subcommittee_index) not in (
            self.sync_subnets_for_validator(int(message.aggregator_index))
        ):
            raise AttestationError("aggregator not in the subcommittee")
        if not h.is_sync_committee_aggregator(
            bytes(message.selection_proof), self.spec
        ):
            raise AttestationError("invalid sync aggregator selection")
        key = (slot, int(contribution.subcommittee_index),
               contribution.hash_tree_root())
        if key in self.observed_sync_contributions:
            raise AttestationError("contribution already known")
        from ..consensus.config import SYNC_COMMITTEE_SUBNET_COUNT

        all_members = self._sync_committee_members(state)
        size = self.spec.preset.SYNC_COMMITTEE_SIZE // SYNC_COMMITTEE_SUBNET_COUNT
        start = int(contribution.subcommittee_index) * size
        members = all_members[start : start + size]
        participants = [
            m for m, bit in zip(members, contribution.aggregation_bits) if bit
        ]
        get_pubkey = self.pubkey_cache.as_getter()
        sets = [
            sigs.sync_committee_selection_proof_signature_set(
                state, get_pubkey, message, self.spec
            ),
            sigs.signed_contribution_and_proof_signature_set(
                state, get_pubkey, signed_contribution, self.spec
            ),
        ]
        contrib_set = sigs.sync_committee_contribution_signature_set(
            state, get_pubkey, contribution, participants, self.spec
        )
        if contrib_set is not None:
            sets.append(contrib_set)
        if not verify_signature_sets(sets, backend=self.backend):
            raise AttestationError("invalid sync contribution signature(s)")
        self.observed_sync_contributions.add(key)
        self.op_pool.insert_sync_contribution(contribution)
        return signed_contribution

    # ------------------------------------------------------------ persistence
    def persist(self) -> None:
        """Write head/fork-choice/op-pool to the store so a restart
        resumes exactly here (persist_head/persist_fork_choice)."""
        from .persistence import save_chain

        save_chain(self)

    @classmethod
    def from_store(cls, store, spec, slot_clock, backend=None) -> "BeaconChain":
        """Resume from a persisted store (ClientGenesis::FromStore)."""
        from .persistence import load_chain

        return load_chain(store, spec, slot_clock, backend=backend)

    # ------------------------------------------------------------ slot tasks
    def per_slot_task(self) -> None:
        """(reference: beacon_chain.rs per_slot_task via timer)"""
        slot = self.current_slot()
        self.naive_aggregation_pool.prune(slot)
        self.naive_sync_pool.prune(slot)
        # sync observation sets are (slot, …)-keyed; retain a short window
        cutoff = slot - 3
        self.observed_sync_contributors = {
            k for k in self.observed_sync_contributors if k[0] >= cutoff
        }
        self.observed_sync_contributions = {
            k for k in self.observed_sync_contributions if k[0] >= cutoff
        }
        self.fork_choice.update_time(slot)


class VerifiedAttestation:
    __slots__ = ("attestation", "indexed")

    def __init__(self, attestation, indexed):
        self.attestation = attestation
        self.indexed = indexed


# ---------------------------------------------------------------- typestates


class GossipVerifiedBlock:
    """Cheap structural checks before the expensive pipeline
    (reference: block_verification.rs:638 GossipVerifiedBlock::new)."""

    def __init__(self, chain: BeaconChain, signed_block, block_root=None):
        self.signed_block = signed_block
        block = signed_block.message
        spec = chain.spec
        current_slot = chain.current_slot()

        if int(block.slot) > current_slot + FUTURE_SLOT_TOLERANCE:
            raise BlockError("block from the future")
        finalized_epoch, _ = chain.finalized_checkpoint()
        if int(block.slot) <= finalized_epoch * spec.preset.SLOTS_PER_EPOCH:
            raise BlockError("block older than finalization")
        parent_root = bytes(block.parent_root)
        if not chain.fork_choice.contains_block(parent_root):
            raise BlockError("unknown parent block")
        expected_fork = spec.fork_name_at_epoch(
            int(block.slot) // spec.preset.SLOTS_PER_EPOCH
        )
        if type(block).fork != expected_fork:
            raise BlockError(
                f"wrong fork: block {type(block).fork}, schedule {expected_fork}"
            )
        # check-only: recording happens post-verification in import_block
        if chain.observed_block_producers.is_known(
            int(block.slot), int(block.proposer_index)
        ):
            raise BlockError("proposer equivocation: slot already seen")

        self.block_root = (
            block_root if block_root is not None else block.hash_tree_root()
        )
        self.chain = chain


class ExecutionPendingBlock:
    """State transition + full signature verification
    (reference: block_verification.rs:1038 + SignatureVerifiedBlock)."""

    def __init__(self, chain: BeaconChain, gossip: GossipVerifiedBlock):
        signed_block = gossip.signed_block
        block = signed_block.message
        parent_root = bytes(block.parent_root)

        pre_state = chain.snapshot_cache.get_cloned(parent_root)
        if pre_state is None:
            pre_state = chain._state_for_block_root(parent_root)
        if pre_state is None:
            raise BlockError("missing pre-state for parent")
        state = pre_state.copy() if pre_state is chain._head.state else pre_state

        if int(state.slot) > int(block.slot):
            raise BlockError("parent state ahead of block")
        state = process_slots(state, int(block.slot), chain.spec)

        # expected proposer
        expected_proposer = h.get_beacon_proposer_index(state, chain.spec)
        if int(block.proposer_index) != expected_proposer:
            raise BlockError(
                f"wrong proposer: block {block.proposer_index}, "
                f"expected {expected_proposer}"
            )

        # full transition; ONE bulk signature batch incl. the proposal
        # (on the TPU backend: one fused multi-pairing per block)
        try:
            per_block_processing(
                state,
                signed_block,
                chain.spec,
                strategy=SignatureStrategy.VERIFY_BULK,
                get_pubkey=chain.pubkey_cache.as_getter(),
                backend=chain.backend,
            )
        except BlockProcessingError as e:
            raise BlockError(f"state transition failed: {e}") from e

        computed_root = chain.state_root_cache.state_root(state)
        if computed_root != bytes(block.state_root):
            raise BlockError("state root mismatch")

        self.signed_block = signed_block
        self.block_root = gossip.block_root
        self.post_state = state
        fork = state_fork_name(state)
        if fork == "bellatrix" and hasattr(block.body, "execution_payload"):
            from ..consensus.transition.block import is_execution_enabled

            if is_execution_enabled(state, block.body, chain.spec):
                payload = block.body.execution_payload
                self.execution_block_hash = bytes(payload.block_hash)
                if chain.execution_layer is not None:
                    # verify with the engine (execution_payload.rs
                    # notify_new_payload); INVALID payloads kill the block
                    from ..execution.execution_layer import payload_to_engine_json

                    status = chain.execution_layer.notify_new_payload(
                        payload_to_engine_json(payload)
                    )
                    if status == ExecutionStatus.INVALID:
                        raise BlockError("execution payload invalid")
                    self.execution_status = status
                else:
                    self.execution_status = ExecutionStatus.OPTIMISTIC
            else:
                self.execution_status = ExecutionStatus.IRRELEVANT
                self.execution_block_hash = None
        else:
            self.execution_status = ExecutionStatus.IRRELEVANT
            self.execution_block_hash = None
