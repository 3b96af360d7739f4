"""Registry-scale slot drive: config #5 THROUGH THE CHAIN.

The bench's slot mode (bench.py slot_mode) measures the BLS layer on a
fixture; this module runs the same scale through the real node stack —
a BeaconChain at N validators (device-built blsrt registry, lazy pubkey
cache), gossip-shaped SignedAggregateAndProof objects entering via the
BeaconProcessor's aggregate queue, the Router's batch handler verifying
all of a slot's aggregates in ONE device batch (3 signature sets per
aggregate), and fork choice observing every attester — head update out
(VERDICT r3 item 9; reference: beacon_processor/mod.rs:1004-1070 worker
pools + attestation_verification/batch.rs).

Key scale techniques:
  * sequential-key registry (sk_i = i+1): pubkeys from one device table
    build; a committee's aggregate signature is (sum sk_i mod r)*H(m);
  * aggregate/selection/aggregator signatures via ``bulk_g2_mul`` — one
    hash per distinct message, scalar multiplications batched on the
    device G2 kernel in fixed-width calls (host muls off-TPU);
  * the aggregator search evaluates candidates' selection proofs until
    one passes is_aggregator, exactly the VC's duty check.
"""

from __future__ import annotations

import time

import numpy as np

from ..common.slot_clock import ManualSlotClock
from ..consensus import helpers as h
from ..consensus.config import ChainSpec, compute_signing_root
from ..consensus.genesis import scale_genesis_state
from ..consensus.ssz import uint64
from ..consensus.types import AttestationData, spec_types
from ..crypto.bls.api import AggregateSignature, Signature
from ..crypto.bls.constants import R as CURVE_ORDER
from ..crypto.bls.curve import g2_to_compressed
from ..crypto.bls.hash_to_curve import hash_to_g2
from ..store.hot_cold import HotColdDB, StoreConfig
from ..store.kv import MemoryStore
from .beacon_chain import BeaconChain
from .pubkey_cache import ValidatorPubkeyCache


def slot_shape(n_validators: int, spec: ChainSpec) -> tuple[int, int]:
    """(committees_per_slot, committee_size) for a registry of
    ``n_validators`` active validators — the spec's
    get_committee_count_per_slot formula without needing a state.
    loadgen/traffic.py seeds its per-slot committee structure from
    this; at mainnet 1M validators: 64 committees of ~488."""
    p = spec.preset
    committees = max(
        1,
        min(
            p.MAX_COMMITTEES_PER_SLOT,
            n_validators // p.SLOTS_PER_EPOCH // p.TARGET_COMMITTEE_SIZE,
        ),
    )
    size = max(1, n_validators // (p.SLOTS_PER_EPOCH * committees))
    return committees, size


# Lanes per device G2 scalar-mul call: every call pads to this, so a
# whole run compiles ONE program for it (a chip compile costs seconds).
BULK_LANES = 64


def bulk_g2_mul(point, scalars: list[int]):
    """[k]P for one G2 point and many scalars.

    On TPU: fused scalar-mul kernel calls of BULK_LANES lanes each
    (ops/tkernel_calls.scalar_mul_g2_t). Off-TPU the kernel would run
    in minutes-slow interpret mode, so small batches fall back to host
    muls — identical results, oracle-tested."""
    import jax

    if jax.default_backend() != "tpu" or len(scalars) < 8:
        return [point.mul(s) for s in scalars]
    out = []
    for lo in range(0, len(scalars), BULK_LANES):
        out += _bulk_g2_mul_lanes(point, scalars[lo:lo + BULK_LANES])
    return out


def _bulk_g2_mul_lanes(point, scalars: list[int]):
    import jax.numpy as jnp

    from ..ops import points as pts
    from ..ops.tkernel_calls import scalar_mul_g2_t, to_affine_g2_t

    n = len(scalars)
    scalars = list(scalars) + [1] * (BULK_LANES - n)
    px, py, _ = pts.g2_to_dev([point])
    # transposed layout: [2, 48] coefficient planes broadcast over lanes
    x = jnp.broadcast_to(jnp.asarray(px[0])[:, :, None], (2, 48, BULK_LANES))
    y = jnp.broadcast_to(jnp.asarray(py[0])[:, :, None], (2, 48, BULK_LANES))
    inf = jnp.zeros((1, BULK_LANES), jnp.int32)
    bits = np.zeros((256, BULK_LANES), np.int32)
    for j, s in enumerate(scalars):
        for b in range(256):
            bits[b, j] = (s >> (255 - b)) & 1
    acc = scalar_mul_g2_t(x, y, inf, jnp.asarray(bits))
    ax, ay, ainf = to_affine_g2_t(acc)
    return pts.g2_from_dev(
        np.moveaxis(np.asarray(ax), -1, 0),
        np.moveaxis(np.asarray(ay), -1, 0),
        np.asarray(ainf),
    )[:n]


class ScaleChain:
    """A chain at registry scale plus the processor/router plumbing."""

    def __init__(self, n_validators: int, spec: ChainSpec,
                 genesis_time: int = 1_600_000_000):
        from .. import blsrt

        t0 = time.perf_counter()
        self.table = blsrt.build_sequential_table(n_validators)
        self.table_build_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        self.compressed = blsrt.compressed_pubkeys(self.table)
        self.compress_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        state = scale_genesis_state(self.compressed, genesis_time, spec)
        self.state_build_s = time.perf_counter() - t0

        self.spec = spec
        self.types = spec_types(spec.preset)
        self.slot_clock = ManualSlotClock(
            genesis_time, spec.SECONDS_PER_SLOT
        )
        cache = ValidatorPubkeyCache.from_device_table(
            self.table, self.compressed
        )
        blsrt.set_device_table(self.table)

        t0 = time.perf_counter()
        hot_cold = HotColdDB(
            MemoryStore(), spec,
            StoreConfig(slots_per_restore_point=spec.preset.SLOTS_PER_EPOCH),
        )
        self.chain = BeaconChain.from_genesis(
            hot_cold, state, spec, self.slot_clock,
            backend="jax", pubkey_cache=cache,
        )
        self.chain_init_s = time.perf_counter() - t0

        from ..network.processor import BeaconProcessor
        from ..network.router import Router

        self.processor = BeaconProcessor(attestation_batch_size=4096)
        self.router = Router(
            self.chain, self.processor, peer_manager=_NullPeerManager(),
            publish=None,
        )

    # ------------------------------------------------------- slot load
    def make_slot_aggregates(self, slot: int):
        """Gossip-shaped SignedAggregateAndProof for EVERY committee of
        ``slot``: full participation, real signatures from the
        sequential-key registry."""
        t = self.types
        spec = self.spec
        state = self.chain.head().state
        epoch = slot // spec.preset.SLOTS_PER_EPOCH
        n_comm = h.get_committee_count_per_slot(state, epoch, spec)

        att_domain = spec.get_domain(
            spec.DOMAIN_BEACON_ATTESTER, epoch, state.fork,
            state.genesis_validators_root,
        )
        sel_domain = spec.get_domain(
            spec.DOMAIN_SELECTION_PROOF, epoch, state.fork,
            state.genesis_validators_root,
        )
        agg_domain = spec.get_domain(
            spec.DOMAIN_AGGREGATE_AND_PROOF, epoch, state.fork,
            state.genesis_validators_root,
        )
        from ..consensus.signature_sets import signing_root_of_root

        slot_root = signing_root_of_root(
            uint64.hash_tree_root(slot), sel_domain
        )
        h_slot = hash_to_g2(slot_root)

        # One attestation production per slot: it advances a copy of the
        # head state to ``slot``, which at 1M validators takes seconds;
        # once per committee it kept the chip's host busy for over 20
        # minutes without finishing a slot (PR 21). Committees differ only
        # in their index and come from the chain's shuffling cache (the
        # committees it verifies against).
        base = self.chain.produce_unaggregated_attestation(slot, 0).data
        out = []
        for ci in range(n_comm):
            committee = self.chain._committee_at(state, slot, ci, epoch)
            data = AttestationData(
                slot=slot, index=ci,
                beacon_block_root=base.beacon_block_root,
                source=base.source, target=base.target,
            )
            att_root = compute_signing_root(data, att_domain)
            sk_sum = sum(int(i) + 1 for i in committee) % CURVE_ORDER
            agg_sig = AggregateSignature(hash_to_g2(att_root).mul(sk_sum))

            full = t.Attestation(
                aggregation_bits=[True] * len(committee), data=data,
                signature=g2_to_compressed(agg_sig.point),
            )

            # aggregator search: first member whose selection proof
            # passes is_aggregator (the VC duty check). Chunked over the
            # WHOLE committee: at mainnet-1M committee sizes (~488) the
            # modulo is ~30, so a fixed 64-candidate cap fails some
            # committee almost every slot.
            agg_index = None
            proof = None
            members = [int(i) for i in committee]
            for lo in range(0, len(members), 64):
                cand = members[lo:lo + 64]
                proofs = bulk_g2_mul(
                    h_slot, [(i + 1) % CURVE_ORDER for i in cand]
                )
                for vi, pt in zip(cand, proofs):
                    pb = g2_to_compressed(pt)
                    if h.is_aggregator(len(committee), pb, spec):
                        agg_index, proof = vi, pb
                        break
                if agg_index is not None:
                    break
            if agg_index is None:
                # P ~ (1-1/modulo)^len: ~3e-8 at len 488; committees
                # without an elected aggregator simply have no aggregate
                # that slot (the spec allows this) — skip it.
                continue

            msg = t.AggregateAndProof(
                aggregator_index=agg_index, aggregate=full,
                selection_proof=proof,
            )
            outer_root = compute_signing_root(msg, agg_domain)
            outer = hash_to_g2(outer_root).mul((agg_index + 1) % CURVE_ORDER)
            out.append(t.SignedAggregateAndProof(
                message=msg, signature=g2_to_compressed(outer)
            ))
        return out

    def drive_slot(self, aggregates) -> dict:
        """Feed one slot's aggregates through the processor queues and
        drain — the gossip worker path — then report head/fork-choice
        effects and timing."""
        from ..network.processor import WorkEvent, WorkType

        t0 = time.perf_counter()
        for sa in aggregates:
            self.processor.send(WorkEvent(
                work_type=WorkType.GOSSIP_AGGREGATE, payload=sa,
                peer_id=None,
            ))
        self.processor.process_pending()
        wall = time.perf_counter() - t0
        return {
            "slot_wall_s": wall,
            "aggregates_verified": self.router.stats["aggregates_verified"],
            "attestations_rejected": self.router.stats["attestations_rejected"],
        }


class _NullPeerManager:
    def report_peer(self, peer_id, action):
        pass

    def is_connected(self, peer_id):
        return False

