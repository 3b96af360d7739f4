"""loadgen/scheduler: continuous cross-slot batching scheduler (ISSUE 15).

Compile-budget discipline: scheduling semantics (priority, preemption
exactly-once, tenant fairness, health-governed shedding, bounded
recorder memory) run on a VirtualClock with an injected verify seam —
no crypto, no compiles. The cache-aliasing tests use a host-side
sequential-key oracle (pk = sk·G1 with tiny sk, so verdict is a point
equality — no pairings). The one jax-dispatching test pins
batch_target=2 / K=2 / LHTPU_VERDICT_GROUPS=2 so it reuses the
(S=2, K=2, G=2) triage bucket tests/test_triage.py already pays for.
"""

import hashlib

import pytest

from lighthouse_tpu.common import health, resilience
from lighthouse_tpu.crypto.bls import api as bls_api
from lighthouse_tpu.loadgen import slo
from lighthouse_tpu.loadgen.scheduler import (
    CompositionCache,
    SchedulerConfig,
    StreamRunner,
    StreamScheduler,
    continuous_digest,
)
from lighthouse_tpu.loadgen.serve import VirtualClock
from lighthouse_tpu.loadgen.traffic import (
    LoadPayload,
    TimedEvent,
    TrafficConfig,
    TrafficGenerator,
)
from lighthouse_tpu.network.processor import WorkEvent, WorkType, work_class

# ---------------------------------------------------------------- fixtures


class _P:
    """Minimal payload standing in for LoadPayload in timing tests."""

    def __init__(self, seq, expected=True):
        self.seq = seq
        self.sig_set = object()
        self.expected = expected


def _ev(seq, wt=WorkType.GOSSIP_ATTESTATION, peer="p0"):
    return WorkEvent(work_type=wt, payload=_P(seq), peer_id=peer)


def _sched(verify=None, **cfg):
    cfg.setdefault("cache", False)  # fake payloads have no signing_keys
    return StreamScheduler(
        SchedulerConfig(**cfg), clock=VirtualClock(),
        verify=verify or (lambda sets: [True] * len(sets)),
    )


def _msg(tag):
    return hashlib.sha256(tag.encode()).digest()


def _fixture_oracle(seen=None, max_sk=256):
    """Exact BLS verification for sequential-key fixture sets.

    Pool key i has sk = i+1, so an aggregate pubkey is (Σsk)·G1 with a
    small scalar: recover Σsk by table lookup and check the point
    equality sig == (Σsk)·H(m) — true BLS semantics (e(sig, G) =
    e(H(m), Σpk) for pk = sk·G), no pairings, no device."""
    from lighthouse_tpu.crypto.bls.curve import g1_generator
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2

    g = g1_generator()
    table, acc = {}, g
    for sk in range(1, max_sk + 1):
        table[bls_api.PublicKey(acc).to_bytes()] = sk
        acc = acc.add(g)
    memo = {}

    def verify(sets):
        if seen is not None:
            seen.append([len(s.signing_keys) for s in sets])
        out = []
        for s in sets:
            agg = bls_api.aggregate_pubkeys(list(s.signing_keys))
            sk = table[agg.to_bytes()]
            pt = memo.get(s.message)
            if pt is None:
                pt = memo[s.message] = hash_to_g2(s.message)
            out.append(s.signature.point == pt.mul(sk))
        return out

    return verify


def _agg_event(seq, gen, members, msg, poisoned, peer="peer-0", slot=0):
    payload = LoadPayload(
        seq=seq, kind="aggregate", slot=slot,
        sig_set=gen._sig_set(members, msg, poisoned),
        expected=not poisoned, message=msg, members=members,
    )
    return WorkEvent(
        work_type=WorkType.GOSSIP_AGGREGATE, payload=payload,
        peer_id=peer, seen_slot=slot,
    )


# ------------------------------------------------------- priority classes


def test_every_work_type_has_a_class():
    for wt in WorkType:
        assert work_class(wt) is not None


def test_class_priority_dispatch_order():
    """With everything due at once, dispatch order is BLOCK, AGGREGATE,
    ATTESTATION, SYNC — regardless of offer order (which is reversed
    here on purpose)."""
    sched = _sched(
        batch_target=64, block_deadline_ms=0.0,
        agg_deadline_ms=0.0, att_deadline_ms=0.0, sync_deadline_ms=0.0,
    )
    stream = [
        TimedEvent(t=0.0, event=_ev(0, WorkType.GOSSIP_SYNC_SIGNATURE)),
        TimedEvent(t=0.0, event=_ev(1, WorkType.GOSSIP_ATTESTATION)),
        TimedEvent(t=0.0, event=_ev(2, WorkType.GOSSIP_AGGREGATE)),
        TimedEvent(t=0.0, event=_ev(3, WorkType.GOSSIP_BLOCK)),
    ]
    dispatched = []
    orig = sched._dispatch_batch
    sched._dispatch_batch = lambda cls, items: (
        dispatched.append(cls.value), orig(cls, items))[-1]
    report = sched.run(stream)
    assert dispatched == ["block", "aggregate", "attestation", "sync"]
    assert report["events_served"] == 4
    assert report["accounting"]["balanced"]


def test_partial_batch_fires_at_class_deadline():
    """A partial aggregate batch dispatches AT agg_deadline_ms on the
    virtual clock — the oldest event's recorded latency is exactly the
    deadline."""
    sched = _sched(batch_target=100, agg_deadline_ms=50.0)
    sched.run([
        TimedEvent(t=0.0, event=_ev(0, WorkType.GOSSIP_AGGREGATE)),
        TimedEvent(t=0.0, event=_ev(1, WorkType.GOSSIP_AGGREGATE)),
    ])
    overall = sched.recorder.summary()["overall"]
    assert overall["count"] == 2
    assert overall["max_ms"] == pytest.approx(50.0, abs=0.1)


# ------------------------------------------------------------- preemption


def test_block_preempts_window_and_requeues_exactly_once(monkeypatch):
    """A block arriving inside an attestation coalescing window preempts
    the remainder, which re-enqueues EXACTLY once: a batch containing a
    re-enqueued event is never preempted again (no starvation), every
    event is served once, and the outcome identity stays balanced."""
    monkeypatch.setattr(StreamScheduler, "_quantum", lambda self: 2)
    sched = _sched(
        batch_target=8, att_deadline_ms=0.0, dispatch_ms=10.0,
    )
    stream = [TimedEvent(t=0.0, event=_ev(i)) for i in range(8)]
    # blocks land mid-window: after chunk 1 (t=10ms) and during the
    # re-dispatched remainder (t=25ms) — the second must NOT preempt.
    stream.append(
        TimedEvent(t=0.005, event=_ev(100, WorkType.GOSSIP_BLOCK)))
    stream.append(
        TimedEvent(t=0.025, event=_ev(101, WorkType.GOSSIP_BLOCK)))
    report = sched.run(stream)
    assert report["sched"]["preempted_batches"] == 1
    assert report["sched"]["preempted_by_class"] == {"attestation": 1}
    assert report["sched"]["requeued_by_class"] == {"attestation": 6}
    # exactly-once: all 10 events served, none twice, none lost
    assert report["events_served"] == 10
    assert len(sched.verdicts) == 10
    assert report["accounting"]["balanced"]
    assert report["sched"]["block"]["shed"] == 0
    assert report["sched"]["block"]["dropped"] == 0


def test_preemption_classified_transient():
    cat, kind = resilience.classify(
        resilience.BatchPreempted("window abandoned"))
    assert cat == resilience.TRANSIENT
    assert kind == "preempted"


def test_block_batch_is_never_preemptible(monkeypatch):
    """A block batch runs to completion even if another block arrives
    mid-dispatch."""
    monkeypatch.setattr(StreamScheduler, "_quantum", lambda self: 1)
    sched = _sched(batch_target=4, dispatch_ms=10.0)
    stream = [
        TimedEvent(t=0.0, event=_ev(0, WorkType.GOSSIP_BLOCK)),
        TimedEvent(t=0.0, event=_ev(1, WorkType.GOSSIP_BLOCK)),
        TimedEvent(t=0.005, event=_ev(2, WorkType.GOSSIP_BLOCK)),
    ]
    report = sched.run(stream)
    assert report["sched"]["preempted_batches"] == 0
    assert report["events_served"] == 3


# -------------------------------------------------------- tenant fairness


def test_round_robin_interleaves_tenants():
    """One hot peer cannot fill a batch: lanes drain round-robin."""
    sched = _sched(batch_target=8, att_deadline_ms=0.0)
    for i in range(6):
        sched.offer(_ev(i, peer="hot"), t=0.0)
    for i in range(2):
        sched.offer(_ev(100 + i, peer="quiet"), t=0.0)
    batch = sched._form(work_class(WorkType.GOSSIP_ATTESTATION))
    got = [ev.payload.seq for _, ev in batch]
    # RR order: hot, quiet, hot, quiet, then hot drains alone
    assert got == [0, 100, 1, 101, 2, 3, 4, 5]


def test_tenant_quota_sheds_before_watermark():
    """Admission: a tenant is capped at quota×watermark before the
    class watermark engages; the class watermark then sheds everyone."""
    sched = _sched(batch_target=64, queue_cap=32, tenant_quota=0.25)
    # attestation watermark = 32 * 0.50 = 16; tenant quota = 4
    for i in range(6):
        sched.offer(_ev(i, peer="noisy"), t=0.0)
    assert sched.shed_by_reason == {"tenant_quota": 2}
    for i in range(5):
        sched.offer(_ev(10 + i, peer="other"), t=0.0)
    # well below the watermark, the second tenant's quota still binds
    assert sched.shed_by_reason == {"tenant_quota": 3}
    for i in range(8):
        sched.offer(_ev(20 + i, peer=f"p{i}"), t=0.0)
    assert sched.admitted == 16  # depth == watermark now
    assert not sched.offer(_ev(40, peer="third"), t=0.0)
    assert sched.shed_by_reason == {"tenant_quota": 3, "watermark": 1}
    assert sched.shed_by_tenant == {"noisy": 2, "other": 1, "third": 1}


def test_blocks_have_no_quota_and_never_shed():
    sched = _sched(batch_target=64, queue_cap=4, tenant_quota=0.25)
    for i in range(64):
        assert sched.offer(_ev(i, WorkType.GOSSIP_BLOCK, peer="one"),
                           t=0.0)
    assert sched.shed_by_class.get("block", 0) == 0
    assert sched.lanes[work_class(WorkType.GOSSIP_BLOCK)].dropped == 0


# ------------------------------------------------- health-governed shedding


def test_degraded_halves_watermarks(monkeypatch):
    monkeypatch.setattr(health, "current_state", lambda: health.DEGRADED)
    sched = _sched(batch_target=64, queue_cap=16, tenant_quota=1.0)
    # attestation watermark 8 → halved to 4 under DEGRADED
    for i in range(5):
        sched.offer(_ev(i, peer=f"p{i}"), t=0.0)
    assert sched.admitted == 4
    assert sched.shed_by_reason == {"watermark": 1}


def test_critical_is_blocks_only(monkeypatch):
    monkeypatch.setattr(health, "current_state", lambda: health.CRITICAL)
    sched = _sched(batch_target=64, queue_cap=16)
    assert not sched.offer(_ev(0, WorkType.GOSSIP_ATTESTATION), t=0.0)
    assert not sched.offer(_ev(1, WorkType.GOSSIP_AGGREGATE), t=0.0)
    assert not sched.offer(_ev(2, WorkType.GOSSIP_SYNC_SIGNATURE), t=0.0)
    assert sched.offer(_ev(3, WorkType.GOSSIP_BLOCK), t=0.0)
    assert sched.shed_by_reason == {"blocks_only": 3}
    assert sched.shed_by_class.get("block", 0) == 0


# -------------------------------------------------- composition cache


def test_cross_slot_cache_folds_and_never_aliases_poisoned_duplicate():
    """The aliasing trap: three aggregates share ONE committee
    composition across slots — two honest, one with a signature over a
    tampered message. The composition cache hits on all repeats (the
    cross-slot dedup), the fold hands the verifier single-pubkey sets,
    and the poisoned duplicate still verdicts False: nothing signature-
    or message-dependent is ever cached, so a hit cannot alias."""
    gen = TrafficGenerator(TrafficConfig(key_pool=8))
    members = (0, 1)
    m0, m1 = _msg("slot-0-head"), _msg("slot-1-head")
    seen = []
    sched = StreamScheduler(
        SchedulerConfig(batch_target=64, agg_deadline_ms=0.0, cache=True),
        clock=VirtualClock(), verify=_fixture_oracle(seen=seen),
    )
    stream = [
        TimedEvent(t=0.0, event=_agg_event(0, gen, members, m0, False)),
        TimedEvent(t=0.0, event=_agg_event(1, gen, members, m1, False,
                                           slot=1)),
        TimedEvent(t=0.0, event=_agg_event(2, gen, members, m0, True,
                                           slot=2)),
    ]
    report = sched.run(stream)
    assert sched.verdicts == {0: True, 1: True, 2: False}
    assert report["verdicts"]["mismatches"] == 0
    cache = report["sched"]["cache"]
    assert cache == {
        "enabled": True, "entries": 1, "cap": 4096, "hits": 2,
        "misses": 1, "bypass": 0, "faults": 0, "fault_kinds": {},
    }
    # the verifier really saw folded single-pubkey sets
    assert [k for chunk in seen for k in chunk] == [1, 1, 1]


def test_cache_fault_degrades_to_identity_not_a_verdict(monkeypatch):
    """An injected fault at the sched_cache stage falls back to the
    identity transform: the verifier sees the original K-pubkey set and
    every verdict is still correct."""
    monkeypatch.setenv("LHTPU_FAULT_INJECT", "sched_cache:assert:1")
    resilience.rearm_faults()
    try:
        gen = TrafficGenerator(TrafficConfig(key_pool=8))
        members = (2, 5)
        seen = []
        sched = StreamScheduler(
            SchedulerConfig(batch_target=64, agg_deadline_ms=0.0,
                            cache=True),
            clock=VirtualClock(), verify=_fixture_oracle(seen=seen),
        )
        stream = [
            TimedEvent(t=0.0, event=_agg_event(
                0, gen, members, _msg("m"), False)),
            TimedEvent(t=0.0, event=_agg_event(
                1, gen, members, _msg("m"), True, slot=1)),
        ]
        report = sched.run(stream)
        assert sched.verdicts == {0: True, 1: False}
        assert report["verdicts"]["mismatches"] == 0
        cache = report["sched"]["cache"]
        assert cache["faults"] == 1
        assert cache["fault_kinds"] == {"AssertionError": 1}
        # first set rode through unfolded (K=2), second folded after a
        # fresh aggregate (miss): the fallback is per-set, not sticky
        assert [k for chunk in seen for k in chunk] == [2, 1]
        assert cache["misses"] == 1
    finally:
        monkeypatch.delenv("LHTPU_FAULT_INJECT")
        resilience.rearm_faults()


def test_cache_lru_eviction_respects_cap():
    gen = TrafficGenerator(TrafficConfig(key_pool=8))
    cache = CompositionCache(cap=2, enabled=True)
    for members in ((0, 1), (2, 3), (4, 5)):
        cache.fold(gen._sig_set(members, _msg("m"), False))
    rep = cache.report()
    assert rep["entries"] == 2
    assert rep["misses"] == 3
    # (0,1) was evicted: folding it again is a miss, not a hit
    cache.fold(gen._sig_set((0, 1), _msg("m"), False))
    assert cache.report()["misses"] == 4


# ------------------------------------------------- bounded recorder memory


def test_recorder_memory_stays_flat_on_long_stream():
    """Regression (ISSUE 15 satellite): the recorder retains at most
    ``cap`` samples per work type over an arbitrarily long stream while
    the counts stay exact totals — RSS flat, no leak-sentinel trips."""
    rec = slo.LatencyRecorder(cap=128)
    sizes = []
    for i in range(10_000):
        rec.observe("gossip_attestation", i * 1e-3)
        if i % 1000 == 999:
            sizes.append(rec.window_size())
    assert max(sizes) <= 128
    assert sizes[-1] == sizes[0]  # flat, not growing
    assert rec.count() == 10_000
    s = rec.summary()["overall"]
    assert s["count"] == 10_000
    assert s["window"] == 128
    # quantiles exact within the window (last 128 observations)
    assert s["max_ms"] == pytest.approx(9999.0)
    assert s["p50_ms"] == pytest.approx(
        slo.quantile([i * 1.0 for i in range(9872, 10_000)], 0.50))


def test_scheduler_stream_holds_recorder_window_bounded(monkeypatch):
    monkeypatch.setenv("LHTPU_SLO_SAMPLE_CAP", "64")
    sched = _sched(batch_target=32, att_deadline_ms=0.0, queue_cap=1 << 16)
    stream = [
        TimedEvent(t=i * 1e-4, event=_ev(i, peer=f"p{i % 7}"))
        for i in range(2000)
    ]
    report = sched.run(stream)
    assert sched.recorder.window_size() <= 64
    assert report["events_served"] == 2000
    assert report["slo"]["per_class"]["attestation"]["count"] == 2000
    assert report["slo"]["per_class"]["attestation"]["window"] <= 64


# ------------------------------------------------------------ stream runner


def test_stream_runner_spans_epochs_with_unique_seqs():
    traffic = TrafficConfig(
        validators=64, slots=2, seconds_per_slot=2.0,
        committees_per_slot=2, committee_size=2,
        unaggregated_per_slot=4, sync_per_slot=2, blocks=True,
        key_pool=8, seed=3, peers=4,
    )
    rows = []
    runner = StreamRunner(
        traffic, 2,
        SchedulerConfig(batch_target=4, agg_deadline_ms=10.0,
                        att_deadline_ms=10.0, sync_deadline_ms=10.0,
                        cache=False),
        clock=VirtualClock(),
        verify=lambda sets: [True] * len(sets),
        chaos="", emit=rows.append,
    )
    # ground truth is not checked here (seam returns all-True); the
    # runner mechanics are: epoch rows, seq renumbering, accounting
    report = runner.run()
    assert len(rows) == 2
    assert report["stream"]["epochs"] == 2
    assert report["events_offered"] == report["stream"]["events"]
    assert report["accounting"]["balanced"]
    assert sum(r["offered"] for r in rows) == report["events_offered"]
    digest = report["stream"]["verdict_digest"]
    assert isinstance(digest, str) and len(digest) == 64
    assert digest != continuous_digest({})  # covers the verdict content
    # epoch 1 seqs renumbered past the stride — no collisions, so the
    # verdict dict holds one entry per served event
    assert report["verdicts"]["served"] == report["events_served"]


@pytest.fixture
def triage_env(monkeypatch):
    monkeypatch.setenv("LHTPU_VERDICT_GROUPS", "2")
    monkeypatch.setenv("LHTPU_PIPELINE", "0")
    monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
    resilience.reset()
    yield
    resilience.reset()


def test_stream_chaos_digest_parity_jax(triage_env):
    """The acceptance contract at unit scale: a 2-epoch poisoned stream
    through the real triage backend with a transient injected mid-epoch
    finishes with a verdict digest bit-identical to the chaos-free
    replay, zero mismatches against ground truth, and zero blocks shed.

    Compile-bucket pinned: aggregate-only K=2 traffic, batch_target=2,
    VG=2 → the (S=2, K=2, G=2) bucket test_triage.py already pays for;
    counts stay even so no partial (S=1) batch ever forms."""
    traffic = TrafficConfig(
        validators=64, slots=2, seconds_per_slot=2.0,
        committees_per_slot=2, committee_size=2,
        unaggregated_per_slot=0, sync_per_slot=0, blocks=False,
        poison_rate=0.25, key_pool=8, seed=11, peers=4,
    )
    cfg = SchedulerConfig(
        batch_target=2, agg_deadline_ms=60_000.0, cache=False,
    )

    def run(chaos):
        return StreamRunner(
            traffic, 2, cfg, clock=VirtualClock(), backend="jax",
            chaos=chaos,
        ).run()

    chaos_rep = run("0:dispatch:aborted:1")
    resilience.reset()
    clean_rep = run("")
    for rep in (chaos_rep, clean_rep):
        assert rep["verdicts"]["mismatches"] == 0
        assert rep["accounting"]["balanced"]
        assert rep["sched"]["block"]["shed"] == 0
        assert rep["events_served"] == rep["events_offered"] == 8
    assert (chaos_rep["stream"]["verdict_digest"]
            == clean_rep["stream"]["verdict_digest"])
    assert chaos_rep["verdicts"]["invalid"] >= 1  # poison really landed
