"""Pipelined microbatch dispatch (ISSUE 4).

Covers the chunking policy, verdict equality between the pipelined and
single-shot paths (including under fault injection), the vectorized
Montgomery pack golden contract, the cross-call input caches, and the
new metrics surface.

Compile-bucket budget: the 4-set fixture alternates single-pubkey and
2-key aggregate sets, so pipelined chunks of 2 land in the (S=2, K=2)
bucket the rest of the suite already pays for; the single-shot
comparison adds ONE (S=4, K=2) compile for the whole module.

Named ``test_zz_`` so it collects last: the device-integration tests
here cost whole seconds of CPU-device verify each, and under a CI
wall-clock budget they must spend leftover time, not crowd out the
broader suite.
"""

import random

import numpy as np
import pytest

from lighthouse_tpu import blsrt
from lighthouse_tpu import jax_backend as jb
from lighthouse_tpu.common import pipeline, resilience, tracing
from lighthouse_tpu.crypto.bls.api import (
    AggregateSignature,
    SecretKey,
    SignatureSet,
    verify_signature_sets_python,
)
from lighthouse_tpu.crypto.bls.backends import get_backend

SKS = [SecretKey.from_int(i + 31) for i in range(6)]
PKS = [sk.public_key() for sk in SKS]
M0 = b"\x33" * 32
M1 = b"\x44" * 32
M_BAD = b"\x55" * 32


def _mixed_sets(bad=()):
    """4 sets alternating [single, 2-key agg, single, 2-key agg];
    positions in ``bad`` get a signature over the wrong message."""
    sets = []
    for i in range(4):
        m = M0 if i % 2 == 0 else M1
        signed = M_BAD if i in bad else m
        if i % 2 == 0:
            sk = SKS[i // 2]
            sets.append(
                SignatureSet.single_pubkey(sk.sign(signed), sk.public_key(), m)
            )
        else:
            a, b = SKS[2 + i], SKS[3 + (i % 2)]
            agg = AggregateSignature.aggregate([a.sign(signed), b.sign(m)])
            sets.append(
                SignatureSet.multiple_pubkeys(
                    agg, [a.public_key(), b.public_key()], m
                )
            )
    return sets


def _pipeline_env(monkeypatch, on: bool):
    monkeypatch.setenv("LHTPU_PIPELINE", "1" if on else "0")
    monkeypatch.setenv("LHTPU_PIPELINE_MIN_SETS", "2")
    monkeypatch.setenv("LHTPU_PIPELINE_CHUNK", "2")


# ------------------------------------------------------------- policy


def test_pipeline_policy_knobs(monkeypatch):
    monkeypatch.delenv("LHTPU_PIPELINE_CHUNK", raising=False)
    monkeypatch.delenv("LHTPU_PIPELINE_MIN_SETS", raising=False)
    monkeypatch.setenv("LHTPU_PIPELINE", "0")
    assert not pipeline.should_pipeline(4096)
    monkeypatch.setenv("LHTPU_PIPELINE", "1")
    assert not pipeline.should_pipeline(pipeline.min_sets() - 1)
    assert pipeline.should_pipeline(2048)
    assert pipeline.chunk_size(2048) == 512  # next_pow2(2048) // 4
    assert pipeline.chunk_size(600) == 256   # floor at MIN_CHUNK
    monkeypatch.setenv("LHTPU_PIPELINE_CHUNK", "300")
    assert pipeline.chunk_size(2048) == 512  # rounded to a power of two
    monkeypatch.setenv("LHTPU_PIPELINE_CHUNK", "4")
    chunks = pipeline.split(list(range(10)))
    assert [len(c) for c in chunks] == [4, 4, 2]
    assert [c[0] for c in chunks] == [0, 4, 8]


# ------------------------------------------- verdict equality (tentpole)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pipeline_matches_single_shot(monkeypatch, seed):
    """Bit-identical verdicts across LHTPU_PIPELINE=0/1 on randomized
    valid/invalid mixed batches (the tentpole contract)."""
    rng = random.Random(seed)
    bad = tuple(i for i in range(4) if rng.random() < 0.4)
    sets = _mixed_sets(bad)
    be = get_backend("jax")
    _pipeline_env(monkeypatch, on=False)
    v_single = be.verify_signature_sets(sets)
    _pipeline_env(monkeypatch, on=True)
    v_pipe = be.verify_signature_sets(sets)
    assert v_single == v_pipe == (not bad)
    assert be.last_path.endswith("+pipeline")
    if seed == 0 and not bad:
        assert verify_signature_sets_python(sets) == v_single


@pytest.mark.parametrize(
    "spec,expect",
    [
        ("hash_to_curve:aborted:1", "retried"),
        ("device_sync:aborted:1", "retried"),
        ("dispatch:mosaic:1", "degraded"),
    ],
)
def test_pipeline_matches_under_fault_injection(monkeypatch, spec, expect):
    """A chunk hitting a transient is retried in-stage; a permanent
    fault trips the breaker and the chunk degrades down the ladder —
    either way the verdict matches the single-shot path."""
    sets = _mixed_sets()
    be = get_backend("jax")
    _pipeline_env(monkeypatch, on=True)
    monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
    resilience.reset()
    retries0 = sum(v for _, v in resilience.RETRIES_TOTAL.items())
    degraded0 = sum(v for _, v in resilience.DEGRADED_TOTAL.items())
    monkeypatch.setenv("LHTPU_FAULT_INJECT", spec)
    try:
        verdict = be.verify_signature_sets(sets)
    finally:
        monkeypatch.delenv("LHTPU_FAULT_INJECT")
    retries = sum(v for _, v in resilience.RETRIES_TOTAL.items()) - retries0
    degraded = (
        sum(v for _, v in resilience.DEGRADED_TOTAL.items()) - degraded0
    )
    assert verdict is True
    if expect == "retried":
        assert retries >= 1 and degraded == 0
    else:
        assert degraded >= 1
    resilience.reset()
    _pipeline_env(monkeypatch, on=False)
    assert be.verify_signature_sets(sets) is True


class _FailingForce:
    """Device verdict stand-in whose force raises a transient once,
    then (if reached again) resolves True."""

    def __init__(self):
        self.raised = False

    def __bool__(self):
        if not self.raised:
            self.raised = True
            raise ConnectionError("socket reset during force")
        return True


def test_force_pipelined_redispatch_failure_degrades(monkeypatch):
    """If the transient-retry re-dispatch itself dies (same device
    fault that poisoned the force), the pipelined force degrades every
    pending chunk down the ladder instead of raising out of
    verify_signature_sets."""
    monkeypatch.setenv("LHTPU_RESILIENCE", "1")
    monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
    resilience.reset()
    be = jb.JaxBackend()
    calls = {"dispatch": 0, "resilient": []}

    def boom(chunk, path_override=None):
        calls["dispatch"] += 1
        raise ConnectionError("connection reset: device still down")

    monkeypatch.setattr(be, "_dispatch", boom)
    monkeypatch.setattr(
        be,
        "_verify_resilient",
        lambda c: calls["resilient"].append(c) or True,
    )
    pending = [["chunk0"], ["chunk1"]]
    assert be._force_pipelined(_FailingForce(), pending, {}) is True
    assert calls["dispatch"] == 1  # first re-dispatch raised
    assert calls["resilient"] == pending
    resilience.reset()


def test_force_pipelined_all_bool_recovery_records_success(monkeypatch):
    """A transient force failure recovered entirely by host-bool
    re-dispatches records a breaker success, like _verify_once's
    recovered calls do."""
    monkeypatch.setenv("LHTPU_RESILIENCE", "1")
    monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
    resilience.reset()
    be = jb.JaxBackend()
    monkeypatch.setattr(
        be, "_dispatch", lambda chunk, path_override=None: True
    )
    rung = be._ladder()[0]
    br = resilience.breaker(rung)
    br.record_failure()  # pre-existing strike the recovery must clear
    assert be._force_pipelined(_FailingForce(), [["c0"], ["c1"]], {}) is True
    assert br._failures == 0 and br.state_name == "closed"
    resilience.reset()


# ----------------------------------------------- vectorized pack golden


def test_mont_batch_vectorized_matches_reference():
    """The float64-matrix Montgomery limbification is byte-identical to
    the original per-int bigint loop (dtype, shape, every limb)."""
    from lighthouse_tpu.crypto.bls.constants import P
    from lighthouse_tpu.ops.points import _mont_batch, _mont_batch_reference

    rng = random.Random(1234)
    vals = [rng.randrange(P) for _ in range(300)] + [
        0, 1, 2, 3, P - 1, P - 2, P // 2,
        (1 << 380) - 1, 1 << 256, 255, 65535, 65536,
    ]
    got = _mont_batch(vals)
    want = _mont_batch_reference(vals)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want)
    assert _mont_batch([]).shape == (0, 48)


def test_pack_grid_cached_matches_uncached(monkeypatch):
    """The arena-cached [S, K] pubkey grid is byte-identical to the
    direct g1_to_dev build, cold and warm."""
    from lighthouse_tpu.crypto.bls.curve import g1_infinity

    sets = _mixed_sets()
    S, K, n = 4, 2, 4
    inf1 = g1_infinity()
    monkeypatch.setenv("LHTPU_INPUT_CACHE", "0")
    ref = jb.JaxBackend._pack_pubkey_grid(sets, S, K, n, inf1)
    monkeypatch.setenv("LHTPU_INPUT_CACHE", "1")
    blsrt.reset_input_caches()
    cold = jb.JaxBackend._pack_pubkey_grid(sets, S, K, n, inf1)
    warm = jb.JaxBackend._pack_pubkey_grid(sets, S, K, n, inf1)
    for a, b, c in zip(ref, cold, warm):
        assert np.array_equal(a, b) and np.array_equal(a, c)
    hits = blsrt.CACHE_EVENTS.value(cache="pubkey_rows", event="hit")
    assert hits >= 6  # the warm pass resolved every real lane from cache
    blsrt.reset_input_caches()


def test_pack_grid_oversized_batch_bypasses_cache(monkeypatch):
    """A batch with more distinct pubkeys than the arena has slots must
    NOT take the insert-then-gather path: the miss-insert loop's LRU
    evictions would overwrite slots already recorded for this batch
    before the gather runs. It builds uncached (bypass events) and the
    grid stays byte-identical."""
    from lighthouse_tpu.crypto.bls.curve import g1_infinity

    sets = _mixed_sets()  # 6 lanes, 5 distinct pubkeys
    S, K, n = 4, 2, 4
    inf1 = g1_infinity()
    monkeypatch.setenv("LHTPU_INPUT_CACHE", "0")
    ref = jb.JaxBackend._pack_pubkey_grid(sets, S, K, n, inf1)
    monkeypatch.setenv("LHTPU_INPUT_CACHE", "1")
    monkeypatch.setenv("LHTPU_PUBKEY_CACHE", "2")  # clamp floor < 5 distinct
    blsrt.reset_input_caches()
    bypass0 = blsrt.CACHE_EVENTS.value(cache="pubkey_rows", event="bypass")
    got = jb.JaxBackend._pack_pubkey_grid(sets, S, K, n, inf1)
    for a, b in zip(ref, got):
        assert np.array_equal(a, b)
    assert (
        blsrt.CACHE_EVENTS.value(cache="pubkey_rows", event="bypass")
        - bypass0
        == 6
    )
    assert len(blsrt.PUBKEY_ROW_CACHE) == 0  # nothing was inserted
    blsrt.reset_input_caches()


def test_pubkey_cache_key_canonical():
    """A key built from a raw point and one built from bytes map to the
    same canonical cache key — mixed forms never duplicate arena rows."""
    from lighthouse_tpu.crypto.bls.api import PublicKey

    pk = SKS[0].public_key()
    raw = pk.to_bytes()
    from_point = PublicKey(pk.point)  # _bytes starts out None
    assert from_point._bytes is None
    assert blsrt.pubkey_cache_key(from_point) == raw
    assert blsrt.pubkey_cache_key(pk) == raw


# ------------------------------------------------- cross-call caches


def test_input_cache_lru_eviction():
    c = blsrt.InputCache("test_lru", "LHTPU_TEST_LRU_CAP", 2)
    c.put(b"a", 1)
    c.put(b"b", 2)
    assert c.get(b"a") == 1    # refresh: b becomes the LRU entry
    c.put(b"c", 3)             # evicts b
    assert c.get(b"b") is None
    assert c.get(b"c") == 3
    assert len(c) == 2


def test_pubkey_row_cache_arena_lru(monkeypatch):
    monkeypatch.setenv("LHTPU_TEST_ROWS_CAP", "2")
    cache = blsrt.PubkeyRowCache("test_rows", "LHTPU_TEST_ROWS_CAP", 2)
    row = lambda i: (np.full(48, i, np.int32), np.full(48, 48 + i, np.int32))
    cache.insert(b"a", *row(1), False)
    cache.insert(b"b", *row(2), False)
    idx, misses = cache.lookup([b"a"])  # refresh a: b becomes LRU
    assert misses == [] and idx[0] >= 0
    cache.insert(b"c", *row(3), True)   # evicts b
    idx, misses = cache.lookup([b"a", b"b", b"c"])
    assert misses == [1] and len(cache) == 2
    gx, gy, ginf = cache.gather(idx[[0, 2]])
    assert (gx[0] == 1).all() and (gy[0] == 49).all() and not ginf[0]
    assert (gx[1] == 3).all() and ginf[1]
    assert blsrt.CACHE_EVENTS.value(cache="test_rows", event="evict") >= 1


def test_htc_memo_persists_and_evicts(monkeypatch):
    """_hash_message_bytes' distinct-message memo lives across calls in
    a bounded LRU; eviction recomputes correctly (satellite a)."""
    from lighthouse_tpu.crypto.bls.curve import g2_infinity

    monkeypatch.setenv("LHTPU_DEVICE_HTC", "0")
    monkeypatch.setenv("LHTPU_HTC_CACHE", "2")
    blsrt.reset_input_caches()
    be = jb.JaxBackend()
    inf2 = g2_infinity()
    msgs = [bytes([0x60 + i]) * 32 for i in range(3)]

    evict0 = blsrt.CACHE_EVENTS.value(cache="hash_to_curve", event="evict")
    cached = be._hash_message_bytes(msgs, 4, inf2)
    assert len(blsrt.HTC_CACHE) == 2  # capacity bound held
    assert (
        blsrt.CACHE_EVENTS.value(cache="hash_to_curve", event="evict")
        - evict0
        >= 1
    )
    # Second call in reverse order: the two survivors hit (same-order
    # replay of 3 keys through a cap-2 LRU would thrash every lookup),
    # the evicted message recomputes — the output must be byte-identical
    # to the uncached path either way.
    hit0 = blsrt.CACHE_EVENTS.value(cache="hash_to_curve", event="hit")
    rev = list(reversed(msgs))
    warm = be._hash_message_bytes(rev, 4, inf2)
    assert (
        blsrt.CACHE_EVENTS.value(cache="hash_to_curve", event="hit") - hit0
        >= 2
    )
    monkeypatch.setenv("LHTPU_INPUT_CACHE", "0")
    ref = be._hash_message_bytes(msgs, 4, inf2)
    ref_rev = be._hash_message_bytes(rev, 4, inf2)
    for a, b in zip(ref, cached):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(ref_rev, warm):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    blsrt.reset_input_caches()


# ----------------------------------------------------- metrics surface


def test_pipeline_metrics_exported(monkeypatch):
    """bls_pipeline_chunks_total / bls_pipeline_overlap_seconds / cache
    counters appear in the Prometheus gather and in
    dispatch_stage_report() after a pipelined verify."""
    from lighthouse_tpu.common.metrics import REGISTRY

    sets = _mixed_sets()
    be = get_backend("jax")
    _pipeline_env(monkeypatch, on=True)
    blsrt.reset_input_caches()
    chunks0 = sum(v for _, v in pipeline.PIPELINE_CHUNKS.items())
    assert be.verify_signature_sets(sets)
    assert be.verify_signature_sets(sets)  # warm: cache hits recorded

    assert (
        sum(v for _, v in pipeline.PIPELINE_CHUNKS.items()) - chunks0 == 4
    )
    text = REGISTRY.gather()
    for family in (
        "bls_pipeline_chunks_total",
        "bls_pipeline_overlap_seconds",
        "bls_input_cache_events_total",
    ):
        assert family in text

    rep = jb.dispatch_stage_report()
    pipe = rep["pipeline"]
    assert pipe["enabled"] is True and pipe["chunks"] == 2
    assert pipe["overlap_s"] >= 0.0
    if tracing.enabled():
        assert pipe["overlap_s"] > 0.0  # chunk 1's host time was hidden
        assert pipe["stages"]  # per-stage hidden/exposed breakdown
    caches = rep["cache"]
    assert "pubkey_rows" in caches and "hash_to_curve" in caches
    assert caches["pubkey_rows"]["hit"] >= 1
    assert 0.0 <= caches["pubkey_rows"]["hit_rate"] <= 1.0
    # stage seconds aggregate across chunks, device_sync from the force
    for stage in ("pack", "hash_to_curve", "scalars", "msm_schedule",
                  "dispatch", "device_sync"):
        assert stage in be.last_stage_seconds
    blsrt.reset_input_caches()


# ------------------------------------------------- pack-stage benchmark


@pytest.mark.slow
def test_pack_stage_speedup_at_4096_rows():
    """ISSUE 4 acceptance: ≥5× pack-stage speedup at 4096 rows.

    Old pack stage = the seed's per-int Python Montgomery loop over the
    full [S, K] grid (_mont_batch_reference). New pack stage = the
    vectorized limbifier feeding the cross-call row arena — measured
    warm, the steady state for validator workloads where the same
    pubkeys recur every epoch. Both sides are full stage reproductions
    (grid assembly included), best-of-5.
    """
    import time
    from types import SimpleNamespace

    from lighthouse_tpu.crypto.bls.constants import P
    from lighthouse_tpu.crypto.bls.curve import g1_infinity
    from lighthouse_tpu.ops.points import _mont_batch_reference

    rng = random.Random(77)
    S, K = 4096, 1
    fakes = []
    for i in range(S):
        x, y = rng.randrange(P), rng.randrange(P)
        fakes.append(
            SimpleNamespace(
                _bytes=x.to_bytes(48, "big"),
                point=SimpleNamespace(
                    x=SimpleNamespace(n=x),
                    y=SimpleNamespace(n=y),
                    infinity=False,
                ),
            )
        )
    sets = [SimpleNamespace(signing_keys=[pk]) for pk in fakes]
    inf1 = g1_infinity()

    def old_pack():
        pk_rows = [[pk.point for pk in s.signing_keys] for s in sets]
        flat = [p for row in pk_rows for p in row]
        px = _mont_batch_reference([p.x.n for p in flat])
        py = _mont_batch_reference([p.y.n for p in flat])
        pinf = np.asarray([p.infinity for p in flat])
        return px.reshape(S, K, 48), py.reshape(S, K, 48), pinf.reshape(S, K)

    def new_pack():
        return jb.JaxBackend._pack_pubkey_grid(sets, S, K, S, inf1)

    import os

    os.environ["LHTPU_INPUT_CACHE"] = "1"
    blsrt.reset_input_caches()
    try:
        cold = new_pack()  # populate the arena (also JIT-warms numpy)
        ref = old_pack()
        for a, b in zip(ref, cold):
            assert np.array_equal(a, b)  # bit-identical before timing

        t_old = min(
            _timed(old_pack, time) for _ in range(5)
        )
        t_new = min(
            _timed(new_pack, time) for _ in range(5)
        )
        ratio = t_old / t_new
        print(
            f"\npack 4096 rows: old {t_old * 1e3:.2f} ms, "
            f"warm cached {t_new * 1e3:.2f} ms, {ratio:.1f}x"
        )
        assert ratio >= 5.0, (
            f"warm pack only {ratio:.1f}x faster "
            f"({t_old * 1e3:.2f} ms -> {t_new * 1e3:.2f} ms)"
        )
    finally:
        blsrt.reset_input_caches()
        os.environ.pop("LHTPU_INPUT_CACHE", None)


def _timed(fn, time):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
