"""Protocol-aware message dedup in front of hash-to-curve (ISSUE 10).

Covers the plan builder (blsrt.dedup_plan), the oracle-path gather's
bit-exactness against per-row hashing at duplication factors {1, 8, 64},
the htc_dedup/htc_map/htc_cofactor sub-stage instrumentation, and the
degradation contract: any fault inside htc_dedup falls back to the
identity plan with bit-identical output — dedup is a pure optimization
and must never change a result or crash a dispatch.

Everything here runs the HOST (oracle) hash path — no Pallas, no device
compile; the device-path twins of these assertions live in the slow-tier
tests/test_htc.py.
"""

import numpy as np
import pytest

from lighthouse_tpu import blsrt
from lighthouse_tpu import jax_backend as jb
from lighthouse_tpu.common import resilience
from lighthouse_tpu.crypto.bls.curve import g2_infinity
from lighthouse_tpu.crypto.bls.fields import Fq2
from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
from lighthouse_tpu.ops import tower


def _rows(out):
    return tuple(np.asarray(v) for v in out)


def _total(counter) -> float:
    return sum(v for _, v in counter.items())


@pytest.fixture(autouse=True)
def _clean_caches():
    blsrt.reset_input_caches()
    yield
    blsrt.reset_input_caches()
    resilience.reset()


class TestDedupPlan:
    def test_collapses_duplicates_first_seen_order(self):
        p = blsrt.dedup_plan([b"a", b"b", b"a", b"c", b"b", b"a"])
        assert p.enabled
        assert p.distinct == [b"a", b"b", b"c"]
        assert list(p.index) == [0, 1, 0, 2, 1, 0]
        assert p.index.dtype == np.int32
        assert p.n == 6

    def test_identity_plan_when_disabled(self, monkeypatch):
        monkeypatch.setenv("LHTPU_HTC_DEDUP", "0")
        p = blsrt.dedup_plan([b"a", b"a", b"a"])
        assert not p.enabled
        assert p.distinct == [b"a", b"a", b"a"]
        assert list(p.index) == [0, 1, 2]

    def test_identity_plan_helper(self):
        p = blsrt.identity_plan([b"x", b"y"])
        assert not p.enabled
        assert p.distinct == [b"x", b"y"]
        assert list(p.index) == [0, 1]

    def test_traffic_counter(self):
        d0 = blsrt.DEDUP_MESSAGES.value(outcome="distinct")
        u0 = blsrt.DEDUP_MESSAGES.value(outcome="duplicate")
        blsrt.dedup_plan([b"a", b"a", b"b", b"a"])
        assert blsrt.DEDUP_MESSAGES.value(outcome="distinct") == d0 + 2
        assert blsrt.DEDUP_MESSAGES.value(outcome="duplicate") == u0 + 2

    def test_empty_batch(self):
        p = blsrt.dedup_plan([])
        assert p.distinct == [] and p.n == 0


class TestOracleGatherParity:
    @pytest.mark.parametrize("dup", [1, 8, 64])
    def test_rows_match_per_row_oracle(self, dup):
        """Row i of the deduped gather equals hash_to_g2(messages[i]) —
        exact, at the un-deduped (1), committee-shaped (64), and
        intermediate (8) duplication factors."""
        be = jb.JaxBackend()
        n = 64
        msgs = [(i // dup).to_bytes(8, "big") for i in range(n)]
        mx, my, minf = _rows(be._hash_message_bytes(msgs, n, g2_infinity()))
        assert not minf.any()
        # spot-check full Fq2 equality on a stride; duplicates must be
        # byte-equal to their first occurrence everywhere
        for i in range(0, n, max(1, dup)):
            want = hash_to_g2(msgs[i])
            assert Fq2(*tower.fp2_from_dev(mx[i])) == want.x, f"row {i}"
            assert Fq2(*tower.fp2_from_dev(my[i])) == want.y, f"row {i}"
        for i in range(n):
            j = (i // dup) * dup
            np.testing.assert_array_equal(mx[i], mx[j])
            np.testing.assert_array_equal(my[i], my[j])

    def test_padding_slots_are_infinity(self):
        be = jb.JaxBackend()
        out = _rows(be._hash_message_bytes([b"m", b"m"], 4, g2_infinity()))
        minf = out[2]
        assert list(minf) == [False, False, True, True]

    def test_disabled_dedup_bit_identical(self, monkeypatch):
        be = jb.JaxBackend()
        msgs = [b"dup"] * 8 + [b"other"] * 8
        a = _rows(be._hash_message_bytes(msgs, 16, g2_infinity()))
        monkeypatch.setenv("LHTPU_HTC_DEDUP", "0")
        blsrt.reset_input_caches()
        b = _rows(be._hash_message_bytes(msgs, 16, g2_infinity()))
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


class TestSubStages:
    def test_sub_stages_recorded(self):
        be = jb.JaxBackend()
        stages: dict = {}
        be._hash_message_bytes(
            [b"a", b"b"], 2, g2_infinity(), stages=stages
        )
        assert {"htc_dedup", "htc_map", "htc_cofactor"} <= set(stages)
        assert all(v >= 0.0 for v in stages.values())

    def test_names_are_canonical(self):
        from lighthouse_tpu.common.stages import is_canonical

        for s in ("htc_dedup", "htc_map", "htc_cofactor"):
            assert is_canonical(s), s

    def test_drill_matrices_cover_sub_stages(self):
        from tools.fault_drill import STAGES, TRIAGE_STAGES

        for s in ("htc_dedup", "htc_map", "htc_cofactor"):
            assert s in STAGES and s in TRIAGE_STAGES, s


class TestDedupFaultDegradation:
    def test_permanent_fault_degrades_to_identity_bit_identically(
        self, monkeypatch
    ):
        """A permanent fault inside htc_dedup must NOT ride the rung
        ladder: the batch degrades in place to the un-deduped path,
        records the degradation, and returns bit-identical rows."""
        be = jb.JaxBackend()
        msgs = [b"x"] * 4 + [b"y"] * 4
        clean = _rows(be._hash_message_bytes(msgs, 8, g2_infinity()))
        monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "htc_dedup:mosaic:1")
        resilience.rearm_faults()
        blsrt.reset_input_caches()
        degraded0 = resilience.DEGRADED_TOTAL.value(path="htc-dedup")
        out = _rows(be._hash_message_bytes(msgs, 8, g2_infinity()))
        assert resilience.DEGRADED_TOTAL.value(path="htc-dedup") \
            == degraded0 + 1
        for a, b in zip(clean, out):
            np.testing.assert_array_equal(a, b)

    def test_transient_fault_retried_in_stage(self, monkeypatch):
        be = jb.JaxBackend()
        msgs = [b"x", b"x", b"z"]
        clean = _rows(be._hash_message_bytes(msgs, 4, g2_infinity()))
        monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
        monkeypatch.setenv(
            "LHTPU_FAULT_INJECT", "htc_dedup:aborted:1"
        )
        resilience.rearm_faults()
        blsrt.reset_input_caches()
        retries0 = _total(resilience.RETRIES_TOTAL)
        degraded0 = _total(resilience.DEGRADED_TOTAL)
        out = _rows(be._hash_message_bytes(msgs, 4, g2_infinity()))
        assert _total(resilience.RETRIES_TOTAL) >= retries0 + 1
        assert _total(resilience.DEGRADED_TOTAL) == degraded0
        for a, b in zip(clean, out):
            np.testing.assert_array_equal(a, b)
