"""Resilience-layer tests: the error classifier against the REAL
r03/r04/r05 failure strings, bounded retry, the circuit-breaker
lifecycle, deterministic fault injection, the device_sync deadline, and
the degradation ladder end-to-end through JaxBackend on CPU.

The fused rung cannot execute off-TPU (its Pallas bodies would inline
into an exploding XLA:CPU compile — see jax_backend's classic-core
note), so the three-rung ladder MECHANICS are pinned with a stubbed
dispatch, while classic↔native/host rung verdict bit-equality runs for
real; fused↔classic bit-equality is the existing TPU parity suite's
job (test_tpu_parity / test_tkernel)."""

import time

import pytest

from lighthouse_tpu import jax_backend as jb
from lighthouse_tpu.common import resilience
from lighthouse_tpu.common.timeout_lock import LockTimeout
from lighthouse_tpu.crypto.bls.api import (
    AggregateSignature,
    SecretKey,
    SignatureSet,
)

SKS = [SecretKey.from_int(i + 7) for i in range(3)]
PKS = [sk.public_key() for sk in SKS]
M0 = b"\x11" * 32
M1 = b"\x22" * 32

# Error strings this JAX and libtpu raise on a TPU, and the Mosaic
# refusal that zeroed a bench round (ISSUE 2).
TPU_ABORTED = (
    "ABORTED: Internal error when accessing libtpu multi-process "
    "lockfile. Run \"$ sudo rm /tmp/libtpu_lockfile\"."
)
TPU_BACKEND_INIT = (
    "Unable to initialize backend 'tpu': ABORTED: The TPU is already in "
    "use by process with pid 4242. Not attempting to load libtpu.so in "
    "this process. (set JAX_PLATFORMS='' to automatically choose an "
    "available backend)"
)
R04_MOSAIC = (
    "Unimplemented primitive in Pallas TPU lowering for KernelType.TC: "
    "dynamic_slice. Please file an issue on "
    "https://github.com/jax-ml/jax/issues."
)


def _valid_sets():
    """Same (S=2, K=2) compile bucket as test_jax_backend — no new XLA
    program for this module."""
    s0 = SignatureSet.single_pubkey(SKS[0].sign(M0), PKS[0], M0)
    agg = AggregateSignature.aggregate([SKS[1].sign(M1), SKS[2].sign(M1)])
    s1 = SignatureSet.multiple_pubkeys(agg, [PKS[1], PKS[2]], M1)
    return [s0, s1]


def _tampered_sets():
    sets = _valid_sets()
    sets[0] = SignatureSet.single_pubkey(SKS[0].sign(M0), PKS[1], M0)
    return sets


class TestClassifier:
    @pytest.mark.parametrize("exc,category,kind", [
        # the real incidents
        (RuntimeError(TPU_ABORTED), resilience.TRANSIENT,
         "aborted"),
        (RuntimeError(TPU_BACKEND_INIT), resilience.TRANSIENT,
         "backend_init"),
        (NotImplementedError(R04_MOSAIC), resilience.PERMANENT, "lowering"),
        # type-driven
        (ConnectionResetError("[Errno 104] Connection reset by peer"),
         resilience.TRANSIENT, "socket"),
        (TimeoutError("poll timed out"), resilience.TRANSIENT, "timeout"),
        (resilience.DeadlineExceeded("device_sync exceeded 0.2s deadline"),
         resilience.TRANSIENT, "hang"),
        (LockTimeout("read lock timeout"), resilience.TRANSIENT, "timeout"),
        (AssertionError("verdict mismatch"), resilience.PERMANENT,
         "AssertionError"),
        (TypeError("dot_general shape mismatch"), resilience.PERMANENT,
         "TypeError"),
        (ValueError("bad limb count"), resilience.PERMANENT, "ValueError"),
        # message-driven permanents beat transient-looking words
        (RuntimeError("INTERNAL: Mosaic failed: op unavailable"),
         resilience.PERMANENT, "lowering"),
        (RuntimeError("RESOURCE_EXHAUSTED: HBM OOM while allocating"),
         resilience.PERMANENT, "oom"),
        # unknowns default to permanent (ladder rescues, retry doesn't)
        (RuntimeError("some novel failure"), resilience.PERMANENT,
         "unclassified"),
    ])
    def test_table(self, exc, category, kind):
        assert resilience.classify(exc) == (category, kind)

    def test_assert_beats_transient_message(self):
        # a correctness assert mentioning "timeout" is still permanent
        got = resilience.classify(AssertionError("timeout in verdict"))
        assert got == (resilience.PERMANENT, "AssertionError")

    @pytest.mark.parametrize("msg", [
        # the libtpu literal
        TPU_ABORTED,
        # the same ABORTED status as JaxRuntimeError renders it, and
        # from other runtime calls — each must land transient on the
        # status itself, not on the lock-file wording
        f"jax.errors.JaxRuntimeError: {TPU_ABORTED}",
        "ABORTED: Cancelled execution of the program on TPU_0",
        "INTERNAL: while running replica 0: ABORTED: transfer cancelled",
    ])
    def test_aborted_status_family_is_transient(self, msg):
        category, kind = resilience.classify(RuntimeError(msg))
        assert category == resilience.TRANSIENT
        assert kind == "aborted"

    def test_aborted_never_outranks_permanent(self):
        # the permanent table wins even when the message carries an
        # ABORTED status
        got = resilience.classify(
            RuntimeError("Mosaic lowering failed: ABORTED: kernel refused")
        )
        assert got == (resilience.PERMANENT, "lowering")


class TestRetryPolicy:
    def test_backoff_doubles_and_caps(self):
        p = resilience.RetryPolicy(
            max_retries=5, base_s=0.1, cap_s=0.5, jitter=0.0
        )
        assert [p.backoff(i) for i in (1, 2, 3, 4)] == [0.1, 0.2, 0.4, 0.5]

    def test_jitter_bounded_and_seedable(self, monkeypatch):
        monkeypatch.setenv("LHTPU_RETRY_SEED", "42")
        p = resilience.RetryPolicy(
            max_retries=3, base_s=1.0, cap_s=10.0, jitter=0.25
        )
        seq = [p.backoff(1) for _ in range(8)]
        assert all(1.0 <= d <= 1.25 for d in seq)
        monkeypatch.setenv("LHTPU_RETRY_SEED", "43")
        resilience._jitter_rng()  # register the seed change...
        monkeypatch.setenv("LHTPU_RETRY_SEED", "42")  # ...then re-seed
        assert [p.backoff(1) for _ in range(8)] == seq  # deterministic

    def test_call_with_retries_second_attempt_wins(self, monkeypatch):
        monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
        attempts = []

        def flaky():
            attempts.append(1)
            if len(attempts) < 2:
                raise RuntimeError(TPU_ABORTED)
            return "ok"

        before = resilience.RETRIES_TOTAL.value(
            stage="unit", kind="aborted"
        )
        assert resilience.call_with_retries(flaky, stage="unit") == "ok"
        assert len(attempts) == 2
        assert resilience.RETRIES_TOTAL.value(
            stage="unit", kind="aborted"
        ) == before + 1

    def test_permanent_not_retried(self, monkeypatch):
        monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
        attempts = []

        def broken():
            attempts.append(1)
            raise AssertionError("wrong verdict")

        with pytest.raises(AssertionError):
            resilience.call_with_retries(broken, stage="unit")
        assert len(attempts) == 1

    def test_budget_exhausted_reraises(self, monkeypatch):
        monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
        monkeypatch.setenv("LHTPU_RETRY_MAX", "2")
        attempts = []

        def always():
            attempts.append(1)
            raise TimeoutError("timed out")

        with pytest.raises(TimeoutError):
            resilience.call_with_retries(always, stage="unit")
        assert len(attempts) == 3  # initial + 2 retries


class TestCircuitBreaker:
    def test_lifecycle(self):
        now = [0.0]
        br = resilience.CircuitBreaker(
            "unit-rung", threshold=2, cooldown_s=10, clock=lambda: now[0]
        )
        assert br.allow() and br.state == resilience.CLOSED
        br.record_failure()
        assert br.state == resilience.CLOSED  # below threshold
        br.record_failure()
        assert br.state == resilience.OPEN
        assert not br.allow()  # cooldown not elapsed
        now[0] = 11.0
        assert br.allow()  # open -> half-open probe
        assert br.state == resilience.HALF_OPEN
        assert not br.allow()  # only ONE in-flight probe
        br.record_failure()  # probe failed
        assert br.state == resilience.OPEN
        now[0] = 22.0
        assert br.allow()
        br.record_success()
        assert br.state == resilience.CLOSED
        assert resilience.BREAKER_STATE.value(
            path="unit-rung"
        ) == resilience.CLOSED

    def test_permanent_trips_immediately(self):
        br = resilience.CircuitBreaker(
            "unit-rung2", threshold=5, cooldown_s=10, clock=lambda: 0.0
        )
        br.record_failure(permanent=True)
        assert br.state == resilience.OPEN

    def test_success_resets_failure_streak(self):
        br = resilience.CircuitBreaker(
            "unit-rung3", threshold=2, cooldown_s=10, clock=lambda: 0.0
        )
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == resilience.CLOSED  # streak broken by success


class TestFaultInjector:
    def test_counts_decrement_and_spec_reset(self, monkeypatch):
        inj = resilience.FaultInjector()
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "pack:aborted:2")
        inj.fire("hash_to_curve")  # other stages unaffected
        with pytest.raises(RuntimeError, match="ABORTED"):
            inj.fire("pack")
        with pytest.raises(RuntimeError, match="ABORTED"):
            inj.fire("pack")
        inj.fire("pack")  # count exhausted -> no-op
        # changing the spec re-arms
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "pack:socket:1")
        with pytest.raises(ConnectionResetError):
            inj.fire("pack")
        monkeypatch.delenv("LHTPU_FAULT_INJECT")
        inj.fire("pack")  # cleared env -> no-op

    def test_injected_faults_classify_like_production(self, monkeypatch):
        inj = resilience.FaultInjector()
        monkeypatch.setenv(
            "LHTPU_FAULT_INJECT",
            "a:aborted:1,a:backend_init:1,a:mosaic:1",
        )
        cats = []
        for _ in range(3):
            with pytest.raises(Exception) as ei:
                inj.fire("a")
            cats.append(resilience.classify(ei.value))
        assert cats == [
            (resilience.TRANSIENT, "aborted"),
            (resilience.TRANSIENT, "backend_init"),
            (resilience.PERMANENT, "lowering"),
        ]

    def test_malformed_spec_ignored(self, monkeypatch):
        inj = resilience.FaultInjector()
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "garbage,pack:socket:x")
        inj.fire("pack")  # no raise, just a stderr note


class TestDeadline:
    def test_value_and_error_pass_through(self):
        assert resilience.force_with_deadline(
            lambda: 42, stage="unit", deadline_s=5.0
        ) == 42
        with pytest.raises(ValueError, match="inner"):
            resilience.force_with_deadline(
                lambda: (_ for _ in ()).throw(ValueError("inner")),
                stage="unit", deadline_s=5.0,
            )

    def test_hang_becomes_classified_transient(self):
        before = resilience.DEADLINE_TOTAL.value(stage="unit")
        with pytest.raises(resilience.DeadlineExceeded) as ei:
            resilience.force_with_deadline(
                lambda: time.sleep(2.0), stage="unit", deadline_s=0.1
            )
        assert resilience.classify(ei.value) == (resilience.TRANSIENT, "hang")
        assert resilience.DEADLINE_TOTAL.value(stage="unit") == before + 1

    def test_disabled_runs_inline(self):
        assert resilience.force_with_deadline(
            lambda: "inline", stage="unit", deadline_s=0
        ) == "inline"


class TestLadderMechanics:
    """Three-rung ladder with a stubbed dispatch (the fused rung cannot
    execute off-TPU): permanent fused failure trips the fused breaker,
    classic answers, verdicts stay bit-identical across rungs."""

    def _stub(self, monkeypatch, verdicts):
        calls = []

        def fake_dispatch(self_b, sets, path_override=None):
            rung = path_override or "fused"
            calls.append(rung)
            out = verdicts[rung]
            if isinstance(out, Exception):
                raise out
            self_b.last_path = rung
            self_b._last_rung = rung
            return out

        monkeypatch.setattr(jb.JaxBackend, "_dispatch", fake_dispatch)
        monkeypatch.setattr(jb, "_fused_choice", lambda: "1")
        return calls

    def test_permanent_fused_failure_degrades_to_classic(self, monkeypatch):
        calls = self._stub(monkeypatch, {
            "fused": NotImplementedError(R04_MOSAIC),
            "classic": True,
            "native": True,
        })
        be = jb.JaxBackend()
        degraded = resilience.DEGRADED_TOTAL.value(path="classic")
        assert be.verify_signature_sets(_valid_sets()) is True
        assert calls == ["fused", "classic"]
        assert resilience.breaker("fused").state == resilience.OPEN
        assert resilience.breaker("classic").state == resilience.CLOSED
        assert resilience.DEGRADED_TOTAL.value(path="classic") == degraded + 1
        # while the fused breaker is open, calls skip straight to classic
        assert be.verify_signature_sets(_valid_sets()) is True
        assert calls == ["fused", "classic", "classic"]

    def test_all_rungs_bit_identical(self, monkeypatch):
        for verdict in (True, False):
            self._stub(monkeypatch, {
                "fused": verdict, "classic": verdict, "native": verdict,
            })
            be = jb.JaxBackend()
            assert be._verify_once([object()], "classic") is verdict
            assert be._verify_once([object()], "native") is verdict

    def test_double_rung_failure_reaches_native(self, monkeypatch):
        calls = self._stub(monkeypatch, {
            "fused": NotImplementedError(R04_MOSAIC),
            "classic": RuntimeError(TPU_ABORTED),
            "native": True,
        })
        monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
        monkeypatch.setenv("LHTPU_RETRY_MAX", "1")
        be = jb.JaxBackend()
        assert be.verify_signature_sets(_valid_sets()) is True
        # fused fails permanently; classic raises transiently straight
        # from _dispatch (no in-stage retry in the stub) and feeds its
        # breaker; native answers as the last resort
        assert calls[0] == "fused" and calls[-1] == "native"
        assert resilience.DEGRADED_TOTAL.value(path="native") >= 1


class TestDispatchIntegration:
    """The real classic rung on CPU, exercised via LHTPU_FAULT_INJECT."""

    def test_retry_succeeds_on_second_attempt(self, monkeypatch):
        monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
        monkeypatch.setenv(
            "LHTPU_FAULT_INJECT", "hash_to_curve:aborted:1"
        )
        be = jb.JaxBackend()
        before = resilience.RETRIES_TOTAL.value(
            stage="hash_to_curve", kind="aborted"
        )
        errors_before = jb.DISPATCH_ERRORS.value(stage="hash_to_curve")
        assert be.verify_signature_sets(_valid_sets())
        assert resilience.RETRIES_TOTAL.value(
            stage="hash_to_curve", kind="aborted"
        ) == before + 1
        # PR 1 attribution is preserved: the failed attempt still counted
        assert jb.DISPATCH_ERRORS.value(
            stage="hash_to_curve"
        ) == errors_before + 1
        # no degradation: the retry answered on the primary rung
        assert be.last_path not in ("native-fallback", "python-fallback")
        assert resilience.breaker("classic").state == resilience.CLOSED
        # the report surface bench.py embeds carries the resilience story
        report = jb.dispatch_stage_report()
        assert report["retries"].get("hash_to_curve:aborted", 0) >= 1
        assert set(report["breaker"]) == set(resilience.LADDER)
        assert report["path"] == be.last_path

    def test_permanent_fault_degrades_bit_identical(self, monkeypatch):
        be = jb.JaxBackend()
        good, bad = _valid_sets(), _tampered_sets()
        assert be.verify_signature_sets(good) is True  # healthy baseline
        assert be.verify_signature_sets(bad) is False

        monkeypatch.setenv("LHTPU_FAULT_INJECT", "hash_to_curve:mosaic:1")
        assert be.verify_signature_sets(good) is True  # bit-identical
        assert be.last_path in ("native-fallback", "python-fallback")
        assert resilience.breaker("classic").state == resilience.OPEN

        resilience.reset()  # re-arm the injector and close breakers
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "pack:mosaic:1")
        assert be.verify_signature_sets(bad) is False  # rejects identically
        assert be.last_path in ("native-fallback", "python-fallback")

    def test_breaker_half_open_recovery(self, monkeypatch):
        monkeypatch.setenv("LHTPU_BREAKER_COOLDOWN_S", "0")
        resilience.reset()  # breakers re-read the cooldown
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "dispatch:mosaic:1")
        be = jb.JaxBackend()
        sets = _valid_sets()
        assert be.verify_signature_sets(sets)  # degraded; classic opens
        assert resilience.breaker("classic").state == resilience.OPEN
        monkeypatch.delenv("LHTPU_FAULT_INJECT")
        # cooldown elapsed (0s): next call is the half-open probe, it
        # succeeds and closes the breaker — full recovery
        assert be.verify_signature_sets(sets)
        assert be.last_path == "classic"
        assert resilience.breaker("classic").state == resilience.CLOSED

    def test_wedged_device_sync_retried_via_deadline(self, monkeypatch):
        """A hung force hits the LHTPU_SYNC_DEADLINE_S deadline, is
        classified transient(hang) and retried by re-dispatching. The
        dispatch is stubbed to an instantly-forceable scalar so the
        tight test deadline races only the injected 2 s hang, not the
        real CPU pairing time."""
        import numpy as np

        def fake_dispatch(self_b, sets, path_override=None):
            self_b.last_path = "classic"
            self_b._last_rung = "classic"
            return np.bool_(True)  # non-bool: goes through device_sync

        monkeypatch.setattr(jb.JaxBackend, "_dispatch", fake_dispatch)
        monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "device_sync:hang:1")
        monkeypatch.setenv("LHTPU_FAULT_HANG_S", "2.0")
        monkeypatch.setenv("LHTPU_SYNC_DEADLINE_S", "0.2")
        be = jb.JaxBackend()
        before = resilience.RETRIES_TOTAL.value(
            stage="device_sync", kind="hang"
        )
        deadline_before = resilience.DEADLINE_TOTAL.value(stage="device_sync")
        assert be.verify_signature_sets(_valid_sets())
        assert be.last_path == "classic"  # answered after retry, no degrade
        assert resilience.RETRIES_TOTAL.value(
            stage="device_sync", kind="hang"
        ) == before + 1
        assert resilience.DEADLINE_TOTAL.value(
            stage="device_sync"
        ) == deadline_before + 1

    def test_async_resolver_falls_back_resilient(self, monkeypatch):
        monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "device_sync:mosaic:1")
        be = jb.JaxBackend()
        resolve = be.verify_signature_sets_async(_valid_sets())
        # the force fails permanently -> the resolver re-runs the
        # resilient ladder synchronously; the verdict is late, not lost
        assert resolve() is True

    def test_resilience_disabled_raw_raise(self, monkeypatch):
        monkeypatch.setenv("LHTPU_RESILIENCE", "0")
        monkeypatch.setenv(
            "LHTPU_FAULT_INJECT", "hash_to_curve:aborted:1"
        )
        be = jb.JaxBackend()
        with pytest.raises(RuntimeError, match="ABORTED"):
            be.verify_signature_sets(_valid_sets())


class TestNativeLoadAttribution:
    def test_failure_logged_once_and_counted(self, monkeypatch):
        import lighthouse_tpu.crypto.bls.native_backend as nbmod

        marker = f"synthetic native load failure #{len(jb._NATIVE_LOAD_WARNED)}"

        def boom():
            raise RuntimeError(marker)

        monkeypatch.setattr(nbmod, "load_native_backend", boom)
        before = jb.NATIVE_LOAD_FAILURES.value()
        assert jb._try_load_native() is None
        assert jb.NATIVE_LOAD_FAILURES.value() == before + 1
        assert any(marker in c for c in jb._NATIVE_LOAD_WARNED)
        # same cause again: logged/counted once, not per call
        assert jb._try_load_native() is None
        assert jb.NATIVE_LOAD_FAILURES.value() == before + 1


class TestFaultDrillSmoke:
    def test_quick_matrix_passes(self):
        """Tier-1 smoke of tools/fault_drill.py: one stage × both fault
        classes through the real backend (full matrix: run the tool)."""
        from tools.fault_drill import run_drill

        results = run_drill(stages=("hash_to_curve",))
        assert results and all(r["ok"] for r in results), results


