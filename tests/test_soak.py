"""Soak runner tests (ISSUE 7): chaos-schedule grammar, a fast
2-virtual-epoch chaos soak with bit-identical digest parity vs the
chaos-free replay, re-promotion to the primary rung within the recovery
budget after a permanent fault, the wedged-slot watchdog drill, and
accounting disjointness under combined shed + force-degrade.

Shape economics: the dispatching cells run once in a module fixture
and pin batch_target=2 over an aggregate-only stream, so every device
dispatch is the (S=2, K=2, G=2) triage bucket tests/test_triage.py
already pays for — no fresh XLA programs mid-soak."""

import json
import threading

import pytest

from lighthouse_tpu.common import health, resilience
from lighthouse_tpu.loadgen.soak import (
    ChaosEvent,
    SoakConfig,
    SoakRunner,
    chaos_spec_for_epoch,
    parse_chaos_schedule,
)
from lighthouse_tpu.loadgen.serve import ServeConfig, ServingLoop, \
    VirtualClock
from lighthouse_tpu.loadgen.traffic import TrafficConfig, TrafficGenerator


def _traffic(**overrides) -> TrafficConfig:
    cfg = dict(
        validators=64, slots=2, seconds_per_slot=2.0,
        committees_per_slot=2, committee_size=2,
        unaggregated_per_slot=0, sync_per_slot=0, blocks=False,
        poison_rate=0.25, key_pool=8, seed=7,
    )
    cfg.update(overrides)
    return TrafficConfig(**cfg)


def _configure_sentinels():
    # Deterministic sentinels only: the RSS/jit-cache sentinels react to
    # unrelated compile activity elsewhere in the suite.
    health.configure(sentinels=[
        health.BreakerFlapSentinel(), health.SloBreachSentinel(),
    ])


def _warm_triage_buckets():
    """Pay the (S=2, K=2, G=2) triage trace+load — with one poisoned
    set, which walks the refinement path too — BEFORE the soaks start.
    A soak scores steady-state lifetime behavior; without this, a
    degraded epoch 0 defers the device program into later epochs and
    its XLA arenas (GBs on CPU) read as an RSS leak. The soak tests pin
    batch_target=2 with a deadline longer than within-slot arrival
    jitter so (S=2, K=2, G=2) is the ONLY device bucket the epochs can
    dispatch (per-epoch seed shifts at batch_target=4 formed odd
    S=1/S=3 batches that compiled fresh programs mid-soak)."""
    from lighthouse_tpu.crypto.bls.api import (
        AggregateSignature,
        SecretKey,
        SignatureSet,
        verify_signature_sets_triaged,
    )

    sks = [SecretKey.from_int(i + 7) for i in range(4)]
    bad = b"\xee" * 32
    sets = []
    for i in range(2):
        m = bytes([i + 1]) * 32
        signed = bad if i == 1 else m  # one poisoned set
        a, b = sks[i], sks[i + 2]
        agg = AggregateSignature.aggregate([a.sign(signed), b.sign(m)])
        sets.append(SignatureSet.multiple_pubkeys(
            agg, [a.public_key(), b.public_key()], m
        ))
    verify_signature_sets_triaged(sets, backend="jax")
    resilience.reset()


@pytest.fixture(scope="module")
def soak_results():
    """Run both dispatching soak cells ONCE for the module: the
    warm-up trace+load of the grouped program costs ~a minute on CPU
    and each epoch pays seconds of pure-Python signing — per-test
    repetition is what blew the fast-tier budget."""
    mp = pytest.MonkeyPatch()
    out = {}
    try:
        mp.setenv("LHTPU_VERDICT_GROUPS", "2")
        mp.setenv("LHTPU_PIPELINE", "0")
        mp.setenv("LHTPU_RETRY_BASE_MS", "0")
        # breakers must re-close inside the cells' wall time
        mp.setenv("LHTPU_BREAKER_COOLDOWN_S", "0.01")
        resilience.reset()
        _configure_sentinels()
        _warm_triage_buckets()

        serve = ServeConfig(batch_target=2, batch_deadline_ms=1000.0)
        traffic = _traffic(slots=1)  # one full (S=2) batch per epoch

        lines: list[str] = []
        cfg = SoakConfig(
            epochs=2, seed=7, backend="jax", recovery_epochs=2,
            replay=True, traffic=traffic, serve=serve,
        )
        chaos = [ChaosEvent(epoch=0, stage="dispatch",
                            kind="aborted", count=1)]
        out["transient"] = (
            SoakRunner(cfg, chaos=chaos, emit=lines.append).run(),
            _rows(lines),
        )

        resilience.reset()
        _configure_sentinels()
        lines = []
        cfg = SoakConfig(
            epochs=3, seed=7, backend="jax", recovery_epochs=2,
            replay=False, traffic=traffic, serve=serve,
        )
        chaos = [ChaosEvent(epoch=0, stage="dispatch",
                            kind="mosaic", count=1)]
        out["permanent"] = (
            SoakRunner(cfg, chaos=chaos, emit=lines.append).run(),
            _rows(lines),
        )
    finally:
        mp.undo()
        resilience.reset()
        health.reset()
    return out


def _rows(lines):
    parsed = [json.loads(line) for line in lines]
    return [p["detail"] for p in parsed if p["metric"] == "soak_epoch"]


# ----------------------------------------------------------------- grammar
def test_parse_chaos_schedule_aliases_and_forgiveness(capsys):
    sched = parse_chaos_schedule(
        "2:dispatch:transient:3; 4:device_sync:permanent:1;bogus;"
        "5:pack:hang:2"
    )
    assert [
        (e.epoch, e.stage, e.kind, e.count) for e in sched
    ] == [
        (2, "dispatch", "aborted", 3),   # transient alias
        (4, "device_sync", "mosaic", 1),        # permanent alias
        (5, "pack", "hang", 2),                 # literal kinds pass through
    ]
    assert "bogus" in capsys.readouterr().err
    assert parse_chaos_schedule(None) == []
    assert parse_chaos_schedule("") == []


def test_chaos_spec_for_epoch_joins_same_epoch_events():
    sched = parse_chaos_schedule("1:dispatch:transient:2;1:pack:mosaic:1")
    assert chaos_spec_for_epoch(sched, 1) == \
        "dispatch:aborted:2,pack:mosaic:1"
    assert chaos_spec_for_epoch(sched, 0) == ""


def test_rearm_faults_refreshes_identical_spec(monkeypatch):
    """Consecutive chaos epochs with the SAME spec string must each get
    a fresh fault budget: the injector keeps exhausted counts while the
    env string is unchanged, so the soak re-arms at epoch boundaries."""
    monkeypatch.setenv("LHTPU_FAULT_INJECT", "dispatch:mosaic:1")
    resilience.rearm_faults()
    with pytest.raises(Exception):
        resilience.maybe_inject("dispatch")
    resilience.maybe_inject("dispatch")  # count exhausted: no-op
    resilience.rearm_faults()  # same env string, fresh budget
    with pytest.raises(Exception):
        resilience.maybe_inject("dispatch")


# ------------------------------------------------------- chaos soak (fast)
def test_two_epoch_chaos_soak_digest_parity(soak_results):
    """Transient chaos at epoch 0 of 2: the soak must pass, stay
    un-wedged and balanced, and its per-epoch verdict digests must be
    bit-identical to the chaos-free replay (faults change HOW a verdict
    is reached, never the verdict)."""
    res, rows = soak_results["transient"]

    assert res["verdict"] == "pass", res["reasons"]
    assert res["mismatches_total"] == 0
    assert res["replay"]["ran"] is True
    assert res["replay"]["digests_match"] is True
    assert len(rows) == 2
    assert all(r["accounting_balanced"] for r in rows)
    assert not any(r["wedged"] for r in rows)
    assert rows[0]["phase"] == "chaos" and rows[0]["retries"] >= 1
    assert rows[0]["chaos"] == "dispatch:aborted:1"
    # a transient is absorbed in-stage: nothing degrades
    assert rows[0]["degraded_dispatches"] == 0
    assert res["degraded_time_fraction"] < 1.0


def test_repromotion_after_permanent_chaos(soak_results):
    """A permanent fault at epoch 0 of 3 trips the primary rung's
    breaker (host bisection serves the epoch); within recovery_epochs
    the breaker must re-close and the path return to the primary rung —
    scored by the repromotion block and degraded_time_fraction."""
    res, rows = soak_results["permanent"]

    assert res["verdict"] == "pass", res["reasons"]
    assert res["mismatches_total"] == 0  # degraded, never wrong
    assert rows[0]["degraded"] and rows[0]["degraded_dispatches"] >= 1
    assert res["repromotion"]["required"] is True
    assert res["repromotion"]["ok"] is True
    assert res["repromotion"]["epochs_after_chaos"] <= 2
    assert all(
        s == "closed" for s in rows[-1]["breakers"].values()
    )
    assert 0.0 < res["degraded_time_fraction"] < 1.0


# ---------------------------------------------------------------- watchdog
def test_watchdog_force_degrades_wedged_slot(monkeypatch):
    """A verify seam that never returns (stuck slot) must not hang the
    soak: the watchdog force-degrades the in-flight batch + queues, the
    epoch ends wedged-but-balanced, and the run completes."""
    from lighthouse_tpu.loadgen import soak as soak_mod

    health.configure(sentinels=[health.BreakerFlapSentinel()])
    never = threading.Event()
    real_loop = ServingLoop

    def wedged_loop(cfg, *, clock=None, backend=None, **kw):
        def verify(sets):
            never.wait()  # a slot that never answers
            return [True] * len(sets)

        return real_loop(cfg, clock=clock, verify=verify)

    monkeypatch.setattr(soak_mod, "ServingLoop", wedged_loop)
    lines: list[str] = []
    cfg = SoakConfig(
        epochs=1, seed=5, replay=False,
        watchdog_min_s=0.2, watchdog_k=0.0,
        traffic=_traffic(poison_rate=0.0, slots=1),
        serve=ServeConfig(batch_target=2, batch_deadline_ms=10.0),
    )
    res = SoakRunner(cfg, chaos=[], emit=lines.append).run()

    rows = _rows(lines)
    assert rows[0]["wedged"] is True
    assert rows[0]["force_degraded"] >= 1
    assert rows[0]["served"] == 0
    assert rows[0]["accounting_balanced"] is True
    assert res["watchdog_fired"] == 1
    # a fully-wedged run cannot pass: degraded for its entire lifetime
    assert res["verdict"] == "fail"
    assert res["degraded_time_fraction"] == 1.0


# -------------------------------------------------------------- accounting
def test_accounting_disjoint_under_shed_and_force_degrade():
    """finish() accounting identity under combined stress: everything
    offered lands in exactly one of served / shed / dropped /
    force-degraded / pending."""
    loop = ServingLoop(
        ServeConfig(batch_target=100, batch_deadline_ms=10_000.0,
                    admit_high=2, admit_low=1),
        clock=VirtualClock(),
        verify=lambda sets: [True] * len(sets),
    )
    events = [te.event for te in TrafficGenerator(
        _traffic(poison_rate=0.0, slots=2)
    ).generate()]
    assert len(events) >= 4
    for ev in events:
        loop.offer(ev)  # no processing: gate closes at depth 2
    forced = loop.watchdog_force_degrade(reason="drill")
    report = loop.finish()

    acc = report["accounting"]
    assert acc["balanced"] is True
    assert acc["served"] == 0
    assert acc["force_degraded"] == forced == 2
    assert acc["shed"] == len(events) - 2
    assert acc["pending"] == 0
    assert (acc["served"] + acc["shed"] + acc["dropped"]
            + acc["force_degraded"] + acc["pending"]
            ) == report["events_offered"] == len(events)
    assert report["watchdog"]["fired"] == 1


def test_late_waking_wedged_handler_not_double_counted():
    """The generation counter: a handler that wakes AFTER the watchdog
    reassigned its batch must not also record it as served."""
    gate = threading.Event()
    release = threading.Event()

    def verify(sets):
        gate.set()
        release.wait(timeout=10.0)  # wedged until the test releases it
        return [True] * len(sets)

    loop = ServingLoop(
        ServeConfig(batch_target=2, batch_deadline_ms=10.0),
        clock=VirtualClock(), verify=verify,
    )
    events = TrafficGenerator(_traffic(poison_rate=0.0, slots=1)).generate()

    worker = threading.Thread(
        target=lambda: loop.run(events), daemon=True
    )
    worker.start()
    assert gate.wait(timeout=10.0)  # handler is now wedged in verify
    forced = loop.watchdog_force_degrade(reason="test")
    assert forced >= 1
    release.set()  # the wedged handler wakes late...
    worker.join(timeout=10.0)
    assert not worker.is_alive()

    report = loop.finish()
    acc = report["accounting"]
    # ...and its batch stays force-degraded, never ALSO served
    assert acc["force_degraded"] >= forced
    assert acc["balanced"] is True
