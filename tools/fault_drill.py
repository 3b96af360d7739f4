"""Fault drill: the bench warm path under a matrix of injected faults.

For every (dispatch stage × fault kind) cell, injects one deterministic
fault via ``LHTPU_FAULT_INJECT=<stage>:<kind>:1`` and runs a warm
``verify_signature_sets`` batch through the resilient backend, then
checks the contract of `common/resilience.py`:

* a *transient* kind (``aborted`` — an ABORTED runtime status)
  must be absorbed by an in-stage retry: verdict True, >=1 retry
  recorded, no degradation;
* a *permanent* kind (``mosaic`` — the literal r04 failure) must trip
  the rung's circuit breaker and answer from a lower ladder rung:
  verdict True, >=1 degraded dispatch recorded.

Prints a pass/fail table (or one JSON line with ``--json``) and exits
nonzero if any cell broke the contract — so every rung of the
degradation ladder is exercised in CI without a TPU. ``--quick`` runs
a 3-stage subset (the tier-1 smoke in tests/test_resilience.py calls
run_drill directly with the same subset).

Usage:  python tools/fault_drill.py [--quick] [--json]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lighthouse_tpu.common.stages import CANONICAL_STAGES  # noqa: E402

#: the drillable subset of the canonical grammar: a new canonical stage
#: joins the drill matrix automatically; the multi-chip/fallback/bench
#: stages need topologies this host drill can't fake.
STAGES = tuple(
    s for s in CANONICAL_STAGES
    if s not in ("sharded_dispatch", "native_fallback", "bench_device")
)
QUICK_STAGES = ("hash_to_curve", "dispatch", "device_sync")
#: stages the grouped-triage path actually enters (it never builds an
#: MSM schedule — per-group accumulators are incompatible with the
#: single global MSM fold). Includes the ISSUE 10 hash sub-stages: a
#: dedup fault must degrade in place to the identity plan (bit-identical
#: verdicts), map/cofactor faults ride the normal ladder.
TRIAGE_STAGES = ("pack", "hash_to_curve", "htc_dedup", "htc_map",
                 "htc_cofactor", "scalars", "dispatch", "device_sync")

#: kind -> (classifier category, human label)
KINDS = (
    ("aborted", "transient"),
    ("mosaic", "permanent"),
)


def _mk_sets():
    """A tiny valid batch in the same (S=2, K=2) compile bucket the
    fast test tier already pays for."""
    from lighthouse_tpu.crypto.bls.api import (
        AggregateSignature,
        SecretKey,
        SignatureSet,
    )

    sks = [SecretKey.from_int(i + 7) for i in range(3)]
    m0, m1 = b"\x11" * 32, b"\x22" * 32
    s0 = SignatureSet.single_pubkey(sks[0].sign(m0), sks[0].public_key(), m0)
    agg = AggregateSignature.aggregate([sks[1].sign(m1), sks[2].sign(m1)])
    s1 = SignatureSet.multiple_pubkeys(
        agg, [sks[1].public_key(), sks[2].public_key()], m1
    )
    return [s0, s1]


def _total(counter) -> float:
    return sum(v for _, v in counter.items())


def run_drill(stages=STAGES, kinds=KINDS, sets=None, backend=None,
              pipelined: bool = False):
    """Run the injection matrix; returns a list of per-cell dicts with
    an ``ok`` verdict each. Restores the env and resilience state it
    touched (safe to call from tests).

    ``pipelined=True`` drills the microbatch pipeline instead of the
    single-shot dispatch: the batch is doubled to two chunks and
    LHTPU_PIPELINE forced on with a 2-set chunk size, so per-chunk
    retries and mid-pipeline breaker trips meet the SAME contract (each
    chunk stays in the (S=2, K=2) compile bucket the fast tier pays
    for)."""
    from lighthouse_tpu.common import resilience
    from lighthouse_tpu.jax_backend import JaxBackend

    if backend is None:
        backend = JaxBackend()
    if sets is None:
        sets = _mk_sets()
        if pipelined:
            sets = sets + _mk_sets()  # two chunks of the same bucket

    saved = {
        k: os.environ.get(k)
        for k in ("LHTPU_FAULT_INJECT", "LHTPU_RETRY_BASE_MS",
                  "LHTPU_PIPELINE", "LHTPU_PIPELINE_MIN_SETS",
                  "LHTPU_PIPELINE_CHUNK")
    }
    os.environ["LHTPU_RETRY_BASE_MS"] = "0"  # no backoff sleeps in a drill
    os.environ.pop("LHTPU_FAULT_INJECT", None)
    if pipelined:
        os.environ["LHTPU_PIPELINE"] = "1"
        os.environ["LHTPU_PIPELINE_MIN_SETS"] = "2"
        os.environ["LHTPU_PIPELINE_CHUNK"] = "2"
    else:
        os.environ["LHTPU_PIPELINE"] = "0"
    results = []
    try:
        # Healthy warm pass: pays the one compile and pins the baseline
        # verdict every drilled cell must reproduce.
        assert backend.verify_signature_sets(sets), "healthy warm pass failed"
        healthy_path = backend.last_path

        for stage in stages:
            for kind, category in kinds:
                resilience.reset()
                retries0 = _total(resilience.RETRIES_TOTAL)
                degraded0 = _total(resilience.DEGRADED_TOTAL)
                os.environ["LHTPU_FAULT_INJECT"] = f"{stage}:{kind}:1"
                error = None
                try:
                    verdict = backend.verify_signature_sets(sets)
                except Exception as exc:  # contract breach, not a crash
                    verdict = None
                    cat, kind_c = resilience.classify(exc)
                    error = f"{type(exc).__name__}: {exc} [{cat}/{kind_c}]"
                finally:
                    os.environ.pop("LHTPU_FAULT_INJECT", None)
                retries = _total(resilience.RETRIES_TOTAL) - retries0
                degraded = _total(resilience.DEGRADED_TOTAL) - degraded0
                if category == "transient":
                    ok = verdict is True and retries >= 1 and degraded == 0
                else:
                    ok = verdict is True and degraded >= 1
                results.append({
                    "mode": "pipelined" if pipelined else "single-shot",
                    "stage": stage,
                    "kind": kind,
                    "category": category,
                    "verdict": verdict,
                    "retries": retries,
                    "degraded": degraded,
                    "path": backend.last_path,
                    "healthy_path": healthy_path,
                    "error": error,
                    "ok": ok,
                })
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        resilience.reset()
    return results


def _mk_poisoned_sets():
    """The triage drill batch: 4 sets, index 2 signed over the wrong
    message — round 1 at (S=4, G=2) plus one (S=2, G=2) gs=1 refinement,
    the same two compile buckets tests/test_triage.py pays for."""
    from lighthouse_tpu.crypto.bls.api import (
        AggregateSignature,
        SecretKey,
        SignatureSet,
    )

    sks = [SecretKey.from_int(i + 7) for i in range(6)]
    bad_msg = b"\xee" * 32
    sets = []
    for i in range(4):
        m = bytes([i + 1]) * 32
        signed = bad_msg if i == 2 else m
        if i % 2 == 0:
            sets.append(SignatureSet.single_pubkey(
                sks[i].sign(signed), sks[i].public_key(), m
            ))
        else:
            a, b = sks[i], sks[i + 2]
            agg = AggregateSignature.aggregate([a.sign(signed), b.sign(m)])
            sets.append(SignatureSet.multiple_pubkeys(
                agg, [a.public_key(), b.public_key()], m
            ))
    return sets, [True, True, False, True]


def run_drill_triaged(stages=TRIAGE_STAGES, kinds=KINDS, backend=None):
    """Poisoned-batch drill through verify_signature_sets_triaged
    (ISSUE 5): every cell must keep the per-set verdicts bit-correct —
    a transient is retried in place, a permanent fault may degrade to
    the host bisection (fallback recorded) but NEVER crash or flip a
    verdict."""
    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.common import resilience

    if backend is None:
        backend = jb.JaxBackend()
    sets, expected = _mk_poisoned_sets()

    saved = {
        k: os.environ.get(k)
        for k in ("LHTPU_FAULT_INJECT", "LHTPU_RETRY_BASE_MS",
                  "LHTPU_PIPELINE", "LHTPU_VERDICT_GROUPS")
    }
    os.environ["LHTPU_RETRY_BASE_MS"] = "0"
    os.environ["LHTPU_PIPELINE"] = "0"
    os.environ["LHTPU_VERDICT_GROUPS"] = "2"
    os.environ.pop("LHTPU_FAULT_INJECT", None)
    results = []
    try:
        got = backend.verify_signature_sets_triaged(sets)
        assert got == expected, f"healthy triage pass broken: {got}"
        healthy_path = backend.last_path

        for stage in stages:
            for kind, category in kinds:
                resilience.reset()
                retries0 = _total(resilience.RETRIES_TOTAL)
                degraded0 = _total(resilience.DEGRADED_TOTAL)
                os.environ["LHTPU_FAULT_INJECT"] = f"{stage}:{kind}:1"
                error = None
                try:
                    verdict = backend.verify_signature_sets_triaged(sets)
                except Exception as exc:  # contract breach, not a crash
                    verdict = None
                    cat, kind_c = resilience.classify(exc)
                    error = f"{type(exc).__name__}: {exc} [{cat}/{kind_c}]"
                finally:
                    os.environ.pop("LHTPU_FAULT_INJECT", None)
                retries = _total(resilience.RETRIES_TOTAL) - retries0
                degraded = _total(resilience.DEGRADED_TOTAL) - degraded0
                fallback = jb.dispatch_stage_report()["triage"].get(
                    "fallback"
                )
                if category == "transient":
                    ok = (verdict == expected and retries >= 1
                          and degraded == 0 and fallback is None)
                else:
                    ok = verdict == expected and degraded >= 1
                results.append({
                    "mode": "triaged",
                    "stage": stage,
                    "kind": kind,
                    "category": category,
                    "verdict": verdict == expected if verdict is not None
                    else None,
                    "retries": retries,
                    "degraded": degraded,
                    "path": backend.last_path,
                    "healthy_path": healthy_path,
                    "fallback": fallback,
                    "error": error,
                    "ok": ok,
                })
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        resilience.reset()
    return results


def run_drill_slot_load(kinds=KINDS, backend=None):
    """Fault injection MID-SLOT into a loadgen replay (ISSUE 6
    satellite): a tiny deterministic 2-slot poison-storm stream is
    served through the ServingLoop on a virtual clock while
    ``dispatch:<kind>:1`` fires inside the first verification batch.
    Contract: the replay never crashes, every served verdict still
    matches the generator's ground truth (transient → retried in place;
    permanent → degraded to host bisection), and the SLO report stays
    well-formed.

    Shape economics: aggregate-only traffic at committee_size=2 with
    batch_target=4 and a 100 ms deadline dispatches partial batches of
    2 two-key sets — the (S=2, K=2, G=2) triage bucket
    tests/test_triage.py already pays for; no new compiles."""
    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.common import resilience
    from lighthouse_tpu.loadgen.serve import (
        ServeConfig,
        ServingLoop,
        VirtualClock,
    )
    from lighthouse_tpu.loadgen.traffic import (
        TrafficConfig,
        TrafficGenerator,
        expected_verdicts,
    )

    if backend is None:
        backend = jb.JaxBackend()

    cfg = TrafficConfig(
        validators=64, slots=2, seconds_per_slot=2.0,
        committees_per_slot=2, committee_size=2,
        unaggregated_per_slot=0, sync_per_slot=0, blocks=False,
        poison_rate=0.25, key_pool=8, seed=7,
    )
    gen = TrafficGenerator(cfg)

    def _serve():
        loop = ServingLoop(
            ServeConfig(batch_target=4, batch_deadline_ms=100.0),
            clock=VirtualClock(),
            verify=lambda sets: backend.verify_signature_sets_triaged(sets),
        )
        events = gen.generate()
        report = loop.run(events)
        return loop.verdicts, expected_verdicts(events), report

    saved = {
        k: os.environ.get(k)
        for k in ("LHTPU_FAULT_INJECT", "LHTPU_RETRY_BASE_MS",
                  "LHTPU_PIPELINE", "LHTPU_VERDICT_GROUPS")
    }
    os.environ["LHTPU_RETRY_BASE_MS"] = "0"
    os.environ["LHTPU_PIPELINE"] = "0"
    os.environ["LHTPU_VERDICT_GROUPS"] = "2"
    os.environ.pop("LHTPU_FAULT_INJECT", None)
    results = []
    try:
        got, expected, _ = _serve()  # healthy warm replay (pays compile)
        assert got == expected and any(not v for v in expected.values()), (
            f"healthy slot-load replay broken: {got} vs {expected}"
        )
        healthy_path = backend.last_path

        for kind, category in kinds:
            resilience.reset()
            retries0 = _total(resilience.RETRIES_TOTAL)
            degraded0 = _total(resilience.DEGRADED_TOTAL)
            os.environ["LHTPU_FAULT_INJECT"] = f"dispatch:{kind}:1"
            error = None
            verdicts_ok = None
            slo_ok = False
            try:
                got, expected, report = _serve()
                verdicts_ok = got == expected
                slo = report.get("slo") or {}
                slo_ok = all(
                    k in slo for k in
                    ("p50_ms", "p99_ms", "shed", "dropped", "within_budget")
                )
            except Exception as exc:  # contract breach, not a crash
                cat, kind_c = resilience.classify(exc)
                error = f"{type(exc).__name__}: {exc} [{cat}/{kind_c}]"
            finally:
                os.environ.pop("LHTPU_FAULT_INJECT", None)
            retries = _total(resilience.RETRIES_TOTAL) - retries0
            degraded = _total(resilience.DEGRADED_TOTAL) - degraded0
            if category == "transient":
                ok = bool(verdicts_ok) and slo_ok and retries >= 1 \
                    and degraded == 0
            else:
                ok = bool(verdicts_ok) and slo_ok and degraded >= 1
            results.append({
                "mode": "slot-load",
                "stage": "dispatch",
                "kind": kind,
                "category": category,
                "verdict": verdicts_ok,
                "retries": retries,
                "degraded": degraded,
                "path": backend.last_path,
                "healthy_path": healthy_path,
                "slo_ok": slo_ok,
                "error": error,
                "ok": ok,
            })
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        resilience.reset()
    return results


#: multichip cells: faults fired INSIDE the sharded dispatch stage
#: (transient → retried in place on the mesh; permanent, including a
#: simulated chip loss, → circuit-break down to single-chip).
MULTICHIP_KINDS = (
    ("aborted", "transient"),
    ("mosaic", "permanent"),
    ("chip_loss", "permanent"),
)


def run_drill_multichip(kinds=MULTICHIP_KINDS, backend=None):
    """Sharded-dispatch drill (ISSUE 8): faults injected into the
    multi-chip composition while a batch spans the mesh.

    Contract per cell:

    * transient — the sharded dispatch is retried in place: verdict
      True, >=1 retry, the path STAYS sharded, no degradation;
    * permanent (``mosaic`` lowering bug, ``chip_loss`` device loss) —
      the sharded breaker opens and the SAME packed grids re-dispatch
      on one chip: verdict True (bit-identical), >=1 degraded dispatch,
      ``path`` records the ``+sharded-fallback`` rung.

    Returns [] when the process has fewer than 2 devices (the mesh
    can't form; main() forces an 8-way host mesh before jax init so the
    standalone drill always exercises these rows).
    """
    from lighthouse_tpu.common import resilience
    from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet
    from lighthouse_tpu.jax_backend import JaxBackend
    from lighthouse_tpu.parallel import engine

    if engine.topology().n_devices < 2:
        return []
    if backend is None:
        backend = JaxBackend()

    # 8 single-pubkey sets: the (S=8, K=1) bucket the sharded test tier
    # already compiles, one real set per chip on an 8-way mesh.
    sks = [SecretKey.from_int(i + 7) for i in range(8)]
    msgs = [bytes([i + 1]) * 32 for i in range(8)]
    sets = [
        SignatureSet.single_pubkey(sks[i].sign(msgs[i]),
                                   sks[i].public_key(), msgs[i])
        for i in range(8)
    ]

    saved = {
        k: os.environ.get(k)
        for k in ("LHTPU_FAULT_INJECT", "LHTPU_RETRY_BASE_MS",
                  "LHTPU_PIPELINE", "LHTPU_SHARDED_VERIFY",
                  "LHTPU_DEVICES")
    }
    os.environ["LHTPU_RETRY_BASE_MS"] = "0"
    os.environ["LHTPU_PIPELINE"] = "0"
    os.environ["LHTPU_SHARDED_VERIFY"] = "1"
    os.environ.pop("LHTPU_FAULT_INJECT", None)
    results = []
    try:
        resilience.reset()
        engine.reset()
        assert backend.verify_signature_sets(sets), \
            "healthy sharded warm pass failed"
        healthy_path = backend.last_path
        assert "sharded" in healthy_path, (
            f"sharded path did not engage: {healthy_path}"
        )

        for kind, category in kinds:
            resilience.reset()
            engine.reset()
            retries0 = _total(resilience.RETRIES_TOTAL)
            degraded0 = _total(resilience.DEGRADED_TOTAL)
            os.environ["LHTPU_FAULT_INJECT"] = f"sharded_dispatch:{kind}:1"
            error = None
            try:
                verdict = backend.verify_signature_sets(sets)
            except Exception as exc:  # contract breach, not a crash
                verdict = None
                cat, kind_c = resilience.classify(exc)
                error = f"{type(exc).__name__}: {exc} [{cat}/{kind_c}]"
            finally:
                os.environ.pop("LHTPU_FAULT_INJECT", None)
            retries = _total(resilience.RETRIES_TOTAL) - retries0
            degraded = _total(resilience.DEGRADED_TOTAL) - degraded0
            path = backend.last_path
            if category == "transient":
                ok = (verdict is True and retries >= 1 and degraded == 0
                      and "sharded" in path
                      and "+sharded-fallback" not in path)
            else:
                ok = (verdict is True and degraded >= 1
                      and path.endswith("+sharded-fallback"))
            results.append({
                "mode": "multichip",
                "stage": "sharded_dispatch",
                "kind": kind,
                "category": category,
                "verdict": verdict,
                "retries": retries,
                "degraded": degraded,
                "path": path,
                "healthy_path": healthy_path,
                "reason": engine.parallel_report().get("reason"),
                "error": error,
                "ok": ok,
            })
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        resilience.reset()
        engine.reset()
    return results


def run_drill_lazy(backend=None):
    """ISSUE 18 cell: a mosaic fault while the LAZY-REDUCTION pairing
    tower is live must degrade down the ladder with per-set verdicts
    bit-identical to the strict baseline — the knob changes limb
    representatives mid-chain, never verdicts, and a faulted lazy
    dispatch must land on a rung that agrees with strict bit-for-bit.

    The knobs are read at TRACE time, so the in-process jit caches are
    dropped around the flip (the persistent .jax_cache absorbs the
    recompiles after the first run)."""
    import jax

    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.common import resilience

    if backend is None:
        backend = jb.JaxBackend()
    sets, expected = _mk_poisoned_sets()

    saved = {
        k: os.environ.get(k)
        for k in ("LHTPU_FAULT_INJECT", "LHTPU_RETRY_BASE_MS",
                  "LHTPU_PIPELINE", "LHTPU_VERDICT_GROUPS",
                  "LHTPU_LAZY_REDUCE", "LHTPU_MXU_CARRY")
    }
    os.environ["LHTPU_RETRY_BASE_MS"] = "0"
    os.environ["LHTPU_PIPELINE"] = "0"
    os.environ["LHTPU_VERDICT_GROUPS"] = "2"
    os.environ.pop("LHTPU_FAULT_INJECT", None)
    os.environ.pop("LHTPU_LAZY_REDUCE", None)
    os.environ.pop("LHTPU_MXU_CARRY", None)
    results = []
    try:
        baseline = backend.verify_signature_sets_triaged(sets)
        assert baseline == expected, f"strict baseline broken: {baseline}"

        os.environ["LHTPU_LAZY_REDUCE"] = "1"
        jax.clear_caches()
        healthy = backend.verify_signature_sets_triaged(sets)
        lazy_parity = healthy == baseline

        resilience.reset()
        retries0 = _total(resilience.RETRIES_TOTAL)
        degraded0 = _total(resilience.DEGRADED_TOTAL)
        os.environ["LHTPU_FAULT_INJECT"] = "dispatch:mosaic:1"
        error = None
        try:
            verdict = backend.verify_signature_sets_triaged(sets)
        except Exception as exc:  # contract breach, not a crash
            verdict = None
            cat, kind_c = resilience.classify(exc)
            error = f"{type(exc).__name__}: {exc} [{cat}/{kind_c}]"
        finally:
            os.environ.pop("LHTPU_FAULT_INJECT", None)
        retries = _total(resilience.RETRIES_TOTAL) - retries0
        degraded = _total(resilience.DEGRADED_TOTAL) - degraded0
        if not lazy_parity:
            error = (error or "") + f" lazy healthy pass != strict: {healthy}"
        results.append({
            "mode": "lazy-tower",
            "stage": "dispatch",
            "kind": "mosaic",
            "category": "permanent",
            "verdict": verdict == baseline if verdict is not None else None,
            "retries": retries,
            "degraded": degraded,
            "path": backend.last_path,
            "healthy_path": None,
            "error": error or None,
            "ok": lazy_parity and verdict == baseline and degraded >= 1,
        })
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        resilience.reset()
        jax.clear_caches()  # drop the lazy-traced programs
    return results


def run_drill_soak():
    """Multi-epoch soak drill (ISSUE 7): two endurance cells over
    ``loadgen/soak.SoakRunner`` on the virtual clock, aggregate-only
    traffic pinned to the (S=2, K=2, G=2) bucket the other rows pay
    for (batch_target=2 with a deadline past within-slot jitter, so
    per-epoch seed shifts can never form an odd-sized batch that would
    need a fresh device program mid-soak).

    * ``transient mid-soak``: one ``dispatch:aborted`` fault at
      epoch 1 of 3 — the run must PASS, re-promote to the primary rung
      within the recovery budget, and its per-epoch verdict digests
      must match the chaos-free replay bit-for-bit.
    * ``permanent sustained``: ``dispatch:mosaic`` at epochs 1 and 2 of
      3 — the run must end DEGRADED (breakers open, host bisection
      serving), never crash, and still keep every verdict correct."""
    from lighthouse_tpu.common import health, resilience
    from lighthouse_tpu.loadgen.serve import ServeConfig
    from lighthouse_tpu.loadgen.soak import ChaosEvent, SoakConfig, SoakRunner
    from lighthouse_tpu.loadgen.traffic import TrafficConfig

    saved = {
        k: os.environ.get(k)
        for k in ("LHTPU_FAULT_INJECT", "LHTPU_RETRY_BASE_MS",
                  "LHTPU_PIPELINE", "LHTPU_VERDICT_GROUPS",
                  "LHTPU_BREAKER_COOLDOWN_S")
    }
    os.environ["LHTPU_RETRY_BASE_MS"] = "0"
    os.environ["LHTPU_PIPELINE"] = "0"
    os.environ["LHTPU_VERDICT_GROUPS"] = "2"
    # breakers must half-open inside the drill's wall time
    os.environ["LHTPU_BREAKER_COOLDOWN_S"] = "0.01"
    os.environ.pop("LHTPU_FAULT_INJECT", None)
    # Deterministic sentinels only: the RSS/jit-cache sentinels react to
    # unrelated compile activity earlier in the drill matrix.
    health.configure(sentinels=[
        health.BreakerFlapSentinel(), health.SloBreachSentinel(),
    ])

    def _cfg(replay: bool) -> SoakConfig:
        return SoakConfig(
            epochs=3, seed=7, backend="jax", recovery_epochs=2,
            replay=replay,
            traffic=TrafficConfig(
                validators=64, slots=2, seconds_per_slot=2.0,
                committees_per_slot=2, committee_size=2,
                unaggregated_per_slot=0, sync_per_slot=0, blocks=False,
                poison_rate=0.25, key_pool=8, seed=7,
            ),
            serve=ServeConfig(batch_target=2, batch_deadline_ms=1000.0),
        )

    cells = (
        ("aborted", "transient",
         [ChaosEvent(epoch=1, stage="dispatch",
                     kind="aborted", count=1)], True),
        ("mosaic", "permanent",
         [ChaosEvent(epoch=e, stage="dispatch", kind="mosaic", count=1)
          for e in (1, 2)], False),
    )
    results = []
    try:
        for kind, category, chaos, replay in cells:
            resilience.reset()
            retries0 = _total(resilience.RETRIES_TOTAL)
            degraded0 = _total(resilience.DEGRADED_TOTAL)
            error = None
            res = None
            try:
                res = SoakRunner(_cfg(replay), chaos=chaos, emit=None).run()
            except Exception as exc:  # contract breach, not a crash
                cat, kind_c = resilience.classify(exc)
                error = f"{type(exc).__name__}: {exc} [{cat}/{kind_c}]"
            retries = _total(resilience.RETRIES_TOTAL) - retries0
            degraded = _total(resilience.DEGRADED_TOTAL) - degraded0
            if res is None:
                ok = False
            elif category == "transient":
                # chaos absorbed: verdict passes end-to-end, the ladder
                # re-promotes, and the replay digests are bit-identical
                ok = (res["verdict"] == "pass"
                      and res["mismatches_total"] == 0
                      and res["repromotion"]["required"]
                      and res["repromotion"]["ok"]
                      and res["replay"]["digests_match"] is True)
            else:
                # sustained permanent: degrade (both chaos epochs), keep
                # verdicts exact, never crash or wedge
                ok = (res is not None
                      and not any(r.startswith("crashed")
                                  for r in res["reasons"])
                      and res["mismatches_total"] == 0
                      and res["degraded_epochs"] >= 2
                      and res["degraded_time_fraction"] < 1.0
                      and res["watchdog_fired"] == 0)
            results.append({
                "mode": "soak",
                "stage": "dispatch",
                "kind": kind,
                "category": category,
                "verdict": (res["mismatches_total"] == 0
                            if res is not None else None),
                "retries": retries,
                "degraded": degraded,
                "path": None if res is None else f"soak:{res['verdict']}",
                "healthy_path": None,
                "degraded_time_fraction":
                    res["degraded_time_fraction"] if res else None,
                "error": error,
                "ok": ok,
            })
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        resilience.reset()
        health.reset()
    return results


def run_drill_stream():
    """Continuous-stream drill (ISSUE 15): faults injected into the
    cross-slot StreamScheduler mid-stream over two epochs of mixed
    block + aggregate + attestation traffic on the virtual clock.

    Contract per cell (and the scheduler invariants the matrix pins):

    * ``transient mid-stream`` (``dispatch:aborted``) — retried
      in place: zero mismatches, >=1 retry, no rung degradation;
    * ``permanent mid-stream`` (``dispatch:mosaic``) — degrades down
      the ladder (>=1 degraded dispatch) with every verdict still
      correct;
    * ``cache fault`` (``sched_cache:assert``) — the composition cache
      degrades to the identity transform in place: >=1 recorded cache
      fault, zero mismatches (a cache fault may cost the dedup win,
      never a verdict);
    * ``preempted`` (no injected fault) — a block arriving inside an
      attestation coalescing window preempts the remainder, which
      re-enqueues EXACTLY once: preemptions >=1, every event served
      once, and the offered == served+shed+dropped+pending accounting
      identity stays balanced.

    Every cell additionally requires zero blocks shed or dropped."""
    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.common import resilience
    from lighthouse_tpu.loadgen.scheduler import (
        SchedulerConfig,
        StreamRunner,
        StreamScheduler,
    )
    from lighthouse_tpu.loadgen.serve import VirtualClock
    from lighthouse_tpu.loadgen.traffic import (
        TimedEvent,
        TrafficConfig,
        TrafficGenerator,
    )
    from lighthouse_tpu.network.processor import WorkType

    backend = jb.JaxBackend()
    traffic = TrafficConfig(
        validators=64, slots=2, seconds_per_slot=2.0,
        committees_per_slot=2, committee_size=2,
        unaggregated_per_slot=2, sync_per_slot=0, blocks=True,
        poison_rate=0.25, key_pool=8, seed=7, peers=4,
    )

    def _sched_cfg(**over) -> SchedulerConfig:
        base = dict(
            batch_target=4, agg_deadline_ms=100.0, att_deadline_ms=100.0,
            sync_deadline_ms=100.0, dispatch_ms=0.0, cache=False,
        )
        base.update(over)
        return SchedulerConfig(**base)

    def _run(chaos: str, **cfg_over) -> dict:
        runner = StreamRunner(
            traffic, 2, _sched_cfg(**cfg_over), clock=VirtualClock(),
            verify=lambda sets: backend.verify_signature_sets_triaged(sets),
            chaos=chaos, emit=None,
        )
        return runner.run()

    saved = {
        k: os.environ.get(k)
        for k in ("LHTPU_FAULT_INJECT", "LHTPU_RETRY_BASE_MS",
                  "LHTPU_PIPELINE", "LHTPU_VERDICT_GROUPS")
    }
    os.environ["LHTPU_RETRY_BASE_MS"] = "0"
    os.environ["LHTPU_PIPELINE"] = "0"
    os.environ["LHTPU_VERDICT_GROUPS"] = "2"
    os.environ.pop("LHTPU_FAULT_INJECT", None)

    cells = (
        ("aborted", "transient", "0:dispatch:aborted:1", {}),
        ("mosaic", "permanent", "0:dispatch:mosaic:1", {}),
        ("assert", "cache", "0:sched_cache:assert:1", {"cache": True}),
        ("preempted", "preempt", "", {}),
    )
    results = []
    try:
        healthy = _run("")  # healthy warm run (pays the compiles)
        assert healthy["verdicts"]["mismatches"] == 0, (
            f"healthy stream run broken: {healthy['verdicts']}"
        )
        healthy_path = backend.last_path

        for kind, category, chaos, cfg_over in cells:
            resilience.reset()
            retries0 = _total(resilience.RETRIES_TOTAL)
            degraded0 = _total(resilience.DEGRADED_TOTAL)
            error = None
            rep = None
            preempted = 0
            try:
                if category == "preempt":
                    # Crafted window: a full attestation batch opens at
                    # t=0 with modeled dispatch occupancy; the block
                    # lands inside the window and must preempt it.
                    events = TrafficGenerator(traffic).generate()
                    atts = [te for te in events if te.event.work_type
                            is WorkType.GOSSIP_ATTESTATION]
                    aggs = [te for te in events if te.event.work_type
                            is WorkType.GOSSIP_AGGREGATE]
                    blocks = [te for te in events if te.event.work_type
                              is WorkType.GOSSIP_BLOCK]
                    stream = [TimedEvent(t=0.0, event=te.event)
                              for te in atts + aggs]
                    stream += [TimedEvent(t=0.005 + i * 0.001,
                                          event=te.event)
                               for i, te in enumerate(blocks)]
                    stream.sort(key=lambda te: te.t)
                    sched = StreamScheduler(
                        _sched_cfg(batch_target=8, att_deadline_ms=0.0,
                                   agg_deadline_ms=0.0, dispatch_ms=10.0),
                        clock=VirtualClock(),
                        verify=lambda sets:
                            backend.verify_signature_sets_triaged(sets),
                    )
                    rep = sched.run(stream)
                    preempted = rep["sched"]["preempted_batches"]
                else:
                    rep = _run(chaos, **cfg_over)
                    preempted = rep["sched"]["preempted_batches"]
            except Exception as exc:  # contract breach, not a crash
                cat, kind_c = resilience.classify(exc)
                error = f"{type(exc).__name__}: {exc} [{cat}/{kind_c}]"
            retries = _total(resilience.RETRIES_TOTAL) - retries0
            degraded = _total(resilience.DEGRADED_TOTAL) - degraded0
            if rep is None:
                ok = False
            else:
                block = rep["sched"]["block"]
                base_ok = (rep["verdicts"]["mismatches"] == 0
                           and block["shed"] == 0
                           and block["dropped"] == 0
                           and rep["accounting"]["balanced"])
                if category == "transient":
                    ok = base_ok and retries >= 1 and degraded == 0
                elif category == "permanent":
                    ok = base_ok and degraded >= 1
                elif category == "cache":
                    ok = (base_ok
                          and rep["sched"]["cache"]["faults"] >= 1)
                else:  # preempt: exactly-once re-enqueue accounting
                    ok = (base_ok and preempted >= 1
                          and rep["accounting"]["pending"] == 0
                          and rep["events_served"]
                          == rep["events_offered"]
                          - rep["slo"]["shed"] - rep["slo"]["dropped"])
            results.append({
                "mode": "stream",
                "stage": "sched_cache" if category == "cache"
                         else "dispatch",
                "kind": kind,
                "category": category,
                "verdict": (rep["verdicts"]["mismatches"] == 0
                            if rep is not None else None),
                "retries": retries,
                "degraded": degraded,
                "preempted": preempted,
                "path": backend.last_path,
                "healthy_path": healthy_path,
                "error": error,
                "ok": ok,
            })
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        resilience.reset()
    return results


def run_drill_weather():
    """Chain-weather drill (ISSUE 17): faults injected while the
    stream is under adversarial weather.

    * ``reorg_transient`` — a transient dispatch fault during a reorg
      storm retries in place: zero mismatches, >=1 retry, no rung
      degradation, competing-head blocks all served (never shed);
    * ``flood_permanent`` — a permanent fault mid slashing-flood
      degrades down the ladder without shedding a single block, and
      attestations keep being served (the starvation guard's contract);
    * ``slasher`` — a ``slasher``-stage fault falls back to the host
      scan with IDENTICAL findings: the degraded run's findings digest
      must equal the clean run's bit-for-bit, with >=1 recorded
      fallback.

    Every cell additionally requires its scenario SLOs to pass."""
    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.common import resilience
    from lighthouse_tpu.loadgen.scheduler import (
        SchedulerConfig,
        StreamRunner,
    )
    from lighthouse_tpu.loadgen.serve import VirtualClock
    from lighthouse_tpu.loadgen.traffic import TrafficConfig

    backend = jb.JaxBackend()
    traffic = TrafficConfig(
        validators=64, slots=2, seconds_per_slot=2.0,
        committees_per_slot=2, committee_size=2,
        unaggregated_per_slot=2, sync_per_slot=1, blocks=True,
        poison_rate=0.25, key_pool=8, seed=7, peers=4,
    )

    def _run(chaos: str, weather: str) -> dict:
        runner = StreamRunner(
            traffic, 2,
            SchedulerConfig(
                batch_target=4, agg_deadline_ms=100.0,
                att_deadline_ms=100.0, sync_deadline_ms=100.0,
                slashing_deadline_ms=100.0, dispatch_ms=0.0, cache=False,
            ),
            clock=VirtualClock(),
            verify=lambda sets: backend.verify_signature_sets_triaged(sets),
            chaos=chaos, emit=None, weather=weather,
        )
        return runner.run()

    saved = {
        k: os.environ.get(k)
        for k in ("LHTPU_FAULT_INJECT", "LHTPU_RETRY_BASE_MS",
                  "LHTPU_PIPELINE", "LHTPU_VERDICT_GROUPS",
                  "LHTPU_SLASHER_DEVICE", "LHTPU_SLASHER_CHUNK",
                  "LHTPU_SLASHER_HISTORY")
    }
    os.environ["LHTPU_RETRY_BASE_MS"] = "0"
    os.environ["LHTPU_PIPELINE"] = "0"
    os.environ["LHTPU_VERDICT_GROUPS"] = "2"
    # Drill-sized sink engine on the host scan: the fault/fallback
    # contract is mode-independent and this keeps the matrix compiles
    # pinned to the cached buckets.
    os.environ["LHTPU_SLASHER_DEVICE"] = "0"
    os.environ["LHTPU_SLASHER_CHUNK"] = "64"
    os.environ["LHTPU_SLASHER_HISTORY"] = "64"
    os.environ.pop("LHTPU_FAULT_INJECT", None)

    flood = "*:slashing_flood:2.0"
    cells = (
        ("aborted", "reorg_transient",
         "0:dispatch:aborted:1", "*:reorg_storm:0.9"),
        ("mosaic", "flood_permanent", "0:dispatch:mosaic:1", flood),
        ("assert", "slasher", "0:slasher:assert:1", flood),
    )
    results = []
    try:
        resilience.reset()
        clean_digest = _run("", flood)["sched"]["slasher"]["findings_digest"]

        for kind, category, chaos, weather in cells:
            resilience.reset()
            retries0 = _total(resilience.RETRIES_TOTAL)
            degraded0 = _total(resilience.DEGRADED_TOTAL)
            error = None
            rep = None
            try:
                rep = _run(chaos, weather)
            except Exception as exc:  # contract breach, not a crash
                cat, kind_c = resilience.classify(exc)
                error = f"{type(exc).__name__}: {exc} [{cat}/{kind_c}]"
            retries = _total(resilience.RETRIES_TOTAL) - retries0
            degraded = _total(resilience.DEGRADED_TOTAL) - degraded0
            if rep is None:
                ok = False
            else:
                block = rep["sched"]["block"]
                base_ok = (rep["verdicts"]["mismatches"] == 0
                           and block["shed"] == 0
                           and block["dropped"] == 0
                           and rep["accounting"]["balanced"]
                           and rep["scenarios"]["ok"])
                if category == "reorg_transient":
                    ok = base_ok and retries >= 1 and degraded == 0
                elif category == "flood_permanent":
                    served = rep["slo"]["per_class"]
                    ok = (base_ok and degraded >= 1
                          and served["attestation"]["served"] > 0)
                else:  # slasher fault: host fallback, findings intact
                    sl = rep["sched"]["slasher"]
                    engine = sl["engine"] or {}
                    ok = (base_ok
                          and engine.get("fallbacks", 0) >= 1
                          and sl["findings_digest"] == clean_digest)
            results.append({
                "mode": "weather",
                "stage": "slasher" if category == "slasher"
                         else "dispatch",
                "kind": kind,
                "category": category,
                "verdict": (rep["verdicts"]["mismatches"] == 0
                            if rep is not None else None),
                "retries": retries,
                "degraded": degraded,
                "path": backend.last_path,
                "healthy_path": None,
                "error": error,
                "ok": ok,
            })
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        resilience.reset()
    return results


def main() -> int:
    json_mode = "--json" in sys.argv
    stages = QUICK_STAGES if "--quick" in sys.argv else STAGES
    out = sys.stderr if json_mode else sys.stdout

    # Force an 8-way host mesh BEFORE jax initializes so the multichip
    # rows always run (the flag only affects the host CPU platform —
    # real TPU meshes are untouched).
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from lighthouse_tpu.common.compile_cache import configure

    configure()

    triage_stages = QUICK_STAGES if "--quick" in sys.argv else TRIAGE_STAGES
    n_multichip = len(MULTICHIP_KINDS) if len(jax.devices()) > 1 else 0
    print(f"device={jax.devices()[0].platform} "
          f"cells={(len(stages) + len(QUICK_STAGES) + len(triage_stages) + 1) * len(KINDS) + 2 + n_multichip + 4 + 3 + 1}",
          file=out)
    results = run_drill(stages=stages)
    # Pipelined matrix (3-stage subset): per-chunk retry and
    # mid-pipeline breaker trips must meet the same contract.
    results += run_drill(stages=QUICK_STAGES, pipelined=True)
    # Poisoned-batch triage matrix (ISSUE 5): per-set verdicts must
    # survive every cell — degrade to host bisection, never crash.
    results += run_drill_triaged(stages=triage_stages)
    # Serving-loop matrix (ISSUE 6): transients injected mid-slot into
    # a loadgen poison-storm replay — degrade, never crash.
    results += run_drill_slot_load()
    # Multichip matrix (ISSUE 8): faults inside the sharded dispatch —
    # transients retried on the mesh, chip loss degrades to one chip.
    results += run_drill_multichip()
    # Soak matrix (ISSUE 7): multi-epoch chaos → re-promotion + digest
    # parity; sustained permanents degrade, never crash.
    results += run_drill_soak()
    # Continuous-stream matrix (ISSUE 15): faults mid-stream through
    # the cross-slot scheduler — transients retry in place, permanents
    # degrade down the ladder, a cache fault degrades to the identity
    # transform, blocks are never shed, and preemption-abandoned
    # batches re-enqueue exactly once.
    results += run_drill_stream()
    # Chain-weather matrix (ISSUE 17): faults during reorg storms and
    # slashing floods — retries in place / ladder degradation with
    # blocks never shed, and a slasher-stage fault falling back to the
    # host scan with bit-identical findings.
    results += run_drill_weather()
    # Lazy-tower cell (ISSUE 18): a mosaic fault with LHTPU_LAZY_REDUCE
    # live must degrade to a rung bit-identical to the strict baseline.
    # Runs LAST: it clears the in-process jit caches around the knob
    # flip, which would force earlier drills to re-trace.
    results += run_drill_lazy()
    failed = [r for r in results if not r["ok"]]

    header = (f"{'mode':12s} {'stage':14s} {'kind':16s} {'class':10s} "
              f"{'verdict':8s} {'retries':8s} {'degraded':9s} "
              f"{'path':22s} result")
    print(header, file=out)
    print("-" * len(header), file=out)
    for r in results:
        print(
            f"{r['mode']:12s} {r['stage']:14s} {r['kind']:16s} "
            f"{r['category']:10s} "
            f"{str(r['verdict']):8s} {r['retries']:<8.0f} "
            f"{r['degraded']:<9.0f} {str(r['path']):22s} "
            f"{'PASS' if r['ok'] else 'FAIL' + (' ' + r['error'] if r['error'] else '')}",
            file=out,
        )
    print(f"fault drill: {len(results) - len(failed)}/{len(results)} cells "
          f"passed", file=out)
    if json_mode:
        print(json.dumps({
            "metric": "fault_drill_cells_passed",
            "value": len(results) - len(failed),
            "unit": "cells",
            "vs_baseline": 0.0,
            "detail": {"cells": len(results), "results": results},
        }), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
