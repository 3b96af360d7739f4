"""Benchmark: BLS signature-sets verified per second on the device backend.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Workload = BASELINE.json config #4 shape (gossip attestation batch): S
single-pubkey signature sets, one distinct message each.

Three rates are measured (VERDICT r1 items 2-3 — the headline must be
END-TO-END and the baseline MEASURED):

  * e2e        — JaxBackend.verify_signature_sets from SignatureSet
                 objects to bool: batched device hash-to-G2 (fused SSWU
                 kernels), host assembly, transfer, fused verify. This is
                 the headline `value`.
  * device     — steady-state device time of the fused verify program
                 alone (inputs pre-staged, hash points precomputed).
  * native CPU — the C++ BLS12-381 implementation (native/bls12381.cpp:
                 Montgomery 6x64, same RLC batch check, hash included),
                 timed on a subsample and scaled. `vs_baseline` = e2e /
                 native. The pure-Python oracle rate is also recorded.

Correctness is re-validated on the benchmark device before timing (valid
batch -> True, tampered lane -> False) — pinning the one true
TPU-specific hazard (bf16 matmul passes silently breaking integer
exactness; see ops/limb.py precision notes).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from lighthouse_tpu.common import knobs

# Set once the real headline JSON line is printed: the watchdog/catch-all
# must never append a second, contradictory line after a successful run
# (e.g. a hang or exception in TPU-runtime teardown).
_HEADLINE_EMITTED = False
# The exit code a deliberate sys.exit chose before any teardown hang —
# the watchdog must not overwrite a loud rc=1 with rc=0.
_INTENDED_RC = 0


def _note_swallowed(where: str, exc: BaseException) -> None:
    """Classifier-routed record for every exception bench absorbs: the
    resilience (category, kind) plus the repr land on stderr, so an
    absorbed failure is attributable instead of silent (LH5xx)."""
    from lighthouse_tpu.common import resilience

    category, kind = resilience.classify(exc)
    sys.stderr.write(
        f"bench: {where} swallowed {category}/{kind}: {exc!r}\n"
    )


def _stage_report() -> dict | None:
    """Per-stage attribution of the most recent BLS dispatch (stage wall
    times, error counts, the stage the last failure raised in). Reads
    the already-imported backend module only — a fallback line must not
    trigger fresh imports mid-crash."""
    jb = sys.modules.get("lighthouse_tpu.jax_backend")
    if jb is None:
        return None
    try:
        return jb.dispatch_stage_report()
    except Exception as exc:
        _note_swallowed("stage_report", exc)
        return None


def _resilience_detail() -> dict:
    """{"retries": {stage:kind -> n}, "path": last dispatch path} for
    embedding in EVERY emitted JSON line (success and fallback): a
    surviving-but-retried run must say it retried, a degraded run must
    name the rung that answered (ISSUE 2 satellite)."""
    report = _stage_report() or {}
    return {
        "retries": report.get("retries") or {},
        "path": report.get("path"),
    }


def _dedup_detail() -> dict:
    """Cumulative message-dedup traffic (distinct vs collapsed rows seen
    by blsrt.dedup_plan) and the deduped-batch cache counters, so a
    message_dup_sweep line shows how much hash work the dedup front-end
    actually removed (ISSUE 10 tentpole c)."""
    blsrt = sys.modules.get("lighthouse_tpu.blsrt")
    if blsrt is None:
        return {}
    try:
        report = blsrt.input_cache_report()
        return {
            "messages_distinct": blsrt.DEDUP_MESSAGES.value(
                outcome="distinct"
            ),
            "messages_collapsed": blsrt.DEDUP_MESSAGES.value(
                outcome="duplicate"
            ),
            "batch_cache": report.get("htc_batches") or {},
        }
    except Exception as exc:
        _note_swallowed("dedup_detail", exc)
        return {}


def _pipeline_detail() -> dict:
    """{"pipeline": {...}} for EVERY emitted JSON line: whether the last
    verify took the pipelined microbatch path, its chunk count and
    overlap seconds (host pack time hidden behind device compute), and
    the cross-call input-cache hit rates — so perf deltas between
    pipeline-on and pipeline-off lines are attributable (ISSUE 4)."""
    report = _stage_report() or {}
    pipe = report.get("pipeline") or {}
    caches = report.get("cache") or {}
    return {
        "pipeline": {
            "enabled": bool(pipe.get("enabled")),
            "chunks": pipe.get("chunks", 0),
            "chunk_size": pipe.get("chunk_size"),
            "overlap_s": pipe.get("overlap_s", 0.0),
            "host_exposed_s": pipe.get("host_exposed_s", 0.0),
            "cache_hit_rate": {
                name: c.get("hit_rate", 0.0)
                for name, c in caches.items()
            },
        }
    }


_LINT_CACHE: dict | None = None


def _lint_detail() -> dict:
    """{"lint": {version, clean, findings}} for EVERY emitted JSON
    line — provenance: which lint suite blessed the tree this number
    came from, and whether it was actually clean (ISSUE 9). Linted
    once per process (pure-AST, sub-second) and cached."""
    global _LINT_CACHE
    if _LINT_CACHE is None:
        try:
            from tools.lint import LINT_VERSION, run_lint

            findings = run_lint(os.path.dirname(os.path.abspath(__file__)))
            _LINT_CACHE = {
                "version": LINT_VERSION,
                "clean": not findings,
                "findings": len(findings),
            }
        except Exception as exc:
            _note_swallowed("lint_detail", exc)
            _LINT_CACHE = {"version": None, "clean": None, "findings": None}
    return {"lint": _LINT_CACHE}


def _triage_detail() -> dict:
    """{"triage": {...}} for JSON lines: whether the last triaged verify
    used grouped device verdicts, its round/dispatch/group-outcome
    counts and any fallback route (ISSUE 5)."""
    report = _stage_report() or {}
    return {"triage": report.get("triage") or {"enabled": False}}


def _parallel_detail() -> dict:
    """{"devices": N, "parallel": {...}} for EVERY emitted JSON line
    (ISSUE 8): the mesh width the last dispatch actually used plus the
    engine's routing snapshot (mesh shape, padded sets / pad waste,
    single-chip reason or cross-chip fold ms) — so a multi-chip perf
    line is attributable to its sharding and a single-chip line says
    why it stayed on one chip."""
    report = _stage_report() or {}
    par = report.get("parallel") or {"devices": 1}
    return {"devices": par.get("devices", 1), "parallel": par}


def _forced_sets(backend, sets) -> bool:
    """Backend warmup/measured verify with the same bounded
    transient-retry policy as raw device calls (ISSUE 5 satellite: a
    transient device fault inside a bare warmup assert must cost a
    retry, not the run)."""
    from lighthouse_tpu.common import resilience

    return resilience.call_with_retries(
        lambda: bool(backend.verify_signature_sets(sets)),
        stage="bench_device",
    )


def _emit_config_fallback(metric: str, config: int, err: Exception) -> None:
    """Per-config error line: the remaining configs and the headline
    still run and emit, and the run exits non-zero at its end."""
    global _INTENDED_RC
    _INTENDED_RC = 1
    print(json.dumps({
        "metric": metric,
        "value": 0.0,
        "unit": "sets/sec",
        "vs_baseline": 0.0,
        "error": f"{type(err).__name__}: {err}"[:400],
        "smoke": True,
        "detail": {
            "config": config,
            "stages": _stage_report(),
            **_resilience_detail(),
            **_parallel_detail(),
            **_lint_detail(),
        },
    }), flush=True)


def _emit_fallback(err: str) -> None:
    """The always-parseable last-resort JSON line (metric matches the
    mode actually being run, so a slot-mode failure doesn't record a
    bogus 0.0 under the batch metric). A failure inside dispatch
    carries its per-stage breakdown and named failing stage."""
    global _HEADLINE_EMITTED
    if _HEADLINE_EMITTED:
        return
    mode = os.environ.get("BENCH_MODE", "")
    chain = mode == "slot-chain" or "--slot-chain" in sys.argv
    slot = chain or mode == "slot" or "--slot" in sys.argv
    load = mode == "slot-load" or "--slot-load" in sys.argv
    stream = mode == "stream" or "--stream" in sys.argv
    multi = mode == "multichip" or "--devices" in sys.argv
    metric = ("multichip_sets_per_sec" if multi
              else "stream_sets_per_sec" if stream
              else "slot_load_sets_per_sec" if load
              else "chain_slot_attester_verifications_per_sec" if chain
              else "full_slot_attester_verifications_per_sec" if slot
              else "bls_sets_verified_per_sec")
    line = {
        "metric": metric,
        "value": 0.0,
        "unit": ("sets/sec" if load or multi or stream
                 else "attester-signatures/sec" if slot else "sets/sec"),
        "vs_baseline": 0.0,
        "error": err[:400],
        # A fallback line never re-validated verdicts on the program it
        # reports — mark it so downstream tooling can't mistake it for
        # a measured MULTICHIP/headline result (ISSUE 8).
        "smoke": True,
    }
    line.update(_resilience_detail())
    line.update(_pipeline_detail())
    line.update(_triage_detail())
    line.update(_parallel_detail())
    line.update(_lint_detail())
    stages = _stage_report()
    if stages is not None:
        line["stages"] = stages
    print(json.dumps(line), flush=True)
    _HEADLINE_EMITTED = True


def slot_chain_mode() -> None:
    """Config #5 THROUGH THE CHAIN (VERDICT r3 item 9): a slot of
    gossip-shaped aggregates at registry scale through beacon_chain +
    processor batching — head effects out, TPU-offloaded batch
    verification in the router's aggregate worker. Prints one JSON
    line; `last_path` shows the composed device program used."""
    import jax

    from lighthouse_tpu.chain.scale import ScaleChain
    from lighthouse_tpu.consensus.config import mainnet_spec

    N = int(os.environ.get("BENCH_VALIDATORS", "1000000"))
    sc = ScaleChain(N, mainnet_spec())
    sc.slot_clock.set_slot(1)
    sc.chain.per_slot_task()

    t0 = time.perf_counter()
    aggs = sc.make_slot_aggregates(1)
    prep_s = time.perf_counter() - t0

    res = sc.drive_slot(aggs)
    attesters = sum(
        len(sa.message.aggregate.aggregation_bits) for sa in aggs
    )
    ok = (res["attestations_rejected"] == 0
          and res["aggregates_verified"] == len(aggs))
    from lighthouse_tpu.crypto.bls.backends import get_backend

    be = get_backend("jax")
    print(json.dumps({
        "metric": "chain_slot_attester_verifications_per_sec",
        "value": round(attesters / res["slot_wall_s"], 1) if ok else 0.0,
        "unit": "attester-signatures/sec",
        "vs_baseline": 0.0,
        "detail": {
            "validators": N,
            "aggregates": len(aggs),
            "attesters": attesters,
            "verified": bool(ok),
            "slot_wall_ms": round(res["slot_wall_s"] * 1e3, 1),
            "slot_budget_s": 12.0,
            "within_budget": res["slot_wall_s"] < 12.0,
            "prep_s": round(prep_s, 1),
            "table_build_s": round(sc.table_build_s, 1),
            "compress_s": round(sc.compress_s, 1),
            "state_build_s": round(sc.state_build_s, 1),
            "chain_init_s": round(sc.chain_init_s, 1),
            "last_path": getattr(be, "last_path", None),
            "stages": _stage_report(),
            "device": jax.devices()[0].platform,
            **_resilience_detail(),
            **_pipeline_detail(),
            **_triage_detail(),
            **_parallel_detail(),
            **_lint_detail(),
        },
    }), flush=True)
    global _HEADLINE_EMITTED
    _HEADLINE_EMITTED = True


def slot_load_mode() -> None:
    """ISSUE 6 tentpole: a 1M-validator-shaped SLOT REPLAY served to an
    SLO. Deterministic traffic (loadgen/traffic.py, seeded) paced on the
    wall clock through the serving loop (loadgen/serve.py): deadline
    batching, admission control, triage verdicts per event. Prints one
    BENCH_SLOT-style JSON line whose ``detail.slo`` carries
    p50/p99 enqueue→verdict latency, shed/drop counts and
    ``within_budget``; ``stream_digest``/``verdict_digest`` prove
    seed-reproducibility.

    Knobs: BENCH_VALIDATORS / BENCH_SLOTS / BENCH_POISON / BENCH_SEED /
    BENCH_SPS / BENCH_UNAGG / BENCH_COLD, plus the serving loop's
    LHTPU_BATCH_TARGET / LHTPU_BATCH_DEADLINE_MS / LHTPU_ADMIT_HIGH /
    LHTPU_ADMIT_LOW / LHTPU_SLO_BUDGET_MS. Off-TPU the shape shrinks
    (committees<=2, committee_size<=4, short slots) so the CPU fallback
    answers in seconds on reused compile buckets instead of paying
    mainnet-sized XLA:CPU compiles."""
    import jax

    from lighthouse_tpu.chain.scale import slot_shape
    from lighthouse_tpu.consensus.config import mainnet_spec
    from lighthouse_tpu.crypto.bls import api as bls_api
    from lighthouse_tpu.loadgen.serve import (
        ServeConfig,
        ServingLoop,
        WallClock,
        verdict_digest,
    )
    from lighthouse_tpu.loadgen.traffic import (
        TrafficConfig,
        TrafficGenerator,
        stream_digest,
    )

    dev = jax.devices()[0].platform
    tpu = dev == "tpu"
    N = int(os.environ.get("BENCH_VALIDATORS", "1000000"))
    slots = int(os.environ.get("BENCH_SLOTS", "2"))
    poison = float(os.environ.get("BENCH_POISON", "0.0"))
    seed = int(os.environ.get("BENCH_SEED", "20260805"))
    sps = float(os.environ.get("BENCH_SPS", "12.0" if tpu else "1.0"))
    unagg = int(os.environ.get("BENCH_UNAGG", "512" if tpu else "4"))

    committees, csize = slot_shape(N, mainnet_spec())
    if not tpu:
        # CPU fallback: keep the mainnet-derived STRUCTURE but shrink it
        # to shapes whose compile cost is test-tier.
        committees, csize = min(committees, 2), min(csize, 4)

    os.environ.setdefault("LHTPU_BATCH_TARGET", "256" if tpu else "4")
    os.environ.setdefault("LHTPU_ADMIT_HIGH", "8192" if tpu else "64")
    serve_cfg = ServeConfig.from_env()

    traffic_cfg = TrafficConfig(
        validators=N, slots=slots, seconds_per_slot=sps,
        committees_per_slot=committees, committee_size=csize,
        unaggregated_per_slot=unagg, poison_rate=poison, seed=seed,
        key_pool=4096 if tpu else 32,
    )
    gen = TrafficGenerator(traffic_cfg)
    t0 = time.perf_counter()
    events = gen.generate()
    prep_s = time.perf_counter() - t0
    sdigest = stream_digest(events)

    if os.environ.get("BENCH_COLD") != "1":
        # Pay compiles for the batch shapes the replay will dispatch
        # (full batches + stragglers) so the timed run sees steady state.
        warm = [te.payload.sig_set for te in events]
        for size in {min(serve_cfg.batch_target, len(warm)), 1}:
            if size > 0:
                bls_api.verify_signature_sets_triaged(
                    warm[:size], backend="jax"
                )

    loop = ServingLoop(serve_cfg, clock=WallClock(), backend="jax")
    t0 = time.perf_counter()
    report = loop.run(events)
    wall_s = time.perf_counter() - t0

    slo = report["slo"]
    served = report["events_served"]
    # Ground-truth audit over ADMITTED events: triage verdicts must
    # match the generator's intent exactly (mismatches==0 is the
    # poison-storm acceptance gate).
    ok = report["verdicts"]["mismatches"] == 0 and served > 0
    print(json.dumps({
        "metric": "slot_load_sets_per_sec",
        "value": round(served / wall_s, 2) if ok else 0.0,
        "unit": "sets/sec",
        "vs_baseline": 0.0,
        "detail": {
            "validators": N, "slots": slots,
            "committees": committees, "committee_size": csize,
            "unaggregated_per_slot": unagg,
            "seconds_per_slot": sps,
            "poison_rate": poison, "seed": seed,
            "events": len(events),
            "events_served": served,
            "verified": bool(ok),
            "mismatches": report["verdicts"]["mismatches"],
            "invalid_verdicts": report["verdicts"]["invalid"],
            "stream_digest": sdigest,
            "verdict_digest": verdict_digest(loop.verdicts),
            "slo": slo,
            "within_budget": slo["within_budget"],
            "admission": report["admission"],
            "accounting": report.get("accounting"),
            "health": report.get("health"),
            "batches": report["batches"],
            "replay_wall_s": round(wall_s, 2),
            "prep_s": round(prep_s, 2),
            "serve_config": {
                "batch_target": serve_cfg.batch_target,
                "batch_deadline_ms": serve_cfg.batch_deadline_ms,
                "admit_high": serve_cfg.admit_high,
                "admit_low": serve_cfg.admit_low,
            },
            "device": dev,
            "stages": _stage_report(),
            **_resilience_detail(),
            **_pipeline_detail(),
            **_triage_detail(),
            **_parallel_detail(),
            **_lint_detail(),
        },
    }), flush=True)
    global _HEADLINE_EMITTED
    _HEADLINE_EMITTED = True


def stream_mode() -> None:
    """ISSUE 15 tentpole: CONTINUOUS multi-epoch mixed traffic through
    the cross-slot StreamScheduler (loadgen/scheduler.py) at an
    overload factor. Blocks preempt coalescing windows and are never
    shed; aggregates/attestations/sync coalesce to class deadlines and
    shed under the health-governed watermarks; committee compositions
    repeating across slots hit the cross-slot aggregate-pubkey cache.

    Emits one ``stream_epoch_served`` JSON line per epoch and a final
    ``stream_sets_per_sec`` headline whose ``detail.slo.per_class``
    carries per-class p50/p99/shed/preemption counts. Off-TPU the run
    uses the deterministic virtual clock with a modeled per-chunk
    dispatch cost CALIBRATED to the 1x arrival rate, so
    ``BENCH_OVERLOAD`` (default 2.0) compresses arrivals to exactly
    that factor over service capacity. With ``LHTPU_CHAOS_SCHEDULE``
    set, the same run re-executes chaos-free and the two verdict
    digests must match bit-for-bit (``detail.replay``).

    Chain weather (ISSUE 17): the stream runs a slashing flood by
    default (BENCH_SLASHING attester/proposer slashing events per
    committee-slot riding the block-adjacent SLASHING lane, votes fed
    to the device slasher) and can layer reorg storms
    (BENCH_REORG probability), non-finality stalls (BENCH_NONFINAL
    epochs), and sync period boundaries (BENCH_SYNC_PERIOD slots).
    Each enabled axis is scored as a scenario SLO in
    ``detail.scenarios`` and folded into the headline ``verified``
    bit — "slashing flood must not starve attestations, and blocks
    are never shed" is asserted, not observed.

    Knobs: BENCH_EPOCHS / BENCH_OVERLOAD / BENCH_VALIDATORS /
    BENCH_SLOTS / BENCH_POISON / BENCH_SEED / BENCH_SPS / BENCH_UNAGG /
    BENCH_SYNC / BENCH_SLASHING / BENCH_REORG / BENCH_NONFINAL /
    BENCH_SYNC_PERIOD / BENCH_WALL=1 (force wall clock), plus the
    LHTPU_SCHED_* scheduler family."""
    import jax

    from lighthouse_tpu.chain.scale import slot_shape
    from lighthouse_tpu.common import knobs
    from lighthouse_tpu.consensus.config import mainnet_spec
    from lighthouse_tpu.crypto.bls import api as bls_api
    from lighthouse_tpu.loadgen.scheduler import (
        SchedulerConfig,
        StreamRunner,
    )
    from lighthouse_tpu.loadgen.serve import VirtualClock, WallClock
    from lighthouse_tpu.loadgen.traffic import TrafficConfig, TrafficGenerator

    dev = jax.devices()[0].platform
    tpu = dev == "tpu"
    N = int(os.environ.get("BENCH_VALIDATORS", "1000000"))
    epochs = int(os.environ.get("BENCH_EPOCHS", "3"))
    slots = int(os.environ.get("BENCH_SLOTS", "2"))
    overload = float(os.environ.get("BENCH_OVERLOAD", "2.0"))
    poison = float(os.environ.get("BENCH_POISON", "0.0"))
    seed = int(os.environ.get("BENCH_SEED", "20260805"))
    sps = float(os.environ.get("BENCH_SPS", "12.0" if tpu else "1.0"))
    unagg = int(os.environ.get("BENCH_UNAGG", "512" if tpu else "32"))
    sync = int(os.environ.get("BENCH_SYNC", "128" if tpu else "16"))
    slashing = float(os.environ.get("BENCH_SLASHING", "0.5"))
    reorg = float(os.environ.get("BENCH_REORG", "0.0"))
    nonfinal = int(os.environ.get("BENCH_NONFINAL", "0"))
    sync_period = int(os.environ.get("BENCH_SYNC_PERIOD", "0"))
    wall = tpu or os.environ.get("BENCH_WALL") == "1"

    committees, csize = slot_shape(N, mainnet_spec())
    if not tpu:
        # CPU fast tier: mainnet-derived structure, test-tier shapes.
        committees, csize = min(committees, 2), min(csize, 4)

    os.environ.setdefault("LHTPU_BATCH_TARGET", "256" if tpu else "4")
    if not tpu:
        # Small class queues so the overload factor engages the shed
        # watermarks within a fast-tier epoch (agg 24 / att 16 / sync 8).
        os.environ.setdefault("LHTPU_SCHED_QUEUE_CAP", "32")

    traffic_cfg = TrafficConfig(
        validators=N, slots=slots, seconds_per_slot=sps,
        committees_per_slot=committees, committee_size=csize,
        unaggregated_per_slot=unagg, sync_per_slot=sync,
        poison_rate=poison, seed=seed,
        key_pool=4096 if tpu else 32,
        time_scale=1.0 / max(overload, 1e-6),
        slashing_flood_rate=slashing, reorg_storm=reorg,
        non_finality_epochs=nonfinal, sync_period_boundary=sync_period,
    )

    sched_overrides = {}
    if not wall:
        # Calibrate modeled per-chunk occupancy so service capacity
        # equals the UNSCALED arrival rate: BENCH_OVERLOAD then means
        # "arrivals outpace the device by exactly this factor". Count
        # the real generated stream — the weather axes make the
        # closed-form slot arithmetic undercount.
        events_per_epoch = len(TrafficGenerator(traffic_cfg).generate())
        base_rate = events_per_epoch / max(slots * sps, 1e-9)
        sched_cfg_probe = SchedulerConfig.from_env()
        quantum = max(1, sched_cfg_probe.batch_target // 4)
        if knobs.raw("LHTPU_SCHED_DISPATCH_MS") is None:
            sched_overrides["dispatch_ms"] = round(
                quantum / base_rate * 1e3, 3
            )
    sched_cfg = SchedulerConfig.from_env(**sched_overrides)

    if os.environ.get("BENCH_COLD") != "1":
        # Warm the single-pubkey buckets the stream will dispatch (the
        # composition cache folds K-key aggregates to K=1 host-side).
        warm_events = TrafficGenerator(traffic_cfg).generate()
        singles = [te.payload.sig_set for te in warm_events
                   if len(te.payload.sig_set.signing_keys) == 1]
        for size in {min(sched_cfg.batch_target, len(singles)), 2, 1}:
            if size > 0 and len(singles) >= size:
                bls_api.verify_signature_sets_triaged(
                    singles[:size], backend="jax"
                )

    def epoch_emit(row: dict) -> None:
        print(json.dumps({
            "metric": "stream_epoch_served",
            "value": row["served"],
            "unit": "events",
            "vs_baseline": 0.0,
            "detail": row,
        }), flush=True)

    def one_run(chaos: str | None, emit) -> tuple[dict, float]:
        clock = WallClock() if wall else VirtualClock()
        runner = StreamRunner(
            traffic_cfg, epochs, sched_cfg, clock=clock, backend="jax",
            chaos=chaos, emit=emit,
        )
        t0 = time.perf_counter()
        rep = runner.run()
        return rep, time.perf_counter() - t0

    chaos_spec = knobs.knob("LHTPU_CHAOS_SCHEDULE") or ""
    report, wall_s = one_run(None, epoch_emit)
    replay = None
    if chaos_spec:
        # Chaos-parity acceptance: the chaos-free replay must produce a
        # bit-identical verdict digest (faults may cost retries and
        # rungs, never verdicts).
        from lighthouse_tpu.common import resilience as _resil

        _resil.reset()
        clean, _ = one_run("", lambda row: None)
        replay = {
            "chaos_digest": report["stream"]["verdict_digest"],
            "clean_digest": clean["stream"]["verdict_digest"],
            "digests_match": (report["stream"]["verdict_digest"]
                              == clean["stream"]["verdict_digest"]),
            # slasher findings are part of the parity contract: a fault
            # may change HOW votes were scanned, never WHAT was found
            "slasher_digests_match": (
                report["sched"]["slasher"]["findings_digest"]
                == clean["sched"]["slasher"]["findings_digest"]
            ),
        }

    served = report["events_served"]
    block = report["sched"]["block"]
    scenarios = report["scenarios"]
    ok = (report["verdicts"]["mismatches"] == 0 and served > 0
          and block["shed"] == 0 and block["dropped"] == 0
          and report["accounting"]["balanced"]
          and scenarios["ok"]
          and (replay is None or (replay["digests_match"]
                                  and replay["slasher_digests_match"])))
    print(json.dumps({
        "metric": "stream_sets_per_sec",
        "value": round(served / wall_s, 2) if ok else 0.0,
        "unit": "sets/sec",
        "vs_baseline": 0.0,
        "detail": {
            "validators": N, "epochs": epochs, "slots": slots,
            "committees": committees, "committee_size": csize,
            "unaggregated_per_slot": unagg, "sync_per_slot": sync,
            "seconds_per_slot": sps, "overload": overload,
            "poison_rate": poison, "seed": seed,
            "weather": {
                "slashing_flood_rate": slashing, "reorg_storm": reorg,
                "non_finality_epochs": nonfinal,
                "sync_period_boundary": sync_period,
            },
            "scenarios": scenarios,
            "clock": "wall" if wall else "virtual",
            "events": report["stream"]["events"],
            "events_served": served,
            "verified": bool(ok),
            "mismatches": report["verdicts"]["mismatches"],
            "invalid_verdicts": report["verdicts"]["invalid"],
            "verdict_digest": report["stream"]["verdict_digest"],
            "slo": report["slo"],
            "sched": report["sched"],
            "shed_by_class": report["shed_by_class"],
            "shed_by_reason": report["shed_by_reason"],
            "accounting": report["accounting"],
            "health": report.get("health"),
            "epoch_rows": report["stream"]["rows"],
            "replay": replay,
            "replay_wall_s": round(wall_s, 2),
            "sched_config": {
                "batch_target": sched_cfg.batch_target,
                "dispatch_ms": sched_cfg.dispatch_ms,
                "queue_cap": sched_cfg.queue_cap,
                "tenant_quota": sched_cfg.tenant_quota,
                "cache": sched_cfg.cache,
            },
            "device": dev,
            "stages": _stage_report(),
            **_resilience_detail(),
            **_pipeline_detail(),
            **_triage_detail(),
            **_parallel_detail(),
            **_lint_detail(),
        },
    }), flush=True)
    global _HEADLINE_EMITTED
    _HEADLINE_EMITTED = True


def slot_mode() -> None:
    """BASELINE config #5: a full slot at registry scale.

    BENCH_VALIDATORS validators (default 100k; 1M fits HBM) live in the
    blsrt HBM table; one slot's attestation load = BENCH_COMMITTEES
    aggregate sets of BENCH_COMMITTEE_SIZE attesters each, verified
    end-to-end through the INDEXED backend path (device gather from the
    table, device hashing, fused verify). Prints one JSON line.

    Scale trick for the fixture: sk_i = i+1, so pk_{i+1} = pk_i + G (one
    host point-add per key instead of a full scalar mul), and a set's
    aggregate signature is (sum sk_i mod r) * H(m) — one G2 mul per set.
    """
    import jax

    from lighthouse_tpu import blsrt
    from lighthouse_tpu.crypto.bls.api import (
        AggregateSignature,
        PublicKey,
        SignatureSet,
    )
    from lighthouse_tpu.crypto.bls.constants import R as CURVE_ORDER
    from lighthouse_tpu.crypto.bls.curve import g1_generator, g2_generator
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
    from lighthouse_tpu.jax_backend import JaxBackend

    N = int(os.environ.get("BENCH_VALIDATORS", "1000000"))
    S = int(os.environ.get("BENCH_COMMITTEES", "64"))
    K = int(os.environ.get("BENCH_COMMITTEE_SIZE", "512"))

    # Registry: pk_i = (i+1) * G, built ON DEVICE (blsrt.build_sequential
    # _table — batched scalar-mul + to-affine kernels; round 2's host
    # loop made 1M impractical). Verified spot-wise against the oracle.
    t0 = time.perf_counter()
    table = blsrt.build_sequential_table(N)
    g1 = g1_generator()
    from lighthouse_tpu.ops.points import g1_from_dev

    spot = [0, 1, min(N - 1, 12345)]
    pts = g1_from_dev(
        table._host_x[spot].astype(np.int32),
        table._host_y[spot].astype(np.int32),
        np.zeros(len(spot), bool),
    )
    for i, pt in zip(spot, pts):
        assert pt == g1.mul(i + 1), f"table row {i} wrong"
    blsrt.set_device_table(table)
    build_s = time.perf_counter() - t0

    # One slot's aggregate sets: committee j = indices [j*K, (j+1)*K).
    sets = []
    g2 = g2_generator()
    for j in range(S):
        lo = (j * K) % max(N - K, 1)
        idxs = list(range(lo, lo + K))
        msg = int(j).to_bytes(32, "big")
        sk_sum = sum(i + 1 for i in idxs) % CURVE_ORDER
        agg_sig = AggregateSignature(hash_to_g2(msg).mul(sk_sum))
        pks = [PublicKey.__new__(PublicKey) for _ in idxs]  # points unused
        s = SignatureSet(agg_sig, pks, msg, signing_key_indices=idxs)
        sets.append(s)

    backend = JaxBackend()
    assert backend._table_gather_args(sets, len(sets), K) is not None, (
        "indexed path not engaged"
    )
    ok = _forced_sets(backend, sets)  # compile + warm (retry-wrapped)
    t0 = time.perf_counter()
    ok = _forced_sets(backend, sets) and ok
    dt = time.perf_counter() - t0

    # Native single-core denominator on a subsample (2 sets with REAL
    # PublicKey objects reconstructed from the table planes), scaled to
    # the slot's set count. Round 2 hardcoded vs_baseline 0.0 here.
    native_slot_s = None
    native_err = None
    try:
        from lighthouse_tpu.crypto.bls.native_backend import (
            load_native_backend,
        )

        nb = load_native_backend()
        if nb is not None:
            nsub = 2
            sub = []
            for s in sets[:nsub]:
                idxs = s.signing_key_indices
                pts = g1_from_dev(
                    table._host_x[idxs].astype(np.int32),
                    table._host_y[idxs].astype(np.int32),
                    np.zeros(len(idxs), bool),
                )
                real_pks = [PublicKey(p) for p in pts]
                sub.append(SignatureSet(
                    s.signature, real_pks, s.message
                ))
            assert _forced_sets(nb, sub)  # warm
            t0 = time.perf_counter()
            assert _forced_sets(nb, sub)
            native_slot_s = (time.perf_counter() - t0) * (S / nsub)
    except Exception as e:  # record — a native/device DISAGREEMENT must
        native_err = str(e)[:200]  # not masquerade as a missing toolchain

    print(json.dumps({
        "metric": "full_slot_attester_verifications_per_sec",
        "value": round(S * K / dt, 1),
        "unit": "attester-signatures/sec",
        "vs_baseline": (
            round(native_slot_s / dt, 3) if native_slot_s else 0.0
        ),
        "detail": {
            "validators": N, "sets": S, "committee_size": K,
            "verified": bool(ok),
            "slot_ms": round(dt * 1e3, 1),
            "slot_budget_s": 12.0,
            "within_budget": dt < 12.0,
            "sets_per_sec": round(S / dt, 2),
            "native_cpu_slot_s_scaled": (
                round(native_slot_s, 2) if native_slot_s else None
            ),
            "native_cpu_error": native_err,
            "table_build_s": round(build_s, 1),
            "table_hbm_mb": round(N * 96 / 1e6, 1),
            # Pubkey deserialization/subgroup checks are excluded BY
            # DESIGN: registry keys enter the HBM table once at import
            # (validated there), per-slot verification ships indices.
            "pubkey_objects": "table-resident (deserialization at import)",
            "stages": _stage_report(),
            "device": jax.devices()[0].platform,
            **_resilience_detail(),
            **_pipeline_detail(),
            **_triage_detail(),
            **_parallel_detail(),
            **_lint_detail(),
        },
    }), flush=True)
    global _HEADLINE_EMITTED
    _HEADLINE_EMITTED = True


def _devices_cli_arg() -> list[int] | None:
    """Device counts of ``--devices`` (comma-separated, e.g. ``1,2,4,8``)
    or ``BENCH_DEVICES``; None when the multichip sweep isn't requested.
    A bare ``--devices`` means the default {1,2,4,8} sweep."""
    raw = os.environ.get("BENCH_DEVICES", "")
    if "--devices" in sys.argv:
        i = sys.argv.index("--devices")
        if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("-"):
            raw = sys.argv[i + 1]
        elif not raw:
            raw = "1,2,4,8"
    if not raw and os.environ.get("BENCH_MODE") == "multichip":
        raw = "1,2,4,8"
    if not raw:
        return None
    try:
        ns = sorted({max(1, int(x)) for x in raw.split(",") if x.strip()})
    except ValueError:
        ns = []
    return ns or [1, 2, 4, 8]


def devices_mode() -> None:
    """ISSUE 8 exit proof: ``bench.py --devices 1,2,4,8`` sweeps the
    mesh width and emits one MULTICHIP JSON line per N.

    Off-TPU the sweep forces a host mesh wide enough for max(N)
    (``--xla_force_host_platform_device_count``, set BEFORE jax
    initializes; only the CPU platform reads it), so the multi-chip
    dispatch composition is exercised end-to-end on CPU.

    Every non-smoke line is gated on verdict RE-VALIDATION on the
    actual program the sweep step dispatches: the good batch must
    verify True, a tampered batch False, and for N>1 the engine must
    report an N-way mesh — only then does the line carry
    ``"smoke": false``. Any step that can't prove that emits a
    ``"smoke": true`` line instead (never a bare MULTICHIP number).
    """
    global _HEADLINE_EMITTED

    ns = _devices_cli_arg() or [1, 2, 4, 8]
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={max(ns)}"
        ).strip()

    import jax

    tpu = jax.devices()[0].platform == "tpu"

    from lighthouse_tpu.common import pipeline, resilience
    from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet
    from lighthouse_tpu.jax_backend import JaxBackend
    from lighthouse_tpu.parallel import engine

    S = int(os.environ.get("BENCH_SETS", "4096" if tpu else "8"))
    REPS = int(os.environ.get("BENCH_REPS", "3" if tpu else "2"))

    sks = [SecretKey.from_int(i + 301) for i in range(S)]
    msgs = [i.to_bytes(32, "big") for i in range(S)]
    pks = [sk.public_key() for sk in sks]
    sets = [
        SignatureSet.single_pubkey(sk.sign(m), pk, m)
        for sk, pk, m in zip(sks, pks, msgs)
    ]
    # Tampered lane: set 0 claims set 1's pubkey — the sharded program
    # itself must say False (the verdict re-validation gate).
    tampered = list(sets)
    tampered[0] = SignatureSet.single_pubkey(
        sks[0].sign(msgs[0]), pks[1 % S], msgs[0]
    )

    backend = JaxBackend()
    base_rate = None
    with knobs.scoped_env(
        {"LHTPU_DEVICES": None, "LHTPU_SHARDED_VERIFY": None}
    ):
        for n in ns:
            os.environ["LHTPU_DEVICES"] = str(n)
            os.environ["LHTPU_SHARDED_VERIFY"] = "1" if n > 1 else "0"
            resilience.reset()
            engine.reset()
            pipeline.reset()
            try:
                good = _forced_sets(backend, sets)
                path = backend.last_path
                par = engine.parallel_report()
                bad = (not _forced_sets(backend, tampered)) if S > 1 \
                    else True
                validated = bool(good) and bool(bad) and (
                    n == 1 or (par.get("devices") == n
                               and "sharded" in path)
                )
                if not validated:
                    print(json.dumps({
                        "metric": "multichip_sets_per_sec",
                        "mode": "MULTICHIP",
                        "value": 0.0,
                        "unit": "sets/sec",
                        "vs_baseline": 0.0,
                        "smoke": True,
                        "error": (
                            f"re-validation failed at devices={n}: "
                            f"good={bool(good)} tampered_caught={bool(bad)} "
                            f"mesh={par.get('devices')} path={path}"
                        ),
                        "detail": {
                            "devices": n, "batch_sets": S,
                            "validated": False, "parallel": par,
                            "stages": _stage_report(),
                            **_resilience_detail(),
                            **_lint_detail(),
                        },
                    }), flush=True)
                    continue

                t0 = time.perf_counter()
                for _ in range(REPS):
                    assert _forced_sets(backend, sets)
                dt = (time.perf_counter() - t0) / REPS
                rate = S / dt
                if n == 1 and base_rate is None:
                    base_rate = rate
                fold_ms = engine.measure_fold_ms(n) if n > 1 else 0.0
                par = engine.parallel_report()
                par["fold_ms"] = fold_ms
                print(json.dumps({
                    "metric": "multichip_sets_per_sec",
                    "mode": "MULTICHIP",
                    "value": round(rate, 3),
                    "unit": "sets/sec",
                    "vs_baseline": (
                        round(rate / base_rate, 3) if base_rate else 0.0
                    ),
                    "smoke": False,
                    "detail": {
                        "devices": n,
                        "batch_sets": S,
                        "validated": True,
                        "path": backend.last_path,
                        "parallel": par,
                        "e2e_ms_per_batch": round(dt * 1e3, 2),
                        "device": jax.devices()[0].platform,
                        "stages": _stage_report(),
                        **_resilience_detail(),
                        **_lint_detail(),
                        **_pipeline_detail(),
                    },
                }), flush=True)
            except Exception as e:
                _emit_config_fallback("multichip_sets_per_sec", n, e)
    _HEADLINE_EMITTED = True


def _pipeline_cli_arg() -> str | None:
    """Value of ``--pipeline`` (on | off | sweep), or None when absent.
    A bare ``--pipeline`` means sweep (paired on+off lines)."""
    if "--pipeline" not in sys.argv:
        return None
    i = sys.argv.index("--pipeline")
    if i + 1 < len(sys.argv) and sys.argv[i + 1] in ("on", "off", "sweep"):
        return sys.argv[i + 1]
    return "sweep"


def pipeline_sweep(backend, sets, reps: int, which: str) -> None:
    """``--pipeline {on,off}`` sweep: time the synchronous e2e path with
    the pipelined engine forced on and/or off and emit one
    ``bls_pipeline_sweep`` JSON line per mode from a single run, each
    carrying ``detail.pipeline`` — chunk count, overlap seconds, cache
    hit rates — so the on/off perf delta is attributable."""
    modes = ("off", "on") if which == "sweep" else (which,)
    for mode in modes:
        with knobs.scoped_env(
            {"LHTPU_PIPELINE": "1" if mode == "on" else "0"}
        ):
            from lighthouse_tpu.common import pipeline as _pl

            _pl.reset()  # else the off line reports the prior on-run
            assert _forced_sets(backend, sets)  # warm (compiles)
            t0 = time.perf_counter()
            for _ in range(reps):
                assert _forced_sets(backend, sets)
            dt = (time.perf_counter() - t0) / reps
            print(json.dumps({
                "metric": "bls_pipeline_sweep",
                "pipeline": mode,
                "value": round(len(sets) / dt, 3),
                "unit": "sets/sec",
                "detail": {
                    "batch_sets": len(sets),
                    "e2e_sync_ms_per_batch": round(dt * 1e3, 2),
                    "path": backend.last_path,
                    **_pipeline_detail(),
                    **_parallel_detail(),
                    **_lint_detail(),
                },
            }), flush=True)


def _message_dup_cli_arg() -> list[int] | None:
    """Duplication factors of ``--message-dup`` (comma-separated), or
    None when absent. Bare ``--message-dup`` means the default sweep."""
    if "--message-dup" not in sys.argv:
        return None
    i = sys.argv.index("--message-dup")
    if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("-"):
        try:
            return [int(x) for x in sys.argv[i + 1].split(",")]
        except ValueError:
            pass
    return [1, 8, 64]


def message_dup_sweep(backend, S: int, reps: int,
                      factors: list[int]) -> None:
    """``--message-dup``: e2e rate on batches where many sets share one
    message — the gossip-attestation reality (a committee's unaggregated
    attestations all sign the SAME data). One ``bls_message_dup_sweep``
    JSON line per duplication factor. Since ISSUE 10 the backend dedups
    these batches before hash_to_curve, so each line also carries the
    htc_dedup/htc_map/htc_cofactor sub-stage split (detail.stages) and
    the dedup traffic counters that prove how many hashes the gather
    plan saved."""
    from lighthouse_tpu.crypto.bls.api import (
        AggregateSignature,
        SignatureSet,
    )
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2

    pool = _mk_key_pool(min(S, 512))
    for factor in factors:
        distinct = max(1, S // max(1, factor))
        h2g = {}  # one host hash per DISTINCT message (fixture only)
        sets = []
        for i in range(S):
            msg = (40_000 + i % distinct).to_bytes(32, "big")
            if msg not in h2g:
                h2g[msg] = hash_to_g2(msg)
            sk = (i % len(pool)) + 1
            sets.append(SignatureSet.single_pubkey(
                AggregateSignature(h2g[msg].mul(sk)),
                pool[sk - 1], msg,
            ))
        try:
            assert _forced_sets(backend, sets)  # compile + warm
            t0 = time.perf_counter()
            for _ in range(reps):
                assert _forced_sets(backend, sets)
            dt = (time.perf_counter() - t0) / reps
            print(json.dumps({
                "metric": "bls_message_dup_sweep",
                "value": round(S / dt, 3),
                "unit": "sets/sec",
                "detail": {
                    "dup_factor": factor,
                    "batch_sets": S,
                    "distinct_messages": distinct,
                    "e2e_sync_ms_per_batch": round(dt * 1e3, 2),
                    "path": backend.last_path,
                    "stages": _stage_report(),
                    "dedup": _dedup_detail(),
                    **_pipeline_detail(),
                    **_resilience_detail(),
                    **_parallel_detail(),
                    **_lint_detail(),
                },
            }), flush=True)
        except Exception as e:
            _emit_config_fallback("bls_message_dup_sweep", factor, e)


def _vs_target(e2e_rate: float, native_rate: float | None, detail: dict) -> float:
    """BASELINE target: >=10x blst on a 64-core CPU (BASELINE.md).

    Derivation (also in README): the measured in-repo native C++ is
    portable (no-asm) single-core; crediting it as blst-equivalent and
    linear core scaling, target = native * 64 cores * 10. With the
    round-2 measurement (~283 sets/s/core) that is ~181k sets/s. This
    UNDERSTATES the real bar by blst's asm advantage (~2-4x/core);
    vs_target reads "fraction of the credited target achieved"."""
    if not native_rate:
        return 0.0
    target = native_rate * 64 * 10
    detail["target_sets_per_sec"] = round(target, 1)
    return round(e2e_rate / target, 4)


def _mk_key_pool(n: int):
    """n deterministic keys: sk_i = i+1, pk by running G1 addition (one
    host point-add per key, not a scalar mul — fixture trick shared with
    slot_mode)."""
    from lighthouse_tpu.crypto.bls.api import PublicKey
    from lighthouse_tpu.crypto.bls.curve import g1_generator

    g1 = g1_generator()
    acc = g1
    pks = []
    for _ in range(n):
        pks.append(PublicKey(acc))
        acc = acc.add(g1)
    return pks


def configs_mode(backend, nb) -> None:
    """BASELINE configs #1-#3, one JSON line each (VERDICT r2 item 6):
      #1 BLS aggregate_verify (128 distinct-message pairs, one aggregate)
      #2 mainnet-block signature batch (~128 mixed-K attestation sets
         + proposal/randao/exit singles)
      #3 sync-committee fast_aggregate_verify (512 keys, one set)
    Each line's vs_baseline divides by the measured native-CPU rate for
    the SAME workload (single core, portable C++)."""
    import jax

    from lighthouse_tpu.common import resilience
    from lighthouse_tpu.crypto.bls.api import (
        AggregateSignature,
        SignatureSet,
    )
    from lighthouse_tpu.crypto.bls.constants import R as CURVE_ORDER
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
    from lighthouse_tpu.jax_backend import aggregate_verify_device

    def _dev_call(fn):
        # raw device calls (not routed through the backend's resilient
        # wrapper) still get bounded transient retry
        return resilience.call_with_retries(fn, stage="bench_device")

    dev = jax.devices()[0].platform
    pool = _mk_key_pool(512)

    def agg_sig_for(idxs, msg):
        sk_sum = sum(i + 1 for i in idxs) % CURVE_ORDER
        return AggregateSignature(hash_to_g2(msg).mul(sk_sum))

    # ---- config #1: aggregate_verify, 128 pairs ------------------------
    def _config1():
        n1 = 128
        msgs1 = [i.to_bytes(32, "big") for i in range(n1)]
        pks1 = pool[:n1]
        # aggregate signature = sum_i sk_i * H(m_i); sk_i = i+1
        acc = None
        for i, m in enumerate(msgs1):
            term = hash_to_g2(m).mul(i + 1)
            acc = term if acc is None else acc.add(term)
        agg1 = AggregateSignature(acc)

        assert _dev_call(lambda: aggregate_verify_device(pks1, msgs1, agg1))  # compile + warm
        t0 = time.perf_counter()
        assert _dev_call(lambda: aggregate_verify_device(pks1, msgs1, agg1))
        dt1 = time.perf_counter() - t0
        nat1 = None
        if nb is not None:
            assert nb.aggregate_verify(pks1, msgs1, agg1)
            t0 = time.perf_counter()
            assert nb.aggregate_verify(pks1, msgs1, agg1)
            nat1 = time.perf_counter() - t0
        print(json.dumps({
            "metric": "bls_aggregate_verify_pairs_per_sec",
            "value": round(n1 / dt1, 1),
            "unit": "pairs/sec",
            "vs_baseline": round((nat1 / dt1), 3) if nat1 else 0.0,
            "detail": {
                "config": 1, "pairs": n1, "device": dev,
                "device_ms": round(dt1 * 1e3, 1),
                "native_cpu_ms": round(nat1 * 1e3, 1) if nat1 else None,
                **_resilience_detail(),
                **_lint_detail(),
            },
        }))

    try:
        _config1()
    except Exception as e:
        _emit_config_fallback("bls_aggregate_verify_pairs_per_sec", 1, e)

    # ---- config #2: mainnet-block signature batch ----------------------
    # ~128 attestation sets with mixed committee sizes + proposal/randao/
    # exit singletons (reference: block_signature_verifier.rs:147 collects
    # exactly this shape).
    sets2 = []
    rng_sizes = [32 + (i * 13) % 97 for i in range(128)]  # 32..128 mixed K
    for j, k in enumerate(rng_sizes):
        lo = (j * 7) % (512 - k)
        idxs = list(range(lo, lo + k))
        msg = (10_000 + j).to_bytes(32, "big")
        sets2.append(SignatureSet.multiple_pubkeys(
            agg_sig_for(idxs, msg), [pool[i] for i in idxs], msg
        ))
    for j in range(4):  # proposal, randao, 2 exits
        msg = (20_000 + j).to_bytes(32, "big")
        sets2.append(SignatureSet.multiple_pubkeys(
            agg_sig_for([j], msg), [pool[j]], msg
        ))

    def _config2():
        assert _forced_sets(backend, sets2)  # compile + warm
        t0 = time.perf_counter()
        assert _forced_sets(backend, sets2)
        dt2 = time.perf_counter() - t0
        nat2 = None
        if nb is not None:
            assert _forced_sets(nb, sets2)
            t0 = time.perf_counter()
            assert _forced_sets(nb, sets2)
            nat2 = time.perf_counter() - t0
        print(json.dumps({
            "metric": "block_batch_sets_per_sec",
            "value": round(len(sets2) / dt2, 1),
            "unit": "sets/sec",
            "vs_baseline": round(nat2 / dt2, 3) if nat2 else 0.0,
            "detail": {
                "config": 2, "sets": len(sets2),
                "attester_sigs": sum(len(s.signing_keys) for s in sets2),
                "device": dev, "device_ms": round(dt2 * 1e3, 1),
                "native_cpu_ms": round(nat2 * 1e3, 1) if nat2 else None,
                **_resilience_detail(),
                **_lint_detail(),
            },
        }))

    try:
        _config2()
    except Exception as e:
        _emit_config_fallback("block_batch_sets_per_sec", 2, e)

    # ---- config #3: 512-key fast_aggregate_verify ----------------------
    def _config3():
        msg3 = (30_000).to_bytes(32, "big")
        idxs3 = list(range(512))
        set3 = SignatureSet.multiple_pubkeys(
            agg_sig_for(idxs3, msg3), [pool[i] for i in idxs3], msg3
        )
        assert _forced_sets(backend, [set3])  # warm (may route host)
        t0 = time.perf_counter()
        assert _forced_sets(backend, [set3])
        dt3 = time.perf_counter() - t0
        path3 = backend.last_path
        # raw device path for the record (production routes tiny batches to
        # the native host fallback — jax_backend._dispatch cost model)
        with knobs.scoped_env({"LHTPU_HOST_FALLBACK": "0"}):
            assert _forced_sets(backend, [set3])  # compile + warm
            t0 = time.perf_counter()
            assert _forced_sets(backend, [set3])
            dev3 = time.perf_counter() - t0
        nat3 = None
        if nb is not None:
            assert _forced_sets(nb, [set3])
            t0 = time.perf_counter()
            assert _forced_sets(nb, [set3])
            nat3 = time.perf_counter() - t0
        print(json.dumps({
            "metric": "fast_aggregate_verify_512_per_sec",
            "value": round(1 / dt3, 2),
            "unit": "verifications/sec",
            "vs_baseline": round(nat3 / dt3, 3) if nat3 else 0.0,
            "detail": {
                "config": 3, "keys": 512, "device": dev,
                "path": path3,
                "routed_ms": round(dt3 * 1e3, 1),
                "device_forced_ms": round(dev3 * 1e3, 1),
                "native_cpu_ms": round(nat3 * 1e3, 1) if nat3 else None,
                "retries": _resilience_detail()["retries"],
                **_lint_detail(),
            },
        }))

    try:
        _config3()
    except Exception as e:
        _emit_config_fallback("fast_aggregate_verify_512_per_sec", 3, e)


def main() -> None:
    global _HEADLINE_EMITTED, _INTENDED_RC

    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.bls.api import (
        SecretKey,
        SignatureSet,
        verify_signature_sets_python,
    )
    from lighthouse_tpu.jax_backend import (
        JaxBackend,
        _rand_bits_array,
        _verify_fused_jit,
        _verify_jit,
    )

    # The fused Pallas-kernel verifier (ops/tkernel*.py) is the
    # production TPU path. Off-TPU it would run in interpreter mode
    # (minutes per call), so the classic path stays the default there.
    # BENCH_FUSED=0/1 overrides.
    fused_choice = os.environ.get("BENCH_FUSED")
    if fused_choice is None:
        fused_choice = "1" if jax.default_backend() == "tpu" else "0"
    _verify = _verify_fused_jit if fused_choice == "1" else _verify_jit
    from lighthouse_tpu.ops.points import g1_to_dev, g2_to_dev

    quick = "--quick" in sys.argv
    # Default batch 4096 (VERDICT r2 item 1: push S with the persistent
    # compile cache; throughput still grows with batch).
    S = int(os.environ.get("BENCH_SETS", "4" if quick else "4096"))
    REPS = int(os.environ.get("BENCH_REPS", "1" if quick else "3"))
    BASELINE_SETS = int(os.environ.get("BENCH_BASELINE_SETS", "2" if quick else "48"))

    # --- build a valid S-set batch (distinct keys, distinct messages) -------
    sks = [SecretKey.from_int(i + 101) for i in range(S)]
    msgs = [i.to_bytes(32, "big") for i in range(S)]
    sets = [
        SignatureSet.single_pubkey(sk.sign(m), sk.public_key(), m)
        for sk, m in zip(sks, msgs)
    ]

    backend = JaxBackend()

    # --- device-only operand staging ---------------------------------------
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2

    px, py, pinf = g1_to_dev([s.signing_keys[0].point for s in sets])
    px, py, pinf = px.reshape(S, 1, 48), py.reshape(S, 1, 48), pinf.reshape(S, 1)
    sx, sy, sinf = g2_to_dev([s.signature.point for s in sets])
    mx, my, minf = g2_to_dev([hash_to_g2(m) for m in msgs])
    from lighthouse_tpu.jax_backend import _rand_scalars
    from lighthouse_tpu.ops import msm as _msm

    r_u64, r_bits = _rand_scalars(S)

    dev_args = (
        (jnp.asarray(px), jnp.asarray(py)), jnp.asarray(pinf),
        (jnp.asarray(sx), jnp.asarray(sy)), jnp.asarray(sinf),
        (jnp.asarray(mx), jnp.asarray(my)), jnp.asarray(minf),
        jnp.asarray(r_bits),
    )
    # Bucketed-MSM schedule: the fused production path (ops/msm.py).
    if fused_choice == "1" and knobs.knob("LHTPU_MSM_VERIFY"):
        sched = _msm.build_schedule(r_u64, _msm.max_rounds(S))
        if sched is not None:
            dev_args = dev_args + (jnp.asarray(sched[0]), jnp.asarray(sched[1]))

    # --- exactness gate on this device (incl. compile/warmup) --------------
    # The raw jitted calls ride the same bounded transient-retry policy
    # as the backend dispatch: one transient fault during warmup costs
    # a retry, not the whole number.
    from lighthouse_tpu.common import resilience

    def _forced(args) -> bool:
        return resilience.call_with_retries(
            lambda: bool(_verify(*args)), stage="bench_device"
        )

    ok = _forced(dev_args)
    bad_sy = np.array(sy)
    bad_sy[0] = sy[(1 if S > 1 else 0)]  # swap in a mismatched signature
    bad_args = list(dev_args)
    bad_args[2] = (jnp.asarray(sx), jnp.asarray(bad_sy))
    bad = _forced(bad_args)
    if not ok or (S > 1 and bad):
        print(json.dumps({"metric": "bls_sets_verified_per_sec", "value": 0.0,
                          "unit": "sets/sec", "vs_baseline": 0.0,
                          "error": "exactness gate failed",
                          "stages": _stage_report(),
                          **_resilience_detail(),
                          **_lint_detail(),
                          **_pipeline_detail()}), flush=True)
        _HEADLINE_EMITTED = True
        _INTENDED_RC = 1
        sys.exit(1)

    # --- timed: device-only -------------------------------------------------
    t0 = time.perf_counter()
    for _ in range(REPS):
        _forced(dev_args)
    dev_dt = (time.perf_counter() - t0) / REPS
    dev_rate = S / dev_dt

    # --- timed: end-to-end through the backend ------------------------------
    assert _forced_sets(backend, sets)  # compile/warm the htc path
    t0 = time.perf_counter()
    assert _forced_sets(backend, sets)
    e2e_sync_dt = time.perf_counter() - t0

    # Steady-state pipelined e2e (the headline): async dispatch lets the
    # host assemble/hash batch i+1 while batch i verifies on device —
    # what a chain under sustained gossip load sees (VERDICT r2 item 2;
    # the reference hides verification behind worker pools,
    # beacon_processor/mod.rs:1004-1070).
    pend = []
    t0 = time.perf_counter()
    for _ in range(REPS):
        pend.append(backend.verify_signature_sets_async(sets))
    assert all(resolve() for resolve in pend)
    e2e_dt = (time.perf_counter() - t0) / REPS
    e2e_rate = S / e2e_dt

    # Per-stage breakdown of the headline batch, captured NOW (before
    # configs_mode dispatches overwrite the last-dispatch snapshot):
    # pack / hash_to_curve / scalars / msm_schedule / dispatch /
    # device_sync, plus error and jit-cache attribution.
    headline_stages = _stage_report()
    headline_path = backend.last_path
    headline_pipeline = _pipeline_detail()
    headline_parallel = _parallel_detail()

    # --- optional --pipeline {on,off} sweep (paired JSON lines) -------------
    pipe_arg = _pipeline_cli_arg()
    if pipe_arg is not None:
        pipeline_sweep(backend, sets, REPS, pipe_arg)

    # --- optional --message-dup sweep (dedup-baseline JSON lines) -----------
    dup_arg = _message_dup_cli_arg()
    if dup_arg is not None:
        message_dup_sweep(backend, S, REPS, dup_arg)

    # --- measured native CPU baseline (C++; BASELINE.md mandate) ------------
    detail = {
        "batch_sets": S,
        "device": jax.devices()[0].platform,
        "device_only_sets_per_sec": round(dev_rate, 3),
        "device_only_ms_per_batch": round(dev_dt * 1e3, 2),
        "e2e_ms_per_batch": round(e2e_dt * 1e3, 2),
        "e2e_sync_ms_per_batch": round(e2e_sync_dt * 1e3, 2),
        "e2e_pipelined": True,
        "cpu_cores": os.cpu_count(),
    }
    native_rate = None
    try:
        from lighthouse_tpu.crypto.bls.native_backend import load_native_backend

        nb = load_native_backend()
        if nb is not None:
            sub = sets[:BASELINE_SETS]
            assert _forced_sets(nb, sub)  # warm
            t0 = time.perf_counter()
            assert _forced_sets(nb, sub)
            native_dt = time.perf_counter() - t0
            native_rate = len(sub) / native_dt
            detail["native_cpu_sets_per_sec"] = round(native_rate, 3)
    except Exception as e:  # toolchain missing: record, don't die
        detail["native_cpu_error"] = str(e)[:200]

    # --- pure-Python oracle rate (context only) ------------------------------
    t0 = time.perf_counter()
    assert verify_signature_sets_python(sets[: max(2, BASELINE_SETS // 8)])
    py_dt = time.perf_counter() - t0
    detail["cpu_python_sets_per_sec"] = round(
        max(2, BASELINE_SETS // 8) / py_dt, 3
    )

    # --- BASELINE configs #1-#3 (their own JSON lines; headline stays
    # last so the driver's single-line parse keeps working) --------------
    configs = os.environ.get("BENCH_CONFIGS")
    if configs is None:
        configs = "1" if (jax.default_backend() == "tpu" and not quick) else "0"
    if configs == "1":
        try:
            nb_handle = nb if native_rate else None
        except NameError:
            nb_handle = None
        configs_mode(backend, nb_handle)

    detail["stages"] = headline_stages
    # Retry/degradation record for the whole run + the path the headline
    # batch actually took: a bench that survived a transient must SAY so.
    detail.update(_resilience_detail())
    detail.update(headline_pipeline)
    detail.update(_triage_detail())
    detail.update(_lint_detail())
    detail.update(headline_parallel)
    detail["path"] = headline_path

    base = native_rate if native_rate else detail["cpu_python_sets_per_sec"]
    vs_target = _vs_target(e2e_rate, native_rate, detail)
    print(json.dumps({
        "metric": "bls_sets_verified_per_sec",
        "value": round(e2e_rate, 3),
        "unit": "sets/sec",
        "vs_baseline": round(e2e_rate / base, 3),
        "vs_target": vs_target,
        "detail": detail,
    }), flush=True)
    _HEADLINE_EMITTED = True


if __name__ == "__main__":
    # Every failure exits non-zero: an escaping exception, a failed
    # correctness gate, a BASELINE config that errored, or a hang the
    # watchdog ends. Each still prints its JSON error line first, with
    # the failing stage, so a dead run names what killed it.
    import signal

    def _watchdog(signum, frame):
        _emit_fallback("bench watchdog timeout")
        sys.stdout.flush()
        # A hang in teardown after the headline keeps the run's own rc.
        os._exit(_INTENDED_RC if _HEADLINE_EMITTED else 1)

    # High enough to clear a healthy cold-cache chip run (2-3 fused
    # compiles at 10-25 min each); its job is ending an infinite hang,
    # not bounding normal variance.
    _budget = int(os.environ.get("BENCH_WATCHDOG_SECS", "7200"))
    if _budget > 0 and hasattr(signal, "SIGALRM"):
        signal.signal(signal.SIGALRM, _watchdog)
        signal.alarm(_budget)

    from lighthouse_tpu.common.compile_cache import configure

    # Each mode initializes the backend in this process; a failure there
    # is a failed run like any other.
    configure()
    try:
        if (os.environ.get("BENCH_MODE") == "multichip"
                or "--devices" in sys.argv):
            devices_mode()
        elif (os.environ.get("BENCH_MODE") == "slot-load"
                or "--slot-load" in sys.argv):
            slot_load_mode()
        elif (os.environ.get("BENCH_MODE") == "stream"
                or "--stream" in sys.argv):
            stream_mode()
        elif (os.environ.get("BENCH_MODE") == "slot-chain"
                or "--slot-chain" in sys.argv):
            slot_chain_mode()
        elif os.environ.get("BENCH_MODE") == "slot" or "--slot" in sys.argv:
            slot_mode()
        else:
            main()
    except (SystemExit, KeyboardInterrupt):
        raise
    except BaseException as e:
        import traceback

        traceback.print_exc(file=sys.stderr)
        _emit_fallback(f"{type(e).__name__}: {e}")
        sys.exit(1)
    sys.exit(_INTENDED_RC)
