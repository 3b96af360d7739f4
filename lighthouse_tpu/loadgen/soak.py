"""Multi-epoch soak runner: the serving-lifetime endurance harness.

PR 6's ``ServingLoop`` judges one stream; this module replays N epochs
of ``TrafficConfig`` streams (``time_scale`` compression keeps 8
virtual epochs inside a CI budget) and scores *lifetime* properties the
per-dispatch resilience layer cannot see:

* **chaos schedules** — ``LHTPU_CHAOS_SCHEDULE`` =
  ``"<epoch>:<stage>:<kind>:<count>;..."`` layered on the existing
  ``LHTPU_FAULT_INJECT`` injector: at each scheduled epoch the spec is
  installed for that epoch only, giving deterministic warmup → chaos →
  recovery phases. ``kind`` accepts the injector's literal kinds plus
  two aliases: ``transient`` (→ ``aborted``, an ABORTED runtime status)
  and ``permanent`` (→ ``mosaic``, the r04 incident).
* **leak sentinels** — each epoch samples RSS
  (``common/monitoring.read_rss_bytes``), the jit-cache entry estimate,
  input-cache hit rates and breaker transitions, and runs the
  ``common/health`` governor; the final verdict fails on RSS growth
  past ``LHTPU_SOAK_LEAK_MB`` between the first and last epoch.
* **re-promotion** — after the last chaos epoch the run must return to
  the ladder's PRIMARY rung (``fused`` on TPU, ``classic`` off-TPU:
  breakers half-open → close, ``path`` prefixed by the rung again)
  within ``recovery_epochs``; ``degraded_time_fraction`` (degraded
  epochs / total epochs) is the scored metric.
* **watchdog** — each epoch runs under a wall-clock budget of
  ``max(LHTPU_SOAK_WATCHDOG_MIN_S, LHTPU_SOAK_WATCHDOG_K × scaled
  epoch length)``. On expiry with a stale dispatch heartbeat
  (``common/pipeline.last_progress_age``) the runner calls
  ``ServingLoop.watchdog_force_degrade`` — pending work is accounted,
  the epoch ends degraded, the soak continues instead of wedging.
* **bit-identical verdicts** — per-epoch ``verdict_digest`` lines; with
  ``replay=True`` the whole schedule re-runs chaos-free on the same
  seeds and the digests must match bit-for-bit (the PR 2/5 guarantee,
  now held across a lifetime).

One JSON line per epoch (``metric=soak_epoch``) plus a final
``metric=soak_verdict`` line, same shape as bench lines.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
import time
from dataclasses import dataclass, field, replace

from ..common import health, knobs, monitoring, pipeline, resilience
from .serve import ServeConfig, ServingLoop, VirtualClock, WallClock, \
    verdict_digest
from .traffic import TrafficConfig, TrafficGenerator

#: chaos-schedule kind aliases onto the injector's literal kinds
KIND_ALIASES = {"transient": "aborted", "permanent": "mosaic"}

#: per-epoch seed stride (any odd prime; keeps epoch streams distinct
#: yet fully determined by the base seed)
_SEED_STRIDE = 7919


@dataclass
class ChaosEvent:
    epoch: int
    stage: str
    kind: str
    count: int

    def inject_spec(self) -> str:
        return f"{self.stage}:{self.kind}:{self.count}"


def parse_chaos_schedule(spec: str | None) -> list[ChaosEvent]:
    """``"<epoch>:<stage>:<kind>:<count>;..."`` → chaos events.
    Malformed items are warned and skipped (same forgiveness as the
    injector's own spec parsing); kind aliases resolve here."""
    out: list[ChaosEvent] = []
    for item in filter(None, (p.strip() for p in (spec or "").split(";"))):
        try:
            epoch_s, stage, kind, count_s = item.split(":")
            out.append(ChaosEvent(
                epoch=int(epoch_s), stage=stage,
                kind=KIND_ALIASES.get(kind, kind), count=int(count_s),
            ))
        except ValueError:
            print(
                f"soak: ignoring malformed LHTPU_CHAOS_SCHEDULE item "
                f"{item!r} (want epoch:stage:kind:count)",
                file=sys.stderr,
            )
    return out


def chaos_spec_for_epoch(schedule: list[ChaosEvent], epoch: int) -> str:
    """The LHTPU_FAULT_INJECT spec for one epoch ('' = no chaos)."""
    return ",".join(
        ev.inject_spec() for ev in schedule if ev.epoch == epoch
    )


# ------------------------------------------------------------ chain weather

#: weather-plan axis names → (TrafficConfig field, value parser)
_WEATHER_AXES = {
    "reorg_storm": ("reorg_storm", float),
    "non_finality": ("non_finality_epochs", int),
    "slashing_flood": ("slashing_flood_rate", float),
    "sync_boundary": ("sync_period_boundary", int),
}


@dataclass
class WeatherEvent:
    epoch: int | None      # None = every epoch (the '*' wildcard)
    field: str             # TrafficConfig field name
    value: float | int


def parse_weather_schedule(spec: str | None) -> list[WeatherEvent]:
    """``"<epoch>:<axis>:<value>;..."`` → weather events; ``*`` as the
    epoch applies the axis to every epoch. Axes are the chain-weather
    names (``reorg_storm`` / ``non_finality`` / ``slashing_flood`` /
    ``sync_boundary``). Weather is TRAFFIC, not faults — it rides the
    TrafficConfig (so chaos-free replays keep it and digests stay
    comparable), never LHTPU_FAULT_INJECT. Malformed items are warned
    and skipped, same forgiveness as the chaos grammar."""
    out: list[WeatherEvent] = []
    for item in filter(None, (p.strip() for p in (spec or "").split(";"))):
        try:
            epoch_s, axis, value_s = item.split(":")
            fld, cast = _WEATHER_AXES[axis]
            out.append(WeatherEvent(
                epoch=None if epoch_s == "*" else int(epoch_s),
                field=fld, value=cast(value_s),
            ))
        except (ValueError, KeyError):
            print(
                f"soak: ignoring malformed LHTPU_WEATHER_SCHEDULE item "
                f"{item!r} (want epoch:axis:value, axis one of "
                f"{sorted(_WEATHER_AXES)})",
                file=sys.stderr,
            )
    return out


def weather_for_epoch(schedule: list[WeatherEvent],
                      epoch: int) -> dict[str, float | int]:
    """TrafficConfig overrides for one epoch (later items win)."""
    out: dict[str, float | int] = {}
    for ev in schedule:
        if ev.epoch is None or ev.epoch == epoch:
            out[ev.field] = ev.value
    return out


def _primary_rung() -> str:
    """The ladder's top rung on THIS host (fused only when the fused
    path is actually the configured primary — off-TPU it is classic)."""
    try:
        from .. import jax_backend as jb

        return "fused" if jb._fused_choice() == "1" else "classic"
    except Exception:  # lhtpu: ignore[LH502] -- jax_backend can't load off-accelerator; ladder top defaults to fused
        return resilience.LADDER[0]


def _last_dispatch_path() -> str | None:
    try:
        from .. import jax_backend as jb

        return jb.dispatch_stage_report().get("path")
    except Exception:  # lhtpu: ignore[LH502] -- dispatch path is diagnostic garnish; None when jax_backend can't load
        return None


def _degraded_total() -> float:
    return sum(v for _, v in resilience.DEGRADED_TOTAL.items())


def _retries_total() -> float:
    return sum(v for _, v in resilience.RETRIES_TOTAL.items())


@dataclass
class SoakConfig:
    epochs: int = 8
    traffic: TrafficConfig = field(default_factory=TrafficConfig)
    serve: ServeConfig | None = None
    seed: int = 1234
    backend: str | None = None
    wall_clock: bool = False          # default: deterministic virtual clock
    recovery_epochs: int = 2          # re-promotion budget after chaos
    leak_mb: float | None = None      # None = LHTPU_SOAK_LEAK_MB (512)
    watchdog_k: float | None = None   # None = LHTPU_SOAK_WATCHDOG_K (20)
    watchdog_min_s: float | None = None  # None = ..._MIN_S (300)
    replay: bool = True               # chaos-free digest-parity replay
    # chain-weather plan ("epoch:axis:value;..."); None = the
    # LHTPU_WEATHER_SCHEDULE knob. Weather survives the replay pass —
    # it is part of the traffic, not of the chaos.
    weather: str | None = None

    def __post_init__(self):
        if self.leak_mb is None:
            self.leak_mb = knobs.knob("LHTPU_SOAK_LEAK_MB")
        if self.watchdog_k is None:
            self.watchdog_k = knobs.knob("LHTPU_SOAK_WATCHDOG_K")
        if self.watchdog_min_s is None:
            # Must clear a cold XLA compile (minutes on CPU); real
            # wedges are caught anyway — just later. Tests shrink it.
            self.watchdog_min_s = knobs.knob("LHTPU_SOAK_WATCHDOG_MIN_S")


class SoakRunner:
    """Drives ``cfg.epochs`` ServingLoop runs under a chaos schedule.

    ``emit`` receives each JSON line (None = silent — the replay pass
    runs this way). ``run()`` returns the final-verdict detail dict."""

    def __init__(self, cfg: SoakConfig,
                 chaos: list[ChaosEvent] | None = None, emit=print):
        self.cfg = cfg
        self.chaos = list(chaos) if chaos is not None else (
            parse_chaos_schedule(knobs.knob("LHTPU_CHAOS_SCHEDULE"))
        )
        self.weather = parse_weather_schedule(
            knobs.knob("LHTPU_WEATHER_SCHEDULE") if cfg.weather is None
            else cfg.weather
        )
        self.emit = emit

    # ------------------------------------------------------------- phases
    def _phase(self, epoch: int) -> str:
        if not self.chaos:
            return "steady"
        first = min(ev.epoch for ev in self.chaos)
        last = max(ev.epoch for ev in self.chaos)
        if epoch < first:
            return "warmup"
        if chaos_spec_for_epoch(self.chaos, epoch):
            return "chaos"
        if epoch > last:
            return "recovery"
        return "steady"

    # -------------------------------------------------------------- epoch
    def _epoch_budget_s(self) -> float:
        t = self.cfg.traffic
        scaled = t.slots * t.seconds_per_slot * t.time_scale
        return max(self.cfg.watchdog_min_s, self.cfg.watchdog_k * scaled)

    def _run_epoch(self, epoch: int, clock) -> tuple[dict, dict]:
        """One epoch: fresh ServingLoop on the shared clock, the
        epoch's chaos installed in LHTPU_FAULT_INJECT, watchdog armed.
        Returns (loop report, {digest, wedged, error})."""
        cfg = self.cfg
        traffic_cfg = replace(
            cfg.traffic, seed=cfg.seed + _SEED_STRIDE * epoch
        )
        over = weather_for_epoch(self.weather, epoch)
        if over:
            traffic_cfg = replace(traffic_cfg, **over)
        events = TrafficGenerator(traffic_cfg).generate()
        loop = ServingLoop(
            cfg.serve or ServeConfig.from_env(),
            clock=clock, backend=cfg.backend,
        )
        spec = chaos_spec_for_epoch(self.chaos, epoch)
        if spec:
            os.environ["LHTPU_FAULT_INJECT"] = spec
        else:
            os.environ.pop("LHTPU_FAULT_INJECT", None)
        # Identical specs in consecutive chaos epochs must each get
        # their full fault count (the injector otherwise keeps the
        # exhausted countdown while the spec string is unchanged).
        resilience.rearm_faults()

        box: dict = {}

        def work():
            try:
                box["report"] = loop.run(events)
            except BaseException as exc:  # surfaced below, not swallowed
                box["error"] = exc

        worker = threading.Thread(
            target=work, daemon=True, name=f"lhtpu-soak-epoch-{epoch}"
        )
        budget = self._epoch_budget_s()
        worker.start()
        worker.join(budget)
        # Slow ≠ wedged: while the dispatch heartbeat (batch completions
        # / pipeline chunks) stays fresh, grant bounded extensions — the
        # watchdog exists to catch a STUCK slot, not a slow one.
        extensions = 0
        while (worker.is_alive() and extensions < 10
               and pipeline.last_progress_age() < budget):
            extensions += 1
            worker.join(budget)
        wedged = worker.is_alive()
        if wedged:
            # The worker is abandoned wedged inside a handler; evacuate
            # and account everything it will never serve.
            loop.watchdog_force_degrade(reason=f"epoch-{epoch}-wedged")
            report = loop.finish()
        elif "error" in box:
            raise box["error"]
        else:
            report = box["report"]
        return report, {
            "digest": verdict_digest(loop.verdicts),
            "wedged": wedged,
            "events": len(events),
        }

    # ---------------------------------------------------------------- run
    def run(self) -> dict:
        cfg = self.cfg
        clock = WallClock() if cfg.wall_clock else VirtualClock()
        governor = health.governor()  # feeds note_slo from finish()
        saved_inject = knobs.raw("LHTPU_FAULT_INJECT")
        epoch_rows: list[dict] = []
        crashed: str | None = None
        t_run0 = time.perf_counter()
        try:
            for epoch in range(cfg.epochs):
                deg0 = _degraded_total()
                ret0 = _retries_total()
                trans0 = resilience.breaker_transitions_total()
                t0 = time.perf_counter()
                try:
                    report, extra = self._run_epoch(epoch, clock)
                except BaseException as exc:
                    crashed = f"epoch {epoch}: {type(exc).__name__}: {exc}"
                    break
                wall_s = time.perf_counter() - t0
                health_level = governor.check()
                rss = monitoring.sample_rss()
                breakers = resilience.breaker_states()
                degraded_delta = _degraded_total() - deg0
                degraded = bool(
                    degraded_delta > 0
                    or extra["wedged"]
                    or any(s != "closed" for s in breakers.values())
                    or health_level > health.HEALTHY
                )
                row = {
                    "epoch": epoch,
                    "phase": self._phase(epoch),
                    "chaos": chaos_spec_for_epoch(self.chaos, epoch),
                    "events": extra["events"],
                    "served": report["events_served"],
                    "sets_per_sec": round(
                        report["events_served"] / wall_s, 2
                    ) if wall_s > 0 else 0.0,
                    "wall_s": round(wall_s, 3),
                    "slo": {
                        "p50_ms": report["slo"]["p50_ms"],
                        "p99_ms": report["slo"]["p99_ms"],
                        "within_budget": report["slo"]["within_budget"],
                        "per_class": report["slo"].get("per_class", {}),
                    },
                    "rss_bytes": rss,
                    "jit_cache_entries": monitoring.jit_cache_entry_count(),
                    "breaker_transitions": int(
                        resilience.breaker_transitions_total() - trans0
                    ),
                    "breakers": breakers,
                    "degraded": degraded,
                    "degraded_dispatches": int(degraded_delta),
                    "retries": int(_retries_total() - ret0),
                    "path": _last_dispatch_path(),
                    "health": governor.report()["state"],
                    "shed": sum(report["shed_by_type"].values()),
                    "dropped": sum(report["dropped_by_type"].values()),
                    "force_degraded": report["force_degraded"],
                    "wedged": extra["wedged"],
                    "accounting_balanced":
                        report["accounting"]["balanced"],
                    "mismatches": report["verdicts"]["mismatches"],
                    "verdict_digest": extra["digest"],
                }
                epoch_rows.append(row)
                self._emit({
                    "metric": "soak_epoch", "value": row["sets_per_sec"],
                    "unit": "sets/sec", "vs_baseline": 0.0, "detail": row,
                })
        finally:
            if saved_inject is None:
                os.environ.pop("LHTPU_FAULT_INJECT", None)
            else:
                os.environ["LHTPU_FAULT_INJECT"] = saved_inject
        result = self._verdict(epoch_rows, crashed,
                               time.perf_counter() - t_run0)
        if cfg.replay and not crashed and self.chaos:
            result["replay"] = self._replay(epoch_rows)
            if not result["replay"]["digests_match"]:
                result["verdict"] = "fail"
                result["reasons"].append("replay digest mismatch")
        self._emit({
            "metric": "soak_verdict",
            "value": 1.0 if result["verdict"] == "pass" else 0.0,
            "unit": "pass", "vs_baseline": 0.0, "detail": result,
        })
        return result

    def _replay(self, epoch_rows: list[dict]) -> dict:
        """Chaos-free re-run of the same seeds; verdict digests must be
        bit-identical (faults may only change HOW a verdict is reached,
        never the verdict). Breaker/injector state is reset first so
        the replay starts from a clean ladder."""
        resilience.reset()
        clean = SoakRunner(
            replace(self.cfg, replay=False), chaos=[], emit=None
        )
        res = clean.run()
        theirs = [r["verdict_digest"] for r in res["epoch_digests_rows"]]
        ours = [r["verdict_digest"] for r in epoch_rows]
        return {
            "ran": True,
            "digests_match": ours == theirs,
            "epoch_digests": theirs,
        }

    def _verdict(self, rows: list[dict], crashed: str | None,
                 wall_s: float) -> dict:
        cfg = self.cfg
        reasons: list[str] = []
        if crashed:
            reasons.append(f"crashed: {crashed}")
        degraded_epochs = sum(1 for r in rows if r["degraded"])
        fraction = degraded_epochs / max(1, len(rows))
        mismatches = sum(r["mismatches"] for r in rows)
        if mismatches:
            reasons.append(f"{mismatches} verdict mismatches")
        if rows and fraction >= 1.0:
            reasons.append("degraded for the entire run")
        if not all(r["accounting_balanced"] for r in rows):
            reasons.append("serving-loop accounting imbalance")
        # Leak check from the SECOND epoch on: epoch 0 pays the cold
        # compiles (XLA arenas dwarf any real leak), the steady-state
        # slope is what the sentinel is for.
        base_row = rows[1] if len(rows) > 1 else (rows[0] if rows else None)
        rss_delta = (
            rows[-1]["rss_bytes"] - base_row["rss_bytes"] if base_row else 0
        )
        rss_delta_mb = rss_delta / 2**20
        if rss_delta_mb > cfg.leak_mb:
            reasons.append(
                f"rss grew {rss_delta_mb:.1f} MB > {cfg.leak_mb} MB budget"
            )
        primary = _primary_rung()
        repromote = self._repromotion(rows, primary)
        if repromote["required"] and not repromote["ok"]:
            reasons.append(
                f"no re-promotion to {primary} within "
                f"{cfg.recovery_epochs} epochs of chaos end"
            )
        combined = hashlib.sha256(
            "|".join(r["verdict_digest"] for r in rows).encode()
        ).hexdigest()
        return {
            "verdict": "fail" if reasons else "pass",
            "reasons": reasons,
            "epochs": len(rows),
            "wall_s": round(wall_s, 3),
            "degraded_time_fraction": round(fraction, 4),
            "degraded_epochs": degraded_epochs,
            "mismatches_total": mismatches,
            "rss_delta_bytes": int(rss_delta),
            "rss_delta_mb": round(rss_delta_mb, 1),
            "leak_budget_mb": cfg.leak_mb,
            "primary_rung": primary,
            "repromotion": repromote,
            "watchdog_fired": sum(1 for r in rows if r["wedged"]),
            "digest": combined,
            "chaos_schedule": ";".join(
                f"{e.epoch}:{e.stage}:{e.kind}:{e.count}" for e in self.chaos
            ),
            "weather_schedule": ";".join(
                f"{'*' if e.epoch is None else e.epoch}:{e.field}:{e.value}"
                for e in self.weather
            ),
            "seed": cfg.seed,
            "replay": {"ran": False, "digests_match": None},
            # full per-epoch digest rows for the replay comparison
            "epoch_digests_rows": [
                {"epoch": r["epoch"], "verdict_digest": r["verdict_digest"]}
                for r in rows
            ],
        }

    def _repromotion(self, rows: list[dict], primary: str) -> dict:
        """Did the run return to the primary rung after chaos ended?
        Required only when the schedule leaves room: at least one
        post-chaos epoch exists. 'Re-promoted' = an epoch after the
        last chaos epoch that is not degraded, has every breaker
        closed, and whose last dispatch path is the primary rung's."""
        if not self.chaos or not rows:
            return {"required": False, "ok": True, "epochs_after_chaos": None}
        last_chaos = max(ev.epoch for ev in self.chaos)
        post = [r for r in rows if r["epoch"] > last_chaos]
        if not post:
            return {"required": False, "ok": True, "epochs_after_chaos": None}
        for r in post:
            path = r["path"] or ""
            if (not r["degraded"]
                    and all(s == "closed" for s in r["breakers"].values())
                    and path.startswith(primary)):
                return {
                    "required": True, "ok": True,
                    "epochs_after_chaos": r["epoch"] - last_chaos,
                }
        return {"required": True, "ok": False, "epochs_after_chaos": None}

    def _emit(self, line: dict) -> None:
        if self.emit is not None:
            self.emit(json.dumps(line))
            if self.emit is print:
                sys.stdout.flush()
