"""Declarative registry of every ``LHTPU_*`` environment knob.

Before this module, ~30 raw ``os.environ`` reads were scattered across
the backend, the ops kernels, the resilience/health/pipeline commons and
the loadgen stack, each re-declaring its own default and parse rule —
and three shipped bug-fixes were instances of exactly that invariant
drift. Now every knob is declared ONCE here (name, kind, default, doc
line, consumer module) and read through :func:`knob`; the lint suite
(``tools/lint``, error family LH2xx) rejects any raw ``LHTPU_*`` read
outside this file, any default re-declared elsewhere, any unregistered
name passed to :func:`knob`, any registered knob with no consumer, and
a README knob table that drifts from :func:`knob_table_markdown`.

Parse rules (uniform across all knobs — previously each call site had
its own; ``bool`` knobs in particular were split between ``!= "0"`` and
``== "1"`` semantics):

* unset or empty string → the registered default;
* ``bool``   — ``0`` / ``false`` / ``no`` / ``off`` (case-insensitive)
  is False, anything else True;
* ``int`` / ``float`` — parsed; a malformed value falls back to the
  default instead of raising (a typo in an env var must not crash a
  serving process);
* ``optint`` — like ``int`` but the default is None ("auto"/"unset");
* ``str`` / ``optstr`` — the raw string; ``optstr`` defaults to None
  (tri-state knobs where unset means "decide from the backend").

Range clamps (e.g. "at least 2 sets per pipeline chunk") stay at the
consumer: they are consumer policy, not knob identity.

This module imports nothing from the rest of the package so every
layer — ops kernels, commons, loadgen, bench — can depend on it.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from dataclasses import dataclass

__all__ = [
    "Knob", "REGISTRY", "knob", "maybe_int", "raw", "scoped_env",
    "knob_table_markdown",
]

_FALSE_WORDS = ("0", "false", "no", "off")


@dataclass(frozen=True)
class Knob:
    """One registered env knob. ``kind`` is the parse rule name,
    ``default`` the value :func:`knob` returns when unset/malformed,
    ``doc`` a one-line description (the README table row), ``consumer``
    the module that owns the policy built on it."""

    name: str
    kind: str       # bool | int | float | str | optint | optstr
    default: object
    doc: str
    consumer: str


# The single source of truth. Keep the table grouped by consumer; the
# README knob table is generated from it (tools/lint --knob-table) and
# lint LH203 fails when the checked-in copy drifts.
_ALL: tuple[Knob, ...] = (
    # ---------------------------------------------- jax_backend.py
    Knob("LHTPU_FUSED_VERIFY", "optstr", None,
         "Force fused Pallas verify (1) or classic XLA (0); unset = fused on TPU only",
         "lighthouse_tpu/jax_backend.py"),
    Knob("LHTPU_HOST_AGG", "optstr", None,
         "Force mixed-K host aggregation on (1) / off (0); unset = TPU heuristic S*K >= 2*keys",
         "lighthouse_tpu/jax_backend.py"),
    Knob("LHTPU_DEVICE_HTC", "optstr", None,
         "Force device hash-to-curve on (1) / off (0); unset = on when the backend is TPU",
         "lighthouse_tpu/jax_backend.py"),
    Knob("LHTPU_VERDICT_GROUPS", "int", 32,
         "Verdict groups per triage dispatch (rounded up to a power of two; 0 disables triage)",
         "lighthouse_tpu/jax_backend.py"),
    Knob("LHTPU_HOST_FALLBACK", "bool", True,
         "Serve tiny batches from the native CPU backend instead of paying a device dispatch",
         "lighthouse_tpu/jax_backend.py"),
    Knob("LHTPU_HOST_FALLBACK_MS", "float", 250.0,
         "Estimated-native-ms ceiling under which the host fallback takes the batch",
         "lighthouse_tpu/jax_backend.py"),
    Knob("LHTPU_MSM_VERIFY", "bool", True,
         "Use the MSM bucket schedule in the fused verify program (0 = per-lane scalar-mul scan)",
         "lighthouse_tpu/jax_backend.py"),
    # ------------------------------------------- common/resilience.py
    Knob("LHTPU_RESILIENCE", "bool", True,
         "0 disables retry + degradation ladder (raw raise-through)",
         "lighthouse_tpu/common/resilience.py"),
    Knob("LHTPU_RETRY_MAX", "int", 3,
         "Max transient retries per dispatch stage",
         "lighthouse_tpu/common/resilience.py"),
    Knob("LHTPU_RETRY_BASE_MS", "float", 50.0,
         "First retry backoff in ms (doubles per attempt)",
         "lighthouse_tpu/common/resilience.py"),
    Knob("LHTPU_RETRY_CAP_MS", "float", 2000.0,
         "Retry backoff ceiling in ms",
         "lighthouse_tpu/common/resilience.py"),
    Knob("LHTPU_RETRY_JITTER", "float", 0.25,
         "Jitter fraction added on top of each backoff",
         "lighthouse_tpu/common/resilience.py"),
    Knob("LHTPU_RETRY_SEED", "optstr", None,
         "Seed for the retry-jitter RNG (deterministic backoff schedules in tests/drills)",
         "lighthouse_tpu/common/resilience.py"),
    Knob("LHTPU_BREAKER_THRESHOLD", "int", 3,
         "Consecutive transient failures that open a dispatch-rung breaker",
         "lighthouse_tpu/common/resilience.py"),
    Knob("LHTPU_BREAKER_COOLDOWN_S", "float", 30.0,
         "Breaker open -> half-open probe delay in seconds",
         "lighthouse_tpu/common/resilience.py"),
    Knob("LHTPU_SYNC_DEADLINE_S", "float", 900.0,
         "device_sync force deadline in seconds (<= 0 runs inline, no deadline thread)",
         "lighthouse_tpu/common/resilience.py"),
    Knob("LHTPU_FAULT_INJECT", "str", "",
         "Deterministic fault injection spec: stage:kind:count[,...]",
         "lighthouse_tpu/common/resilience.py"),
    Knob("LHTPU_FAULT_HANG_S", "float", 3600.0,
         "Sleep length of the injected 'hang' fault kind in seconds",
         "lighthouse_tpu/common/resilience.py"),
    # --------------------------------------------- common/pipeline.py
    Knob("LHTPU_PIPELINE", "bool", True,
         "0 restores single-shot dispatch (no microbatch pipeline)",
         "lighthouse_tpu/common/pipeline.py"),
    Knob("LHTPU_PIPELINE_MIN_SETS", "int", 512,
         "Batches below this many sets stay single-shot",
         "lighthouse_tpu/common/pipeline.py"),
    Knob("LHTPU_PIPELINE_CHUNK", "optint", None,
         "Fixed power-of-two pipeline chunk size; unset = max(256, next_pow2(n)//4)",
         "lighthouse_tpu/common/pipeline.py"),
    # ---------------------------------------------- common/tracing.py
    Knob("LHTPU_TRACE", "bool", True,
         "0 disables span tracing (read once at import; flip at runtime via set_enabled)",
         "lighthouse_tpu/common/tracing.py"),
    # ----------------------------------------------- common/health.py
    Knob("LHTPU_RSS_WINDOW_S", "float", 60.0,
         "RSS-growth sentinel sliding window in seconds",
         "lighthouse_tpu/common/health.py"),
    Knob("LHTPU_RSS_GROWTH_MB", "float", 512.0,
         "RSS growth inside the window that reports degraded",
         "lighthouse_tpu/common/health.py"),
    Knob("LHTPU_RSS_CRITICAL_MB", "float", 16384.0,
         "Absolute RSS ceiling that reports critical",
         "lighthouse_tpu/common/health.py"),
    Knob("LHTPU_JIT_CACHE_MAX", "int", 512,
         "Jit-cache entry watermark; crossing fires one counted cache clear",
         "lighthouse_tpu/common/health.py"),
    Knob("LHTPU_CACHE_HIT_FLOOR", "float", 0.05,
         "Windowed input-cache hit rate below which the sentinel reports degraded",
         "lighthouse_tpu/common/health.py"),
    Knob("LHTPU_CACHE_MIN_SAMPLES", "int", 4096,
         "Input-cache lookups required in a window before the hit-rate floor applies",
         "lighthouse_tpu/common/health.py"),
    Knob("LHTPU_FLAP_WINDOW_S", "float", 60.0,
         "Breaker-flap sentinel sliding window in seconds",
         "lighthouse_tpu/common/health.py"),
    Knob("LHTPU_FLAP_MAX", "int", 6,
         "Breaker transitions inside the window that count as flapping",
         "lighthouse_tpu/common/health.py"),
    Knob("LHTPU_SLO_BREACH_STREAK", "int", 3,
         "Consecutive p99-over-budget reports that report degraded (2x = critical)",
         "lighthouse_tpu/common/health.py"),
    Knob("LHTPU_QUEUE_HIGH_FRAC", "float", 0.85,
         "Scheduler queue depth fraction of LHTPU_SCHED_QUEUE_CAP that counts as pressured",
         "lighthouse_tpu/common/health.py"),
    Knob("LHTPU_QUEUE_STREAK", "int", 3,
         "Consecutive pressured checks that report degraded (2x = critical)",
         "lighthouse_tpu/common/health.py"),
    # -------------------------------------------- parallel/engine.py
    Knob("LHTPU_DEVICES", "optint", None,
         "Cap on mesh device count; unset = every visible device (pow2-floored)",
         "lighthouse_tpu/parallel/engine.py"),
    Knob("LHTPU_SHARDED_VERIFY", "optstr", None,
         "Force sharded dispatch on (1) / off (0); unset = auto (TPU + enough sets per chip)",
         "lighthouse_tpu/parallel/engine.py"),
    Knob("LHTPU_SHARD_MIN_SETS", "int", 4,
         "Auto-sharding threshold: min real sets per chip before the mesh engages",
         "lighthouse_tpu/parallel/engine.py"),
    # ------------------------------------------------------ blsrt.py
    Knob("LHTPU_INPUT_CACHE", "bool", True,
         "0 disables the cross-call pubkey-row and hash-to-curve input caches",
         "lighthouse_tpu/blsrt.py"),
    Knob("LHTPU_PUBKEY_CACHE", "int", 65536,
         "Pubkey-row arena capacity (distinct pubkeys resident across calls)",
         "lighthouse_tpu/blsrt.py"),
    Knob("LHTPU_HTC_CACHE", "int", 4096,
         "Hash-to-curve output cache capacity (distinct messages)",
         "lighthouse_tpu/blsrt.py"),
    Knob("LHTPU_HTC_DEDUP", "bool", True,
         "0 disables protocol-aware message dedup before hash-to-curve (identity plan)",
         "lighthouse_tpu/blsrt.py"),
    Knob("LHTPU_HTC_BATCH_CACHE", "int", 8,
         "Device-resident distinct-message-batch output cache entries (0 disables)",
         "lighthouse_tpu/blsrt.py"),
    # -------------------------------------------------- ops kernels
    Knob("LHTPU_KS_CARRY", "bool", False,
         "Enable the Kogge-Stone carry-select normalization (TPU-lowering gated; see tkernel)",
         "lighthouse_tpu/ops/tkernel.py"),
    Knob("LHTPU_KS_CHECK", "bool", False,
         "Digit-range assertion inside carry normalization (debug; host-eval only)",
         "lighthouse_tpu/ops/tkernel.py"),
    Knob("LHTPU_MXU_FOLD", "optstr", None,
         "Force the MXU Montgomery fold on (1) / off (0); unset = on when the backend is TPU",
         "lighthouse_tpu/ops/tkernel.py"),
    Knob("LHTPU_LAZY_REDUCE", "bool", False,
         "Lazy-reduction tower arithmetic: normalize once per line function (hardware-gated; see tkernel)",
         "lighthouse_tpu/ops/tkernel.py"),
    Knob("LHTPU_MXU_CARRY", "bool", False,
         "Carry propagation as banded-Toeplitz MXU matmuls instead of serial chains (hardware-gated)",
         "lighthouse_tpu/ops/tkernel.py"),
    Knob("LHTPU_HTC_MXU_LADDER", "optstr", None,
         "Force Fp2 muln stacking in the ladder kernels on (1) / off (0); unset = follow the MXU fold",
         "lighthouse_tpu/ops/tkernel.py"),
    Knob("LHTPU_HTC_RESIDENT", "optstr", None,
         "Force the single resident hash-to-G2 map kernel on (1) / off (0); unset = on",
         "lighthouse_tpu/ops/tkernel_htc.py"),
    Knob("LHTPU_VMEM_LIMIT_MB", "int", 64,
         "Pallas compiler VMEM limit per kernel in MiB",
         "lighthouse_tpu/ops/tkernel.py"),
    Knob("LHTPU_PALLAS_MONT_MUL", "bool", False,
         "1 routes mont_mul through the Pallas kernel instead of the XLA path",
         "lighthouse_tpu/ops/limb.py"),
    # ------------------------------------------------ loadgen/serve.py
    Knob("LHTPU_BATCH_TARGET", "int", 256,
         "Full-batch dispatch size for the serving loop",
         "lighthouse_tpu/loadgen/serve.py"),
    Knob("LHTPU_BATCH_DEADLINE_MS", "float", 250.0,
         "Partial-batch latency budget: a held batch fires at this deadline",
         "lighthouse_tpu/loadgen/serve.py"),
    Knob("LHTPU_ADMIT_HIGH", "int", 8192,
         "Sheddable queue depth at which the admission gate closes",
         "lighthouse_tpu/loadgen/serve.py"),
    Knob("LHTPU_ADMIT_LOW", "optint", None,
         "Queue depth at which the gate reopens; unset = admit_high // 2",
         "lighthouse_tpu/loadgen/serve.py"),
    Knob("LHTPU_SLO_BUDGET_MS", "float", 4000.0,
         "p99 enqueue->verdict budget for the within_budget SLO verdict",
         "lighthouse_tpu/loadgen/serve.py"),
    # -------------------------------------------------- loadgen/slo.py
    Knob("LHTPU_SLO_SAMPLE_CAP", "int", 8192,
         "Per-work-type latency sample window (exact quantiles within it); totals stay exact",
         "lighthouse_tpu/loadgen/slo.py"),
    # -------------------------------------------- loadgen/scheduler.py
    Knob("LHTPU_SCHED_BLOCK_DEADLINE_MS", "float", 0.0,
         "Block-class coalescing deadline; 0 = dispatch immediately, preempting any window",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_AGG_DEADLINE_MS", "float", 100.0,
         "Aggregate-class coalescing deadline before a partial batch fires",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_ATT_DEADLINE_MS", "float", 250.0,
         "Attestation-class coalescing deadline before a partial batch fires",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_SYNC_DEADLINE_MS", "float", 500.0,
         "Sync-class coalescing deadline before a partial batch fires",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_QUEUE_CAP", "int", 16384,
         "Per-class queue capacity in the continuous scheduler (shed watermarks scale off it)",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_TENANT_QUOTA", "float", 0.5,
         "Max fraction of a class's shed watermark one tenant may occupy before its offers shed",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_DISPATCH_MS", "float", 0.0,
         "Modeled per-chunk device occupancy on the virtual clock (enables deterministic preemption windows)",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_CACHE", "bool", True,
         "Cross-slot committee-composition pubkey cache on (1) / off (0)",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_CACHE_CAP", "int", 4096,
         "Composition-cache entry capacity (LRU beyond it)",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_SLASHING_DEADLINE_MS", "float", 50.0,
         "Slashing-class coalescing deadline before a partial batch fires",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_STARVATION_MS", "float", 1000.0,
         "Oldest-event wait past which a non-block class outranks priority order (0 disables)",
         "lighthouse_tpu/loadgen/scheduler.py"),
    Knob("LHTPU_SCHED_SLASHER", "bool", True,
         "Feed slashing-event votes through the surround/double-vote slasher sink",
         "lighthouse_tpu/loadgen/scheduler.py"),
    # ---------------------------------------------------- slasher/arrays.py
    Knob("LHTPU_SLASHER_DEVICE", "optstr", None,
         "Force the device slasher planes on (1) / off (0); unset = on when jax imports",
         "lighthouse_tpu/slasher/arrays.py"),
    Knob("LHTPU_SLASHER_CHUNK", "int", 256,
         "Validators per device slasher plane chunk",
         "lighthouse_tpu/slasher/arrays.py"),
    Knob("LHTPU_SLASHER_HISTORY", "int", 4096,
         "Epoch ring length of the device slasher min/max-target planes",
         "lighthouse_tpu/slasher/arrays.py"),
    # ------------------------------------------------- loadgen/soak.py
    Knob("LHTPU_CHAOS_SCHEDULE", "str", "",
         "Soak chaos plan: epoch:stage:kind:count[;...] layered on the fault injector",
         "lighthouse_tpu/loadgen/soak.py"),
    Knob("LHTPU_SOAK_LEAK_MB", "float", 512.0,
         "RSS growth budget between the second and last soak epoch before the verdict fails",
         "lighthouse_tpu/loadgen/soak.py"),
    Knob("LHTPU_SOAK_WATCHDOG_K", "float", 20.0,
         "Epoch watchdog budget multiplier over the scaled epoch length",
         "lighthouse_tpu/loadgen/soak.py"),
    Knob("LHTPU_SOAK_WATCHDOG_MIN_S", "float", 300.0,
         "Epoch watchdog budget floor in seconds (must clear a cold XLA compile)",
         "lighthouse_tpu/loadgen/soak.py"),
    Knob("LHTPU_WEATHER_SCHEDULE", "str", "",
         "Chain-weather plan: epoch:axis:value[;...] over the traffic weather axes",
         "lighthouse_tpu/loadgen/soak.py"),
)

REGISTRY: dict[str, Knob] = {k.name: k for k in _ALL}
assert len(REGISTRY) == len(_ALL), "duplicate knob registration"


def knob(name: str):
    """The current typed value of a registered knob (env is re-read on
    every call — the PR 1 trace-time convention; knobs read once at
    import say so in their doc line). Unregistered names raise KeyError
    loudly: registering is the point."""
    k = REGISTRY[name]
    raw_v = os.environ.get(name)
    if raw_v is None or raw_v == "":
        return k.default
    if k.kind == "bool":
        return raw_v.strip().lower() not in _FALSE_WORDS
    if k.kind in ("int", "optint"):
        try:
            return int(raw_v)
        except ValueError:
            return k.default
    if k.kind == "float":
        try:
            return float(raw_v)
        except ValueError:
            return k.default
    return raw_v  # str / optstr


def maybe_int(name: str, default: int | None = None) -> int:
    """Integer env read for DYNAMIC names (e.g. a cache whose env var
    is injected by tests): registered names parse through :func:`knob`
    (their registry default wins; the caller's is ignored), unregistered
    ones parse raw with the caller's default."""
    if name in REGISTRY:
        return int(knob(name))
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        if default is None:
            raise KeyError(
                f"{name} is unregistered, unset, and has no caller default"
            ) from None
        return default


def raw(name: str) -> str | None:
    """The raw env string of a knob (None when unset) — for
    save/restore blocks and spec-change detection, where the unparsed
    identity matters, not the typed value."""
    return os.environ.get(name)


@contextmanager
def scoped_env(overrides: dict[str, str | None]):
    """Set (value) or unset (None) env knobs for a ``with`` block and
    restore the previous state on exit — the save/set/restore pattern
    bench sweeps and fault drills used to hand-roll."""
    saved = {k: os.environ.get(k) for k in overrides}
    try:
        for k, v in overrides.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def knob_table_markdown() -> str:
    """The README knob table, generated from the registry. Checked in
    under the ``<!-- knob-table:begin -->`` markers; lint LH203 fails
    when the checked-in copy no longer matches this output."""
    rows = [
        "| Knob | Type | Default | Consumer | Description |",
        "|---|---|---|---|---|",
    ]
    for k in _ALL:
        default = "*(auto)*" if k.default is None else f"`{k.default}`"
        consumer = k.consumer.replace("lighthouse_tpu/", "")
        rows.append(
            f"| `{k.name}` | {k.kind} | {default} | `{consumer}` | {k.doc} |"
        )
    return "\n".join(rows)
