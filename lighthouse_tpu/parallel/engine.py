"""Multi-chip dispatch engine: device count as a dispatch dimension.

ISSUE 8 tentpole. This module sits between ``JaxBackend`` and the
sharded program builders in :mod:`lighthouse_tpu.parallel.sharding` and
owns everything about the *decision* to shard a verify dispatch:

* :func:`topology` — how many chips the mesh may span. Discovered from
  ``jax.devices()`` (so ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
  gives CPU CI a real N-way mesh), capped by ``LHTPU_DEVICES``, floored
  to a power of two so the padded set axis keeps its power-of-two
  per-chip slices and a single-chip fallback can reuse the same packed
  grids.
* :func:`plan` — the routing decision for one dispatch: sharded mesh
  width, padded set-axis extent, and the reason when it stays
  single-chip. Forcing ``LHTPU_SHARDED_VERIFY=1`` shards regardless of
  batch size (CI relies on tiny forced batches); the *default* only
  shards on TPU when every chip gets at least
  ``LHTPU_SHARD_MIN_SETS`` sets — below that the cross-chip fold
  overhead outruns the per-chip savings and CPU test runs keep their
  historical single-chip behavior.
* :func:`sharded_verify_fn` / :func:`sharded_grouped_fn` — the jitted
  sharded program cache over (devices, fused, indexed, msm, groups).
  Classic (pure-XLA) variants serve CPU meshes; fused (Pallas) variants
  serve TPU hardware. All share one flat argument convention so the
  backend's dispatch branch is uniform.
* the "sharded" circuit breaker — a permanent fault (chip loss, a
  lowering bug in the sharded composition) opens it and every later
  plan stays single-chip until the cooldown admits a half-open probe,
  which re-promotes the mesh on success. Verdicts never change across
  that transition: the single-chip programs accept the same padded
  grids.

Observability: ``bls_mesh_devices`` (mesh width of the most recent
dispatch), ``bls_sharded_dispatches_total{devices=...}``, and
:func:`parallel_report` which ``dispatch_stage_report()["parallel"]``
and every bench JSON line embed.
"""

from __future__ import annotations

from dataclasses import dataclass

from ..utils import next_pow2
from ..common import knobs, resilience
from ..common.metrics import REGISTRY

MESH_DEVICES = REGISTRY.gauge(
    "bls_mesh_devices",
    "Mesh device count used by the most recent verify dispatch "
    "(1 = single-chip)",
)
SHARDED_DISPATCHES = REGISTRY.counter(
    "bls_sharded_dispatches_total",
    "Sharded (multi-chip) verify dispatches, by mesh device count",
    ("devices",),
)

#: breaker name for the sharded dispatch composition (outside the
#: fused/classic/native rung LADDER: sharding is an *orthogonal*
#: dimension — degrading it keeps the same rung on one chip).
BREAKER = "sharded"


def _pow2_floor(n: int) -> int:
    return 1 if n < 1 else 1 << (n.bit_length() - 1)


def min_sets_per_chip() -> int:
    """Auto-sharding threshold: shard only when every chip gets at
    least this many (real) sets (``LHTPU_SHARD_MIN_SETS``)."""
    return max(1, int(knobs.knob("LHTPU_SHARD_MIN_SETS")))


@dataclass(frozen=True)
class DeviceTopology:
    """What the mesh may span: ``n_devices`` is the usable width
    (power of two, ≤ visible), ``visible`` the raw device count."""

    n_devices: int
    visible: int
    platform: str


def topology() -> DeviceTopology:
    """Discover the dispatchable topology (cheap — ``jax.devices()`` is
    cached by jax after backend init; env knobs are re-read every call
    so a bench sweep can walk ``LHTPU_DEVICES`` without reloads)."""
    import jax

    devs = jax.devices()
    visible = len(devs)
    n = visible
    cap = knobs.knob("LHTPU_DEVICES")
    if cap is not None:
        n = min(n, max(1, int(cap)))
    return DeviceTopology(
        n_devices=_pow2_floor(n),
        visible=visible,
        platform=devs[0].platform if devs else "none",
    )


@dataclass(frozen=True)
class ShardPlan:
    """One dispatch's routing decision. ``devices == 1`` means
    single-chip (``reason`` says why); otherwise ``S`` is the padded
    set-axis extent (a multiple of ``devices`` with power-of-two
    per-chip slices) and ``pad_sets`` the inert infinity lanes added."""

    devices: int
    S: int
    pad_sets: int
    reason: str


def _single(S: int, n_sets: int, reason: str) -> ShardPlan:
    return ShardPlan(1, S, S - n_sets, reason)


def plan(n_sets: int, S: int, *, n_groups: int | None = None,
         path_override: str | None = None) -> ShardPlan:
    """Routing decision for an ``n_sets``-set dispatch already padded
    to ``S`` (power of two) on one chip.

    Order matters: env kill-switch, rung overrides (degraded ladder
    dispatches must behave deterministically under their breaker),
    topology, group divisibility, then the sharded breaker LAST — so a
    half-open probe slot is only consumed by a dispatch that would
    actually shard.
    """
    shard = knobs.knob("LHTPU_SHARDED_VERIFY")
    if shard == "0":
        return _single(S, n_sets, "disabled")
    if path_override is not None:
        return _single(S, n_sets, "rung-override")
    top = topology()
    d = top.n_devices
    if d < 2:
        return _single(S, n_sets, "one-device")
    if n_groups is not None and n_groups % d != 0:
        return _single(S, n_sets, "groups-indivisible")
    if shard != "1":
        # Default routing: TPU meshes shard when every chip gets at
        # least min_sets_per_chip real sets; CPU stays single-chip
        # unless forced (the historical CI behavior).
        if top.platform != "tpu":
            return _single(S, n_sets, "cpu-default")
        if n_sets < d * min_sets_per_chip():
            return _single(S, n_sets, "below-min-sets")
    if not resilience.breaker(BREAKER).allow():
        return _single(S, n_sets, "breaker-open")
    S_sh = S if S % d == 0 else d * next_pow2(-(-S // d))
    return ShardPlan(d, S_sh, S_sh - n_sets, "forced" if shard == "1"
                     else "auto")


# ---------------------------------------------------------------- accounting

_LAST_PARALLEL: dict = {"devices": 1}


def record_dispatch(p: ShardPlan, *, path: str, n_sets: int,
                    fold_ms: float | None = None) -> None:
    """Account one completed dispatch: gauge + (sharded) counter + the
    snapshot ``dispatch_stage_report()["parallel"]`` serves."""
    MESH_DEVICES.set(p.devices)
    if p.devices > 1:
        SHARDED_DISPATCHES.inc(devices=str(p.devices))
    global _LAST_PARALLEL
    _LAST_PARALLEL = {
        "devices": p.devices,
        "mesh": [p.devices, 1],
        "sets": n_sets,
        "padded_sets": p.S,
        "sets_per_chip": p.S // p.devices,
        "pad_waste": round(1.0 - n_sets / p.S, 4) if p.S else 0.0,
        "path": path,
        "reason": p.reason,
        "fold_ms": fold_ms,
    }


def record_success() -> None:
    """A sharded dispatch returned — close/heal the sharded breaker
    (a half-open probe success is the re-promotion path)."""
    resilience.breaker(BREAKER).record_success()


def release_probe() -> None:
    """The planner admitted a sharded dispatch but the caller could not
    run it (retained packs that don't divide the mesh): return the
    possibly-consumed half-open probe slot so the breaker can admit the
    next real candidate."""
    resilience.breaker(BREAKER).release()


def record_failure(exc: BaseException) -> tuple[str, str]:
    """A sharded dispatch raised through its retries: classify and
    trip the sharded breaker (permanent → straight open, so chip loss
    degrades every subsequent dispatch to single-chip until cooldown)."""
    category, kind = resilience.classify(exc)
    resilience.breaker(BREAKER).record_failure(
        permanent=category == resilience.PERMANENT
    )
    return category, kind


def parallel_report() -> dict:
    """Most recent dispatch's parallel routing (stage report / bench)."""
    return dict(_LAST_PARALLEL)


def reset() -> None:
    """Test/drill isolation: forget the last-dispatch snapshot (program
    caches survive — compiles are the expensive part). Breaker state
    lives in resilience and is cleared by ``resilience.reset()``."""
    global _LAST_PARALLEL
    _LAST_PARALLEL = {"devices": 1}
    MESH_DEVICES.set(0)


# ------------------------------------------------------------ pipeline hook

def chunk_floor() -> int:
    """Minimum pipeline chunk size so every microbatch chunk still
    spans the mesh at the min-sets-per-chip threshold; 1 when sharding
    would not engage (the pipeline policy then stays untouched)."""
    shard = knobs.knob("LHTPU_SHARDED_VERIFY")
    if shard == "0":
        return 1
    top = topology()
    if top.n_devices < 2:
        return 1
    if shard != "1" and top.platform != "tpu":
        return 1
    return top.n_devices * min_sets_per_chip()


def dispatch_quantum(batch_target: int) -> int:
    """Smallest batch slice the continuous scheduler
    (``loadgen/scheduler.py``) may dispatch — and therefore its block
    preemption granularity. A quarter of the batch target keeps blocks
    responsive mid-batch; the mesh chunk floor is the lower bound so a
    preempted remainder still spans the mesh at min-sets-per-chip when
    sharding is engaged."""
    return max(1, chunk_floor(), int(batch_target) // 4)


# ----------------------------------------------------- sharded program cache

# (kind, devices, fused, indexed, msm/groups) -> jitted program. All
# programs share the flat argument convention of
# sharding.build_sharded_verifier; grouped programs return bool[G],
# plain ones bool[1].
_PROGRAMS: dict = {}


def sharded_verify_fn(n_dev: int, *, fused: bool, indexed: bool = False,
                      with_msm: bool = False):
    """Jitted sharded scalar-verdict program over an ``n_dev``-way
    ("dp",) mesh. ``fused`` picks the Pallas pipeline (TPU); classic
    XLA otherwise (CPU-viable, no MSM leg)."""
    import jax

    key = ("verify", n_dev, fused, indexed, with_msm)
    if key not in _PROGRAMS:
        from .sharding import (
            build_sharded_fused_indexed_verifier,
            build_sharded_fused_verifier,
            build_sharded_indexed_verifier,
            build_sharded_verifier,
            make_mesh,
        )

        mesh = make_mesh(n_dev, mp=1)
        if fused:
            build = (build_sharded_fused_indexed_verifier if indexed
                     else build_sharded_fused_verifier)
            fn = build(mesh, with_msm=with_msm)
        else:
            assert not with_msm, "classic sharded program has no MSM leg"
            build = (build_sharded_indexed_verifier if indexed
                     else build_sharded_verifier)
            fn = build(mesh)
        _PROGRAMS[key] = jax.jit(fn)
    return _PROGRAMS[key]


def sharded_grouped_fn(n_dev: int, n_groups: int, *, fused: bool,
                       indexed: bool = False):
    """Jitted sharded grouped-verdict program (triage's mesh route)."""
    import jax

    key = ("grouped", n_dev, n_groups, fused, indexed)
    if key not in _PROGRAMS:
        from .sharding import (
            build_sharded_fused_grouped_indexed_verifier,
            build_sharded_fused_grouped_verifier,
            build_sharded_grouped_indexed_verifier,
            build_sharded_grouped_verifier,
            make_mesh,
        )

        mesh = make_mesh(n_dev, mp=1)
        if fused:
            build = (build_sharded_fused_grouped_indexed_verifier if indexed
                     else build_sharded_fused_grouped_verifier)
        else:
            build = (build_sharded_grouped_indexed_verifier if indexed
                     else build_sharded_grouped_verifier)
        _PROGRAMS[key] = jax.jit(build(mesh, n_groups))
    return _PROGRAMS[key]


# ------------------------------------------------------------ fold profiling

_FOLD_PROBES: dict = {}


def _fold_probe(n_dev: int):
    """Tiny shard_map program with the sharded verifiers' cross-chip
    collective skeleton (all_gather of per-chip partials + fold +
    psum'd failure count) on trivial payloads — isolates the fold cost
    from the per-chip compute it normally hides under."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if n_dev not in _FOLD_PROBES:
        from .sharding import make_mesh

        mesh = make_mesh(n_dev, mp=1)

        @partial(shard_map, mesh=mesh, in_specs=(P("dp"),), out_specs=P(),
                 check_vma=False)
        def body(x):
            part = jnp.sum(x, axis=0, keepdims=True)       # per-chip partial
            parts = jax.lax.all_gather(part, "dp")          # [d, 1, 8]
            folded = jnp.sum(parts, axis=0)                 # the fold
            bad = jax.lax.psum(jnp.sum(jnp.zeros((), x.dtype)), "dp")
            return folded + bad

        _FOLD_PROBES[n_dev] = jax.jit(body)
    return _FOLD_PROBES[n_dev]


def measure_fold_ms(n_dev: int, reps: int = 5) -> float:
    """Wall-clock milliseconds of one cross-chip fold round (best of
    ``reps`` forced runs after a warmup). Bench/profile-only: normal
    dispatches leave ``fold_ms`` None rather than paying extra syncs."""
    import time

    import jax.numpy as jnp

    if n_dev < 2:
        return 0.0
    fn = _fold_probe(n_dev)
    x = jnp.ones((n_dev, 8), jnp.float32)
    fn(x).block_until_ready()  # warmup (compile)
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return round(best * 1e3, 4)
