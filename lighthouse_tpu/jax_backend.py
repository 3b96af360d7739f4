"""JAX/TPU BLS backend — ``verify_signature_sets`` executed on device.

This is the component the whole framework exists for: the reference client
funnels every signature it ever checks through one free function
``verify_signature_sets`` (reference: crypto/bls/src/lib.rs:95-151, impls/
blst.rs:36-119 — per set draw a nonzero 64-bit scalar, subgroup-check the
signature, aggregate the set's pubkeys, then one multi-pairing
random-linear-combination check). Here that entire batch — pubkey
aggregation, RLC scalar muls, signature subgroup checks, all Miller loops,
the Fp12 product tree and the final exponentiation — is ONE jitted XLA
program over static-shape batches:

    prod_i e([r_i] agg_pk_i, H(m_i)) * e(-g1, sum_i [r_i] sig_i) == 1

Design notes (TPU-first):
  * Static shapes: the batch is padded to (n_sets -> S, max pubkeys -> K)
    power-of-two buckets, so XLA compiles one program per bucket and reuses
    it; padding lanes carry points at infinity, which every kernel treats as
    the neutral element, so they cannot affect the verdict.
  * Structural edge cases that need no field math (empty set list, a set
    with zero pubkeys, an infinity aggregate signature — reference:
    impls/blst.rs:79-88) are rejected host-side before anything is shipped
    to the device, exactly mirroring the reference's early-outs.
  * Message hashing (RFC 9380 hash-to-G2) is host-side for now: it is
    SHA-256-bound, per-distinct-message (a slot's attestations share few
    distinct messages), and the resulting affine points are tiny. The
    kernels take H(m) as an input, which also keeps them deterministic.
  * Signature subgroup checks ride the same device program as the pairing
    ([r]Q == inf scan), batched across the whole set list.

The random scalars come from the host CSPRNG (``secrets``), like the
reference's rand_core draw — they are blinding factors and must not be
device-PRNG'd into the traced graph.
"""

from __future__ import annotations

import secrets
import time
from contextlib import contextmanager

import numpy as np
import jax
import jax.numpy as jnp

from .common import knobs, monitoring, pipeline, resilience, tracing
from .common.logging import StructuredLogger
from .common.metrics import REGISTRY
from .crypto.bls.backends import register_backend
from .crypto.bls.constants import RAND_BITS
from .crypto.bls.hash_to_curve import hash_to_g2
from .ops import limb, tower
from .ops.pairing import final_exponentiation, miller_loop
from .ops.points import (
    FP2_OPS,
    FP_OPS,
    G1_GEN_DEV,
    g1_to_dev,
    g2_to_dev,
    pt_from_affine,
    pt_subgroup_check,
    pt_scalar_mul_bits,
    pt_to_affine,
    pt_tree_sum,
    pt_tree_sum_axis,
)
from .ops.pairing import fp12_tree_prod, fp12_tree_prod_groups
from .ops.tower import fp12_is_one, fp12_mul
from .parallel import engine as parallel_engine


from .utils import next_pow2 as _next_pow2


# --- dispatch observability (the per-crate metrics.rs of this module) ----
# Every stage of _dispatch is a tracing span mirrored into these
# families; bench.py and tools read the same data through
# dispatch_stage_report(). Names follow the reference's
# beacon_node metric style (lighthouse_metrics).

_POW2_BUCKETS = tuple(float(1 << i) for i in range(14))  # 1..8192

DISPATCH_STAGE_SECONDS = REGISTRY.histogram(
    "bls_dispatch_stage_seconds",
    "Host wall time of each BLS dispatch stage",
    ("stage",),
)
DISPATCH_ERRORS = REGISTRY.counter(
    "bls_dispatch_errors_total",
    "Failures inside BLS dispatch, attributed to the stage that raised",
    ("stage",),
)
DISPATCH_BATCHES = REGISTRY.counter(
    "bls_dispatch_batches_total",
    "Verification batches dispatched, by device program path",
    ("path",),
)
DISPATCH_BATCH_SETS = REGISTRY.histogram(
    "bls_dispatch_batch_sets",
    "Signature sets per dispatched batch (pre-padding)",
    buckets=_POW2_BUCKETS,
)
DISPATCH_BATCH_KEYS = REGISTRY.histogram(
    "bls_dispatch_batch_keys",
    "Total signing keys per dispatched batch (pre-padding)",
    buckets=_POW2_BUCKETS,
)
JIT_CACHE_EVENTS = REGISTRY.counter(
    "bls_jit_cache_events_total",
    "Verify-program jit dispatches by compile-cache outcome",
    ("fn", "event"),
)
NATIVE_LOAD_FAILURES = REGISTRY.counter(
    "native_backend_load_failures_total",
    "Native C++ BLS backend load attempts that found no usable library",
)
TRIAGE_DISPATCHES = REGISTRY.counter(
    "bls_triage_dispatches_total",
    "Grouped-verdict device dispatches issued by poison triage",
)
TRIAGE_GROUPS = REGISTRY.counter(
    "bls_triage_groups",
    "Verdict groups inspected by poison triage, by outcome",
    ("outcome",),
)

_LOG = StructuredLogger("jax_backend")

# Host-fallback cost model: estimated native-backend wall time for a
# batch, fit from the BASELINE bench configs on this pod's CPU (config
# #3, one 512-key sync-committee set: 13.6 ms native; config #2 block
# batches: ~3.3 ms per set plus ~0.05 ms per signing key). Batches whose
# estimate beats LHTPU_HOST_FALLBACK_MS (default 250) skip the device
# dispatch entirely. The device side of this trade has not been measured
# on a locally attached chip yet (PERF.md, open questions).
HOST_FALLBACK_MS_PER_SET = 3.3
HOST_FALLBACK_MS_PER_KEY = 0.05

# Most recent dispatch's stage timings / failure / path, for bench
# attribution (bench.py reads these through dispatch_stage_report even
# when the dispatch died mid-flight).
_LAST_STAGES: dict[str, float] = {}
_LAST_ERROR_STAGE: str | None = None
_LAST_PATH: str | None = None
# Most recent verify_signature_sets_triaged accounting (rounds /
# dispatches / group outcomes / fallback route), mirrored into
# dispatch_stage_report()["triage"] and bench detail.triage.
_LAST_TRIAGE: dict = {"enabled": False}


def _verdict_groups() -> int:
    """Target group count G for grouped-verdict dispatches
    (LHTPU_VERDICT_GROUPS; 0 disables device triage). Default 32: per
    the stage histograms the marginal cost of G verdicts — G-1 extra
    check-pair Miller lanes and a [G]-batched final exponentiation —
    stays under ~5% of the Miller work there. Rounded up to a power of
    two so G always divides the padded set count."""
    v = int(knobs.knob("LHTPU_VERDICT_GROUPS"))
    if v <= 0:
        return 0
    return _next_pow2(max(2, v))


@contextmanager
def _stage(name: str, stages: dict):
    """One dispatch stage: tracing span + histogram mirror + loud error
    attribution. With tracing off only the (exception-path) error
    counter remains — no clock reads on the measured path."""
    global _LAST_ERROR_STAGE
    if not tracing.enabled():
        try:
            yield
        except Exception:
            _LAST_ERROR_STAGE = name
            DISPATCH_ERRORS.inc(stage=name)
            raise
        return
    t0 = time.perf_counter()
    try:
        with tracing.span(
            "bls_dispatch/" + name,
            metric=DISPATCH_STAGE_SECONDS,
            labels={"stage": name},
        ):
            yield
    except Exception:
        _LAST_ERROR_STAGE = name
        DISPATCH_ERRORS.inc(stage=name)
        raise
    stages[name] = time.perf_counter() - t0


def _retry_stage(name: str, stages: dict, fn):
    """Run ONE dispatch stage with fault injection + bounded transient
    retry: the retry re-enters at this stage, not the whole pipeline
    (an ABORTED status inside hash_to_curve re-runs only the
    hash). Each failed attempt still lands in
    bls_dispatch_errors_total{stage=...} (attribution is per-attempt);
    each retry lands in bls_dispatch_retries_total{stage,kind}.
    Permanent faults and exhausted budgets re-raise to the ladder."""

    def attempt():
        with _stage(name, stages):
            resilience.maybe_inject(name)
            return fn()

    return resilience.call_with_retries(attempt, stage=name)


def _jit_cache_probe(fn, label: str):
    """Sample ``fn``'s jit cache size; returns a closure that, called
    after the dispatch, records hit vs miss (a growth in cache size is
    a fresh trace/compile). Counts nothing when the runtime doesn't
    expose _cache_size (non-jit callables, older jax)."""
    try:
        before = fn._cache_size()
    except Exception:  # lhtpu: ignore[LH502] -- _cache_size is a private jax API; absent means cache accounting is off, not an error
        return lambda: None

    def done():
        try:
            after = fn._cache_size()
        except Exception:  # lhtpu: ignore[LH502] -- same probe after dispatch; losing one sample is fine
            return
        miss = after > before
        JIT_CACHE_EVENTS.inc(fn=label, event="miss" if miss else "hit")
        if miss:
            monitoring.note_jit_compile(after - before)

    return done


def dispatch_stage_report() -> dict:
    """Stage attribution of the most recent _dispatch: per-stage wall
    times, cumulative per-stage error counts, and the stage the last
    failure raised in (None = no failure yet). The bench embeds this in
    its JSON so a dead run still names the guilty stage."""
    return {
        "stages_ms": {
            k: round(v * 1e3, 3) for k, v in _LAST_STAGES.items()
        },
        "failed_stage": _LAST_ERROR_STAGE,
        "errors_total": {
            lbl["stage"]: v for lbl, v in DISPATCH_ERRORS.items()
        },
        "jit_cache": {
            f"{lbl['fn']}:{lbl['event']}": v
            for lbl, v in JIT_CACHE_EVENTS.items()
        },
        "retries": {
            f"{lbl['stage']}:{lbl['kind']}": v
            for lbl, v in resilience.RETRIES_TOTAL.items()
        },
        "degraded": {
            lbl["path"]: v for lbl, v in resilience.DEGRADED_TOTAL.items()
        },
        "breaker": resilience.breaker_states(),
        "path": _LAST_PATH,
        "parallel": parallel_engine.parallel_report(),
        "pipeline": pipeline.last_run_report(),
        "cache": _input_cache_report(),
        "triage": dict(_LAST_TRIAGE),
        "slo": _slo_last_report(),
        "health": _health_report(),
    }


def _health_report():
    """Last governor report (lazy + guarded like the SLO hook: the
    health module must stay optional to this module's import)."""
    try:
        from .common import health

        return health.health_report()
    except Exception:  # lhtpu: ignore[LH502] -- health subsystem is import-optional to this module; None = no report
        return None


def _slo_last_report():
    """Most recent serving-loop SLO summary (loadgen). Lazy + guarded:
    the loadgen package must stay optional to this module's import."""
    try:
        from .loadgen import slo

        return slo.last_slo_report()
    except Exception:  # lhtpu: ignore[LH502] -- loadgen package is import-optional to this module; None = no report
        return None


def _input_cache_report() -> dict:
    from . import blsrt

    return blsrt.input_cache_report()


_NATIVE_LOAD_WARNED: set[str] = set()


def _try_load_native():
    """The native C++ BLS backend, or None when the library can't load
    (no compiler / build failure) — callers fall back to device paths.

    A degraded run must be able to say WHY native was unavailable: the
    cause is logged once per distinct message at WARNING and counted in
    native_backend_load_failures_total (previously every exception was
    swallowed silently)."""
    cause = None
    try:
        from .crypto.bls.native_backend import load_native_backend

        backend = load_native_backend()
    except Exception as exc:
        backend = None
        cause = f"{type(exc).__name__}: {exc}"
    if backend is not None:
        return backend
    if cause is None:
        from .native import bls_load_error

        cause = bls_load_error() or "unknown (toolchain unavailable?)"
    if cause not in _NATIVE_LOAD_WARNED:
        _NATIVE_LOAD_WARNED.add(cause)
        NATIVE_LOAD_FAILURES.inc()
        _LOG.warn(
            "Native BLS backend unavailable",
            cause=cause.replace("\n", " ")[:300],
        )
    return None


def _fused_choice() -> str:
    """"1" -> fused Pallas kernels, "0" -> classic XLA. Fused is the TPU
    production path (3-5x the classic program); off-TPU Mosaic isn't
    available and interpret-mode compile cost dominates, so classic
    stays the default there. LHTPU_FUSED_VERIFY=0/1 overrides. One
    policy shared by batch verify (_dispatch) and AggregateVerify."""
    choice = knobs.knob("LHTPU_FUSED_VERIFY")
    if choice is None:
        choice = "1" if jax.default_backend() == "tpu" else "0"
    return choice


def _host_agg_wanted(K: int, S: int, total_keys: int) -> bool:
    """Mixed-K host-aggregation heuristic: collapse the [S, K] pubkey
    grid to K=1 via per-set CPU aggregation when the padded grid is
    mostly padding waste (S*K >= 2 * real keys). TPU-only by default —
    on CPU the device aggregation tree must keep its test coverage.
    LHTPU_HOST_AGG=0/1 overrides. Factored out so the production
    trigger (not just the override) is unit-testable (ADVICE r4)."""
    if K <= 1:
        return False
    host_agg = knobs.knob("LHTPU_HOST_AGG")
    if host_agg is not None:
        return host_agg == "1"
    return jax.default_backend() == "tpu" and S * K >= 2 * total_keys


def _pad_pair_lanes(g1_x, g1_y, g1_inf, g2_x, g2_y, g2_inf, pad: int):
    """Pad multi-pairing operands with ``pad`` inert lanes (replicate the
    last row's coordinates, mark the lane infinity -> contributes Fp12
    one). Shared by the classic batch and aggregate cores."""
    if pad:
        g1_x = jnp.concatenate([g1_x, jnp.broadcast_to(g1_x[-1:], (pad, 48))])
        g1_y = jnp.concatenate([g1_y, jnp.broadcast_to(g1_y[-1:], (pad, 48))])
        g1_inf = jnp.concatenate([g1_inf, jnp.ones((pad,), bool)])
        g2_x = jnp.concatenate(
            [g2_x, jnp.broadcast_to(g2_x[-1:], (pad, 2, 48))]
        )
        g2_y = jnp.concatenate(
            [g2_y, jnp.broadcast_to(g2_y[-1:], (pad, 2, 48))]
        )
        g2_inf = jnp.concatenate([g2_inf, jnp.ones((pad,), bool)])
    return g1_x, g1_y, g1_inf, g2_x, g2_y, g2_inf


def _verify_core(pk, pk_inf, sig, sig_inf, msg, msg_inf, r_bits):
    """The jitted device program. All shapes static.

    pk:      (x[S,K,48], y[S,K,48]) affine G1, Montgomery limbs
    pk_inf:  bool[S,K]   (padding lanes = infinity)
    sig:     (x[S,2,48], y[S,2,48]) affine G2
    sig_inf: bool[S]     (padding sets = infinity; real infinity rejected on host)
    msg:     (x[S,2,48], y[S,2,48]) affine G2 hash points
    msg_inf: bool[S]
    r_bits:  int32[S,64] RLC scalars, MSB first (padding sets: anything)

    Returns a scalar bool.
    """
    S, K = pk_inf.shape

    # Per-set pubkey aggregation: K-leaf binary tree of Jacobian adds.
    pk_j = pt_from_affine(FP_OPS, pk[0], pk[1], pk_inf)
    agg = pt_tree_sum_axis(FP_OPS, pk_j, axis=1, axis_size=K)  # [S]
    agg_aff = pt_to_affine(FP_OPS, agg)

    # RLC: [r_i] agg_pk_i  and  [r_i] sig_i  (64-bit double-and-add scans).
    rpk = pt_scalar_mul_bits(FP_OPS, agg_aff[:2], agg_aff[2], r_bits)
    rsig = pt_scalar_mul_bits(FP2_OPS, sig, sig_inf, r_bits)

    # Signature subgroup membership ([order]sig == inf; infinity passes and
    # is either a padding lane or already rejected host-side).
    sig_j = pt_from_affine(FP2_OPS, sig[0], sig[1], sig_inf)
    sub_ok = jnp.all(pt_subgroup_check(FP2_OPS, sig_j))

    # sum_i [r_i] sig_i, then one affine normalization for the Miller loop.
    sig_acc = pt_tree_sum(FP2_OPS, rsig, S)
    sig_acc_aff = pt_to_affine(FP2_OPS, tuple(c[None] for c in sig_acc))

    # Multi-pairing: S set pairs + 1 check pair, padded to a power of two.
    rpk_aff = pt_to_affine(FP_OPS, rpk)
    neg_g1 = (G1_GEN_DEV[0][None], limb.neg(G1_GEN_DEV[1])[None])
    g1_x = jnp.concatenate([rpk_aff[0], neg_g1[0]])
    g1_y = jnp.concatenate([rpk_aff[1], neg_g1[1]])
    g1_inf = jnp.concatenate([rpk_aff[2], jnp.zeros((1,), bool)])
    g2_x = jnp.concatenate([msg[0], sig_acc_aff[0]])
    g2_y = jnp.concatenate([msg[1], sig_acc_aff[1]])
    g2_inf = jnp.concatenate([msg_inf, sig_acc_aff[2]])

    M = _next_pow2(S + 1)
    g1_x, g1_y, g1_inf, g2_x, g2_y, g2_inf = _pad_pair_lanes(
        g1_x, g1_y, g1_inf, g2_x, g2_y, g2_inf, M - (S + 1)
    )

    f = miller_loop((g1_x, g1_y), g1_inf, (g2_x, g2_y), g2_inf)
    f = fp12_tree_prod(f, M)
    f = final_exponentiation(f)
    return fp12_is_one(f) & sub_ok


_verify_jit = jax.jit(_verify_core)


def _verify_core_grouped(pk, pk_inf, sig, sig_inf, msg, msg_inf, r_bits,
                         n_groups: int):
    """Grouped-verdict variant of :func:`_verify_core` (ISSUE 5).

    The S padded sets split into ``n_groups`` contiguous groups of
    S // n_groups lanes; each group gets its own RLC signature
    accumulator, its own check pair e(-g1, sig_acc_g) and its own Fp12
    Miller-product fold, so ONE dispatch returns bool[n_groups] instead
    of an AND-collapsed scalar — a poisoned batch names its guilty
    group(s) for free. Marginal cost over _verify_core: n_groups - 1
    extra check-pair Miller lanes plus an [n_groups]-batched final
    exponentiation. All-padding groups read True (every lane at
    infinity contributes Fp12 one). With group size 1 each verdict is
    the EXACT per-set pairing check (the nonzero blinding scalar
    cancels: x^r = 1 <=> x = 1 in the prime-order target group).
    """
    S, K = pk_inf.shape
    G = n_groups
    gs = S // G
    assert G * gs == S, "group count must divide the padded set count"

    pk_j = pt_from_affine(FP_OPS, pk[0], pk[1], pk_inf)
    agg = pt_tree_sum_axis(FP_OPS, pk_j, axis=1, axis_size=K)  # [S]
    agg_aff = pt_to_affine(FP_OPS, agg)

    rpk = pt_scalar_mul_bits(FP_OPS, agg_aff[:2], agg_aff[2], r_bits)
    rsig = pt_scalar_mul_bits(FP2_OPS, sig, sig_inf, r_bits)

    sig_j = pt_from_affine(FP2_OPS, sig[0], sig[1], sig_inf)
    sub_ok = jnp.all(
        pt_subgroup_check(FP2_OPS, sig_j).reshape(G, gs), axis=1
    )  # [G]

    # Per-group RLC signature accumulators: one batched halving tree.
    rsig_g = tuple(c.reshape(G, gs, *c.shape[1:]) for c in rsig)
    sig_acc = pt_tree_sum_axis(FP2_OPS, rsig_g, axis=1, axis_size=gs)
    sig_acc_aff = pt_to_affine(FP2_OPS, sig_acc)  # [G]

    # S set pairs + G check pairs in ONE Miller batch.
    rpk_aff = pt_to_affine(FP_OPS, rpk)
    g1_x = jnp.concatenate(
        [rpk_aff[0], jnp.broadcast_to(G1_GEN_DEV[0][None], (G, 48))]
    )
    g1_y = jnp.concatenate(
        [rpk_aff[1], jnp.broadcast_to(limb.neg(G1_GEN_DEV[1])[None], (G, 48))]
    )
    g1_inf = jnp.concatenate([rpk_aff[2], jnp.zeros((G,), bool)])
    g2_x = jnp.concatenate([msg[0], sig_acc_aff[0]])
    g2_y = jnp.concatenate([msg[1], sig_acc_aff[1]])
    g2_inf = jnp.concatenate([msg_inf, sig_acc_aff[2]])

    f = miller_loop((g1_x, g1_y), g1_inf, (g2_x, g2_y), g2_inf)
    f_grp = fp12_tree_prod_groups(f[:S].reshape(G, gs, *f.shape[1:]), gs)
    f_grp = fp12_mul(f_grp, f[S:])      # fold in the check pairs, [G]
    fe = final_exponentiation(f_grp)    # batched over the group axis
    return fp12_is_one(fe) & sub_ok    # bool[G]


_verify_grouped_jit = jax.jit(
    _verify_core_grouped, static_argnames=("n_groups",)
)


# --- mesh collective building blocks of the fused path -------------------
# Named and separated so the fast test tier can certify the collective
# composition on the CPU mesh WITHOUT the Pallas kernel bodies (whose
# interpret-mode trace costs ~17 min): tests/test_parallel.py
# test_fused_collectives_match_host runs exactly these functions inside
# shard_map against a host oracle. _verify_core_fused(axis=...) calls
# them verbatim, so a broken all_gather/fold/psum/axis_index composition
# fails the fast tier, not just TPU hardware.


def mesh_all_ok(ok_lanes, axis):
    """Global AND of per-chip boolean lanes (psum of failure counts)."""
    bad = jax.lax.psum(jnp.sum(~ok_lanes), axis)
    return bad == 0


def mesh_fold_point(ops, point, axis):
    """Fold per-chip partial-sum points over the mesh axis: all_gather
    of one point per chip, then a scan fold (group law is not a ring
    sum — psum cannot combine it)."""
    from .ops.points import pt_fold_scan

    parts = tuple(jax.lax.all_gather(c, axis) for c in point)
    return pt_fold_scan(ops, parts, parts[0].shape[0])


def mesh_rank0_lane(axis):
    """Infinity mask keeping only rank 0's check-pair lane finite (the
    folded accumulator is replicated; other ranks contribute Fp12 one)."""
    return (jax.lax.axis_index(axis) != 0)[None]


def mesh_fold_fp12(f1, axis):
    """Fold per-chip Fp12 Miller partials over the mesh axis."""
    from .ops.pairing import fp12_fold_scan

    f_all = jax.lax.all_gather(f1, axis)
    return fp12_fold_scan(f_all, f_all.shape[0])


def _verify_core_fused(pk, pk_inf, sig, sig_inf, msg, msg_inf, r_bits,
                       msm_idx=None, msm_valid=None,
                       *, axis: str | None = None):
    """Fused-kernel variant of :func:`_verify_core` (same contract).

    The long sequential chains (to-affine inversions, RLC scalar muls,
    subgroup checks, Miller loops, final exponentiation) each run as ONE
    Pallas program (ops/tkernel_calls.py) — loop iterations in-kernel
    cost ~μs vs ~0.1-1ms per XLA-level op, which is what bounds
    _verify_core's wall time. Log-depth glue (aggregation/product trees,
    concatenation) stays in XLA. Verified bit-equivalent to
    _verify_core; both paths share the host-side assembly in JaxBackend.

    ``axis``: when called inside shard_map with the set (S) dimension
    sharded over a mesh axis of that name, the three cross-set
    combination points become collectives riding ICI — psum of subgroup
    failures, all_gather+fold of the RLC signature accumulator, and
    all_gather+fold of the per-chip Fp12 Miller partials (the check pair
    e(-g1, sig_acc) rides only rank 0's lane). This is the ONE code path
    from verify_signature_sets to N chips (VERDICT r1 item 7); rayon
    chunks in the reference (block_signature_verifier.rs:366-375) become
    mesh shards here.
    """
    from .ops import tkernel as tk
    from .ops import tkernel_calls as tc
    from .ops.pairing import fp12_tree_prod

    S, K = pk_inf.shape

    def mask_row(m):
        return m[None, :].astype(jnp.int32)

    # Per-set pubkey aggregation (log2 K tree, XLA).
    pk_j = pt_from_affine(FP_OPS, pk[0], pk[1], pk_inf)
    agg = pt_tree_sum_axis(FP_OPS, pk_j, axis=1, axis_size=K)  # [S]

    # Affine-normalize the aggregates in one inversion kernel.
    agg_t = tuple(tk.batch_to_t(c) for c in agg)
    ax, ay, ainf = tc.to_affine_g1_t(agg_t)

    # RLC scalar muls. The pk side stays a per-set 64-step scan kernel
    # (each [r_i]agg_pk_i is a separate Miller operand); the signature
    # accumulator side is a true MSM and uses the bucketed windowed
    # kernel when the host supplied a schedule (ops/msm.py — VERDICT r2
    # item 1; blst's amortized multi-aggregate check, impls/blst.rs:114).
    bits_t = jnp.transpose(r_bits)                       # [64, S]
    sig_t = (tk.batch_to_t(sig[0]), tk.batch_to_t(sig[1]))
    rpk = tc.scalar_mul_g1_t(ax, ay, mask_row(ainf), bits_t)
    if msm_idx is None:
        rsig = tc.scalar_mul_g2_t(
            sig_t[0], sig_t[1], mask_row(sig_inf), bits_t
        )

    # Signature subgroup membership (psi-criterion kernel: ~64-step
    # chain instead of the 255-step full-order multiply).
    ok_lanes = tc.subgroup_check_g2_fast_t(
        sig_t[0], sig_t[1], mask_row(sig_inf)
    )
    if axis is None:
        sub_ok = jnp.all(ok_lanes)
    else:
        sub_ok = mesh_all_ok(ok_lanes, axis)

    # sum_i [r_i] sig_i: bucketed MSM (one kernel pair) or the scan
    # path's log2 S tree; + mesh fold; then one affine kernel.
    if msm_idx is not None:
        from .ops.msm import msm_g2

        sig_acc = msm_g2(sig[0], sig[1], msm_idx, msm_valid)
    else:
        rsig_c = tuple(tk.batch_from_t(c) for c in rsig)
        sig_acc = pt_tree_sum(FP2_OPS, rsig_c, S)
    if axis is not None:
        sig_acc = mesh_fold_point(FP2_OPS, sig_acc, axis)
    sig_acc_t = tuple(tk.batch_to_t(c[None]) for c in sig_acc)
    sax, say, sainf = tc.to_affine_g2_t(sig_acc_t)

    rx, ry, rinf = tc.to_affine_g1_t(rpk)

    # Multi-pairing operand assembly: exactly S+1 pairs through the
    # Miller kernel (which rounds lanes up to a 128-multiple tile);
    # power-of-two padding with Fp12 ones happens AFTER. The win is for
    # S >= 256, where next_pow2(S+1) = 2S would nearly double the Miller
    # lanes; at S <= 128 both paddings land on the same tile size.
    neg_g1 = (G1_GEN_DEV[0][:, None], limb.neg(G1_GEN_DEV[1])[:, None])
    g1_x = jnp.concatenate([rx, neg_g1[0]], axis=-1)
    g1_y = jnp.concatenate([ry, neg_g1[1]], axis=-1)
    # The check pair is replicated across a mesh (sig_acc is folded), so
    # only rank 0 keeps its lane finite — the others contribute Fp12 one.
    chk_inf = (
        jnp.zeros((1,), bool) if axis is None else mesh_rank0_lane(axis)
    )
    g1_inf = jnp.concatenate([rinf, chk_inf])
    msg_t = (tk.batch_to_t(msg[0]), tk.batch_to_t(msg[1]))
    g2_x = jnp.concatenate([msg_t[0], sax], axis=-1)
    g2_y = jnp.concatenate([msg_t[1], say], axis=-1)
    g2_inf = jnp.concatenate([msg_inf, sainf])

    f = tc.miller_loop_kernel_t((g1_x, g1_y), g1_inf, (g2_x, g2_y), g2_inf)

    # Product tree over the pair lanes (log2 M, XLA, classic layout).
    M = _next_pow2(S + 1)
    f_c = tk.batch_from_t(f)
    pad = M - (S + 1)
    if pad:
        ones = jnp.broadcast_to(tower.FP12_ONE, (pad, *tower.FP12_ONE.shape))
        f_c = jnp.concatenate([f_c, ones])
    f1 = fp12_tree_prod(f_c, M)
    if axis is not None:
        f1 = mesh_fold_fp12(f1, axis)

    # Final exponentiation (≈1000-step chain -> kernel, single lane;
    # replicated per chip under a mesh — one tiny lane, not worth a
    # collective round-trip).
    fe = tc.final_exp_kernel_t(tk.batch_to_t(f1[None]))
    return tower.fp12_is_one(tk.batch_from_t(fe)[0]) & sub_ok


_verify_fused_jit = jax.jit(_verify_core_fused)


def _verify_core_fused_grouped(pk, pk_inf, sig, sig_inf, msg, msg_inf,
                               r_bits, n_groups: int, *,
                               axis: str | None = None):
    """Fused-kernel twin of :func:`_verify_core_grouped` (same grouped
    contract, Pallas kernel bodies — bit-equivalent verdict vector).

    Takes no MSM schedule: the bucketed MSM kernel yields ONE global
    signature accumulator, while grouped verdicts need one accumulator
    per group — the per-lane scalar-mul scan plus a batched per-group
    halving tree is the natural formulation, and its cost was already
    acceptable pre-MSM.

    ``axis``: under shard_map with S sharded over that mesh axis, groups
    stay chip-local (the caller guarantees n_groups divides the same
    way), so the ONLY collective is an all_gather of the per-chip
    verdict lanes — no cross-chip point or Fp12 folds at all.
    """
    from .ops import tkernel as tk
    from .ops import tkernel_calls as tc

    S, K = pk_inf.shape
    G = n_groups
    gs = S // G
    assert G * gs == S, "group count must divide the padded set count"

    def mask_row(m):
        return m[None, :].astype(jnp.int32)

    pk_j = pt_from_affine(FP_OPS, pk[0], pk[1], pk_inf)
    agg = pt_tree_sum_axis(FP_OPS, pk_j, axis=1, axis_size=K)  # [S]
    agg_t = tuple(tk.batch_to_t(c) for c in agg)
    ax, ay, ainf = tc.to_affine_g1_t(agg_t)

    bits_t = jnp.transpose(r_bits)                       # [64, S]
    sig_t = (tk.batch_to_t(sig[0]), tk.batch_to_t(sig[1]))
    rpk = tc.scalar_mul_g1_t(ax, ay, mask_row(ainf), bits_t)
    rsig = tc.scalar_mul_g2_t(sig_t[0], sig_t[1], mask_row(sig_inf), bits_t)

    ok_lanes = tc.subgroup_check_g2_fast_t(
        sig_t[0], sig_t[1], mask_row(sig_inf)
    )
    sub_ok = jnp.all(ok_lanes.reshape(G, gs), axis=1)    # [G], chip-local

    # Per-group RLC signature accumulators + one affine kernel over G.
    rsig_c = tuple(tk.batch_from_t(c) for c in rsig)
    rsig_g = tuple(c.reshape(G, gs, *c.shape[1:]) for c in rsig_c)
    sig_acc = pt_tree_sum_axis(FP2_OPS, rsig_g, axis=1, axis_size=gs)
    sig_acc_t = tuple(tk.batch_to_t(c) for c in sig_acc)
    sax, say, sainf = tc.to_affine_g2_t(sig_acc_t)

    rx, ry, rinf = tc.to_affine_g1_t(rpk)

    # S set pairs + G check pairs through one Miller kernel.
    neg_g1 = (G1_GEN_DEV[0][:, None], limb.neg(G1_GEN_DEV[1])[:, None])
    g1_x = jnp.concatenate(
        [rx, jnp.broadcast_to(neg_g1[0], (48, G))], axis=-1
    )
    g1_y = jnp.concatenate(
        [ry, jnp.broadcast_to(neg_g1[1], (48, G))], axis=-1
    )
    g1_inf = jnp.concatenate([rinf, jnp.zeros((G,), bool)])
    msg_t = (tk.batch_to_t(msg[0]), tk.batch_to_t(msg[1]))
    g2_x = jnp.concatenate([msg_t[0], sax], axis=-1)
    g2_y = jnp.concatenate([msg_t[1], say], axis=-1)
    g2_inf = jnp.concatenate([msg_inf, sainf])

    f = tc.miller_loop_kernel_t((g1_x, g1_y), g1_inf, (g2_x, g2_y), g2_inf)
    f_c = tk.batch_from_t(f)                              # [S+G, ...]
    f_grp = fp12_tree_prod_groups(
        f_c[:S].reshape(G, gs, *f_c.shape[1:]), gs
    )
    f_grp = fp12_mul(f_grp, f_c[S:])

    # Final exponentiation: the kernel is already lane-batched, so the
    # G group lanes ride one program (tools/profile_stages.py --json
    # reports the G-lane vs 1-lane overhead for group-count tuning).
    fe = tc.final_exp_kernel_t(tk.batch_to_t(f_grp))
    ok = tower.fp12_is_one(tk.batch_from_t(fe)) & sub_ok  # [G]
    if axis is not None:
        # Chips hold contiguous S (hence group) slices: gathering the
        # per-chip verdict lanes in axis order IS the global vector.
        ok = jax.lax.all_gather(ok, axis).reshape(-1)
    return ok


_verify_fused_grouped_jit = jax.jit(
    _verify_core_fused_grouped, static_argnames=("n_groups",)
)


def _aggregate_verify_core_fused(pkx, pky, pkinf, mx, my, minf,
                                 sigx, sigy, siginf):
    """Device AggregateVerify: prod_i e(pk_i, H(m_i)) * e(-g1, sig) == 1.

    One multi-pairing over N (pk, msg) pairs + the check pair, plus the
    ψ-criterion subgroup check on the signature — no RLC scalars (a
    single aggregate signature covers all messages; reference:
    crypto/bls/src/generic_aggregate_signature.rs aggregate_verify).
    Inputs are affine (pk [N,48] Fp, msg [N,2,48] Fp2, sig [1,...]);
    pad N to a power of two with infinity lanes. BASELINE config #1
    runs through this.
    """
    from .ops import tkernel as tk
    from .ops import tkernel_calls as tc

    N = pkinf.shape[0]

    sig_t = (tk.batch_to_t(sigx), tk.batch_to_t(sigy))
    sig_inf_row = siginf[None, :].astype(jnp.int32)
    sub_ok = jnp.all(
        tc.subgroup_check_g2_fast_t(sig_t[0], sig_t[1], sig_inf_row)
    )

    neg_g1 = (G1_GEN_DEV[0][:, None], limb.neg(G1_GEN_DEV[1])[:, None])
    pkx_t, pky_t = tk.batch_to_t(pkx), tk.batch_to_t(pky)
    g1_x = jnp.concatenate([pkx_t, neg_g1[0]], axis=-1)
    g1_y = jnp.concatenate([pky_t, neg_g1[1]], axis=-1)
    g1_inf = jnp.concatenate([pkinf, jnp.zeros((1,), bool)])
    mx_t, my_t = tk.batch_to_t(mx), tk.batch_to_t(my)
    g2_x = jnp.concatenate([mx_t, sig_t[0]], axis=-1)
    g2_y = jnp.concatenate([my_t, sig_t[1]], axis=-1)
    g2_inf = jnp.concatenate([minf, siginf])

    f = tc.miller_loop_kernel_t((g1_x, g1_y), g1_inf, (g2_x, g2_y), g2_inf)

    M = _next_pow2(N + 1)
    f_c = tk.batch_from_t(f)
    pad = M - (N + 1)
    if pad:
        ones = jnp.broadcast_to(tower.FP12_ONE, (pad, *tower.FP12_ONE.shape))
        f_c = jnp.concatenate([f_c, ones])
    f1 = fp12_tree_prod(f_c, M)
    fe = tc.final_exp_kernel_t(tk.batch_to_t(f1[None]))
    return tower.fp12_is_one(tk.batch_from_t(fe)[0]) & sub_ok


_aggregate_verify_fused_jit = jax.jit(_aggregate_verify_core_fused)


def _aggregate_verify_core(pkx, pky, pkinf, mx, my, minf,
                           sigx, sigy, siginf):
    """Classic-XLA AggregateVerify core — the off-TPU twin of
    _aggregate_verify_core_fused (same multi-pairing + ψ subgroup
    check, classic ops). The fused core's Pallas bodies inline into
    the outer jaxpr under CPU interpret mode and the resulting
    XLA:CPU compile explodes (observed: 100 GB compiler RSS, killed)
    — the same hazard that keeps _dispatch on the classic path
    off-TPU."""
    N = pkinf.shape[0]

    sig_j = pt_from_affine(FP2_OPS, sigx, sigy, siginf)
    sub_ok = jnp.all(pt_subgroup_check(FP2_OPS, sig_j))

    neg_g1 = (G1_GEN_DEV[0][None], limb.neg(G1_GEN_DEV[1])[None])
    g1_x = jnp.concatenate([pkx, neg_g1[0]])
    g1_y = jnp.concatenate([pky, neg_g1[1]])
    g1_inf = jnp.concatenate([pkinf, jnp.zeros((1,), bool)])
    g2_x = jnp.concatenate([mx, sigx])
    g2_y = jnp.concatenate([my, sigy])
    g2_inf = jnp.concatenate([minf, siginf])

    M = _next_pow2(N + 1)
    g1_x, g1_y, g1_inf, g2_x, g2_y, g2_inf = _pad_pair_lanes(
        g1_x, g1_y, g1_inf, g2_x, g2_y, g2_inf, M - (N + 1)
    )

    f = miller_loop((g1_x, g1_y), g1_inf, (g2_x, g2_y), g2_inf)
    f = fp12_tree_prod(f, M)
    f = final_exponentiation(f)
    return fp12_is_one(f) & sub_ok


_aggregate_verify_jit = jax.jit(_aggregate_verify_core)


def aggregate_verify_device(pubkeys, messages, signature) -> bool:
    """AggregateVerify on device from API objects (jax analogue of
    api.AggregateSignature.aggregate_verify; structural edge cases
    mirror the host path)."""
    from .crypto.bls.curve import g2_infinity
    from .ops.points import g1_to_dev, g2_to_dev

    if not pubkeys or len(pubkeys) != len(messages):
        return False
    if signature.is_infinity():
        return False
    # Infinity pubkeys are invalid (blst key_validate semantics; matches
    # native lhbls_aggregate_verify) — normally unreachable because
    # PublicKey.from_bytes rejects infinity, but defensive parity.
    if any(pk.point.infinity for pk in pubkeys):
        return False

    n = len(pubkeys)
    N = _next_pow2(n)
    from .crypto.bls.curve import g1_infinity

    pts = [pk.point for pk in pubkeys] + [g1_infinity()] * (N - n)
    pkx, pky, pkinf = g1_to_dev(pts)

    inf2 = g2_infinity()
    backend = JaxBackend()
    mx, my, minf = backend._hash_message_bytes(messages, N, inf2)
    sigx, sigy, siginf = g2_to_dev([signature.point])
    fn = (
        _aggregate_verify_fused_jit
        if _fused_choice() == "1"
        else _aggregate_verify_jit
    )
    ok = fn(
        jnp.asarray(pkx), jnp.asarray(pky), jnp.asarray(pkinf),
        jnp.asarray(mx), jnp.asarray(my), jnp.asarray(minf),
        jnp.asarray(sigx), jnp.asarray(sigy), jnp.asarray(siginf),
    )
    return bool(ok)


def _gathered(fn):
    """Wrap a verify core so pubkeys come from an HBM-resident uint8 limb
    table (blsrt.DevicePubkeyTable) via a device-side gather of validator
    indices — the batch then ships S*K int32 indices instead of S*K*2*48
    limb planes, and the table uploads once per registry append."""

    def wrapped(tx, ty, idx, pk_inf, sig, sig_inf, msg, msg_inf, r_bits,
                msm_idx=None, msm_valid=None):
        px = tx[idx].astype(jnp.int32)
        py = ty[idx].astype(jnp.int32)
        if msm_idx is None:  # the classic core takes no MSM schedule
            return fn((px, py), pk_inf, sig, sig_inf, msg, msg_inf, r_bits)
        return fn((px, py), pk_inf, sig, sig_inf, msg, msg_inf, r_bits,
                  msm_idx, msm_valid)

    return wrapped


_verify_indexed_jit = jax.jit(_gathered(_verify_core))
_verify_fused_indexed_jit = jax.jit(_gathered(_verify_core_fused))


def _gathered_grouped(fn):
    """HBM-table wrapper for the grouped cores (no MSM leg — see
    _verify_core_fused_grouped)."""

    def wrapped(tx, ty, idx, pk_inf, sig, sig_inf, msg, msg_inf, r_bits,
                n_groups):
        px = tx[idx].astype(jnp.int32)
        py = ty[idx].astype(jnp.int32)
        return fn((px, py), pk_inf, sig, sig_inf, msg, msg_inf, r_bits,
                  n_groups=n_groups)

    return wrapped


_verify_indexed_grouped_jit = jax.jit(
    _gathered_grouped(_verify_core_grouped), static_argnames=("n_groups",)
)
_verify_fused_indexed_grouped_jit = jax.jit(
    _gathered_grouped(_verify_core_fused_grouped),
    static_argnames=("n_groups",),
)


def fused_program(S: int, K: int = 1, *, table_rows: int = 0,
                  groups: int = 0):
    """(jitted fn, argument avals, static kwargs) of the one-chip fused
    verify program that _dispatch (``groups`` 0, with the bucketed-MSM
    schedule) or _dispatch_grouped (``groups`` verdict groups, no MSM)
    calls for an S-set, K-key padded batch: pubkeys as limb grids, or
    gathered from a ``table_rows``-row device pubkey table. Lets a caller
    build the program before the first batch (chip_smoke.py) or compile
    it for a described chip (tools/lowering_smoke.py);
    tests/test_chip_smoke.py checks it against what dispatch passes."""
    from .ops import msm

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def flag(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bool_)

    if table_rows:
        u8 = jax.ShapeDtypeStruct((table_rows, 48), jnp.uint8)
        pk = (u8, u8, i32(S, K), flag(S, K))
    else:
        pk = ((i32(S, K, 48), i32(S, K, 48)), flag(S, K))
    tail = ((i32(S, 2, 48), i32(S, 2, 48)), flag(S),
            (i32(S, 2, 48), i32(S, 2, 48)), flag(S), i32(S, 64))
    if groups:
        fn = (_verify_fused_indexed_grouped_jit if table_rows
              else _verify_fused_grouped_jit)
        return fn, (*pk, *tail), {"n_groups": groups}
    L = msm.max_rounds(S)
    fn = _verify_fused_indexed_jit if table_rows else _verify_fused_jit
    return fn, (*pk, *tail, i32(L, msm.N_BUCKETS),
                flag(L, msm.N_BUCKETS)), {}

# Sharded program construction + caching lives in parallel/engine.py
# (ISSUE 8); these thin delegates keep the historical call sites.
def _sharded_fused_grouped_fn(n_dev: int, n_groups: int,
                              indexed: bool = False):
    return parallel_engine.sharded_grouped_fn(
        n_dev, n_groups, fused=True, indexed=indexed
    )


def _sharded_fused_fn(n_dev: int, indexed: bool = False,
                      with_msm: bool = False):
    return parallel_engine.sharded_verify_fn(
        n_dev, fused=True, indexed=indexed, with_msm=with_msm
    )


def _rand_scalars(n: int) -> tuple[np.ndarray, np.ndarray]:
    """n nonzero RAND_BITS-bit scalars: (uint64[n], MSB-first bits[n,64]).

    One CSPRNG draw + a vectorized bit unpack (the per-bit Python loop this
    replaces cost ~30 µs/scalar — real money at S=2048). The uint64 view
    feeds the host-side MSM bucket scheduler (ops/msm.py).
    """
    assert RAND_BITS == 64
    buf = np.frombuffer(secrets.token_bytes(n * 8), dtype=np.uint64).copy()
    buf[buf == 0] = 1  # nonzero blinding scalars (reference: impls/blst.rs:44)
    shifts = np.arange(RAND_BITS - 1, -1, -1, dtype=np.uint64)
    bits = ((buf[:, None] >> shifts[None, :]) & 1).astype(np.int32)
    return buf, bits


def _rand_bits_array(n: int) -> np.ndarray:
    """Bit tensor only (kept for tests/benches that don't need the MSM)."""
    return _rand_scalars(n)[1]


class _TriagePack:
    """Padded per-row device inputs for one triage chunk, retained on
    the host so refinement rounds re-dispatch by ROW SLICING — no
    re-pack, no re-hash, no re-limbify (the pack and hash_to_curve
    stages dominate bisection re-dispatch cost per
    bls_dispatch_stage_seconds).

    Pubkeys are either table-indexed (tx/ty HBM planes shared by
    reference, idx/pinf host grids) or materialized limb grids
    (px/py/pinf; K=1 when the host-aggregation path collapsed the
    grid). Hash outputs may be live device arrays on the device-HTC
    path — _rows_take slices those with jnp so they never sync."""

    __slots__ = ("n", "S", "K", "tx", "ty", "idx", "px", "py", "pinf",
                 "sx", "sy", "sinf", "mx", "my", "minf", "r_bits")

    def __init__(self, n: int, S: int, K: int):
        self.n, self.S, self.K = n, S, K
        self.tx = self.ty = self.idx = None
        self.px = self.py = None


def _rows_take(arr, sel, pad: int, fill):
    """Row-select + tail-pad for numpy or jax arrays (device arrays
    stay on device)."""
    if isinstance(arr, np.ndarray):
        out = arr[np.asarray(sel, np.int64)]
        if pad:
            out = np.concatenate(
                [out, np.full((pad, *out.shape[1:]), fill, out.dtype)]
            )
        return out
    out = jnp.take(arr, jnp.asarray(np.asarray(sel, np.int64)), axis=0)
    if pad:
        out = jnp.concatenate(
            [out, jnp.full((pad, *out.shape[1:]), fill, out.dtype)]
        )
    return out


def _concat_pad(parts, pad: int, fill):
    """Concatenate row blocks (numpy or jax) and tail-pad ``pad`` rows
    of ``fill``."""
    xp = np if isinstance(parts[0], np.ndarray) else jnp
    out = parts[0] if len(parts) == 1 else xp.concatenate(parts)
    if pad:
        out = xp.concatenate(
            [out, xp.full((pad, *out.shape[1:]), fill, out.dtype)]
        )
    return out


def _widen_keys(rows, K_to: int, fill):
    """Pad a [rows, K, ...] grid block's key axis to ``K_to`` lanes
    (chunks pad K independently; refinement concatenates across
    chunks)."""
    K = rows.shape[1]
    if K == K_to:
        return rows
    xp = np if isinstance(rows, np.ndarray) else jnp
    pad = xp.full(
        (rows.shape[0], K_to - K, *rows.shape[2:]), fill, rows.dtype
    )
    return xp.concatenate([rows, pad], axis=1)


def _slice_packs(packs, sel):
    """Assemble a refinement _TriagePack by slicing rows out of the
    round-1 packs: ``packs`` is [(offset, pack)] covering the live
    batch in order, ``sel`` sorted global set indices. Returns None
    when chunks disagree on pubkey mode (table vs grid, or different
    table objects — possible only if the device table was swapped
    between chunk packs); the caller degrades those sets to host
    bisection."""
    first = packs[0][1]
    table_mode = first.tx is not None
    for _, p in packs:
        if (p.tx is not None) != table_mode:
            return None
        if table_mode and (p.tx is not first.tx or p.ty is not first.ty):
            return None

    m = len(sel)
    S2 = _next_pow2(m)
    pad = S2 - m
    K2 = max(p.K for _, p in packs)
    out = _TriagePack(n=m, S=S2, K=K2)
    sel = np.asarray(sel, np.int64)

    def rows_of(field, fill, per_key: bool):
        parts = []
        for off, p in packs:
            local = sel[(sel >= off) & (sel < off + p.n)] - off
            if len(local) == 0:
                continue
            block = _rows_take(getattr(p, field), local, 0, fill)
            if per_key:
                block = _widen_keys(block, K2, fill)
            parts.append(block)
        return _concat_pad(parts, pad, fill)

    if table_mode:
        out.tx, out.ty = first.tx, first.ty
        out.idx = rows_of("idx", 0, per_key=True)
        out.pinf = rows_of("pinf", True, per_key=True)
    else:
        out.px = rows_of("px", 0, per_key=True)
        out.py = rows_of("py", 0, per_key=True)
        out.pinf = rows_of("pinf", True, per_key=True)
    out.sx = rows_of("sx", 0, per_key=False)
    out.sy = rows_of("sy", 0, per_key=False)
    out.sinf = rows_of("sinf", True, per_key=False)
    out.mx = rows_of("mx", 0, per_key=False)
    out.my = rows_of("my", 0, per_key=False)
    out.minf = rows_of("minf", True, per_key=False)
    out.r_bits = rows_of("r_bits", 0, per_key=False)
    return out


class JaxBackend:
    """Device batch verifier; drop-in for the ``python`` oracle backend."""

    name = "jax"
    # Which device program the last verify took ("sharded-indexed" |
    # "sharded" | "indexed" | "fused" for the Pallas program; a
    # "classic" label for the XLA one; "native-fallback" on the host) —
    # introspection for tests, chip_smoke.py and ops debugging.
    last_path: str | None = None
    # Stage -> seconds of the most recent _dispatch (same data as the
    # bls_dispatch_stage_seconds histogram, but per-call — bench.py's
    # per-stage breakdown). Empty when tracing is disabled.
    last_stage_seconds: dict = {}

    @staticmethod
    def _use_device_htc() -> bool:
        choice = knobs.knob("LHTPU_DEVICE_HTC")
        if choice is not None:
            return choice == "1"
        return jax.default_backend() == "tpu"

    def _hash_messages(self, sets, S: int, inf2):
        return self._hash_message_bytes([s.message for s in sets], S, inf2)

    @staticmethod
    def _batch_cache_on(blsrt) -> bool:
        """Whole-distinct-batch output caching wants BOTH the cache
        family switch and a nonzero capacity (capacity floors at 1 for
        the LRU itself, so a 0 must be gated here)."""
        cap = int(knobs.knob("LHTPU_HTC_BATCH_CACHE"))
        return blsrt.input_caches_enabled() and cap > 0

    def _hash_message_bytes(self, messages, S: int, inf2, stages=None):
        """(mx, my, minf) for the S padded slots from raw message bytes.

        Three sub-stages, each individually retried/injectable and each
        visible in dispatch_stage_report (ISSUE 10):

        * htc_dedup — protocol-aware gather plan (blsrt.dedup_plan): a
          mainnet slot repeats each committee message ~64×, so hashing
          runs once per DISTINCT message. Any failure here degrades IN
          PLACE to the identity plan — bit-identical output, never a
          crash — because dedup is a pure optimization.
        * htc_map — the curve map for the distinct batch: on TPU the
          resident sswu→iso→add(→cofactor) Pallas program; off-TPU the
          per-message oracle memo fill (the classic XLA pipeline would
          recompile per CPU test shape).
        * htc_cofactor — cofactor clear + canonical affine on TPU (a
          no-op clear when the resident program already ran the
          ladder); off-TPU the gather/limbify assembly.

        Failures in htc_map/htc_cofactor re-raise through the outer
        hash_to_curve stage to the rung ladder, like any dispatch
        stage. ``stages`` defaults to the live per-dispatch dict that
        _dispatch points ``last_stage_seconds`` at.
        """
        from . import blsrt

        if stages is None:
            stages = self.last_stage_seconds
        n = len(messages)
        try:
            plan = _retry_stage(
                "htc_dedup", stages, lambda: blsrt.dedup_plan(messages)
            )
        except Exception as exc:
            resilience.DEGRADED_TOTAL.inc(path="htc-dedup")
            _LOG.warn(
                "message dedup degraded to identity plan",
                cause=str(exc)[:200],
            )
            plan = blsrt.identity_plan(messages)

        if self._use_device_htc():
            from .ops.tkernel_htc import (
                hash_to_g2_finish_dev,
                hash_to_g2_map_dev,
            )

            # Pad the distinct-message batch to a power of two so XLA
            # compiles per bucket, not per count. Everything below stays
            # on device (async dispatch, no numpy sync): the verify
            # program chains directly onto the hash outputs.
            D = _next_pow2(len(plan.distinct))
            padded = plan.distinct + [plan.distinct[0]] * (
                D - len(plan.distinct)
            )
            cache_on = self._batch_cache_on(blsrt)
            key = tuple(padded)
            out = blsrt.HTC_BATCH_CACHE.get(key) if cache_on else None
            if out is None:
                Qc = _retry_stage(
                    "htc_map", stages, lambda: hash_to_g2_map_dev(padded)
                )
                out = _retry_stage(
                    "htc_cofactor", stages,
                    lambda: hash_to_g2_finish_dev(*Qc),
                )
                if cache_on:
                    blsrt.HTC_BATCH_CACHE.put(key, out)
            hx, hy, hinf = out
            idx = np.zeros((S,), np.int32)
            idx[:n] = plan.index
            pad_inf = np.ones((S,), bool)
            pad_inf[:n] = False
            idx_d = jnp.asarray(idx)
            mx = hx[idx_d]
            my = hy[idx_d]
            minf = hinf[idx_d] | jnp.asarray(pad_inf)
            return mx, my, minf

        # Oracle path: each distinct message costs ~8 ms of SHA+SSWU, and
        # steady-state slots repeat the same messages every call — the
        # memo is the bounded cross-call LRU in blsrt (ISSUE 4 satellite;
        # the device-HTC path above keeps per-call dedup only: its
        # outputs live on device and chain into the verify program).
        def fill_memo():
            if blsrt.input_caches_enabled():
                memo = []
                for m in plan.distinct:
                    pt = blsrt.HTC_CACHE.get(m)
                    if pt is None:
                        pt = hash_to_g2(m)
                        blsrt.HTC_CACHE.put(m, pt)
                    memo.append(pt)
                return memo
            return [hash_to_g2(m) for m in plan.distinct]

        memo = _retry_stage("htc_map", stages, fill_memo)

        def assemble():
            msgs = [memo[j] for j in plan.index] + [inf2] * (S - n)
            return g2_to_dev(msgs)

        return _retry_stage("htc_cofactor", stages, assemble)

    def verify_signature_sets(self, sets) -> bool:
        """Resilient entry point: transient faults inside any dispatch
        stage are retried at that stage; a rung that keeps failing (or
        fails permanently) trips its circuit breaker and the call
        degrades down the ladder fused → classic → native, so one
        transient runtime fault no longer turns a verdict into a crash
        (the r03/r05 bench-zeroing class). LHTPU_RESILIENCE=0 restores the
        raw raise-through behavior.

        Batches of LHTPU_PIPELINE_MIN_SETS sets or more take the
        pipelined microbatch engine (LHTPU_PIPELINE=0 restores
        single-shot dispatch; verdicts are bit-identical either way)."""
        if pipeline.should_pipeline(len(sets)):
            return self._verify_pipelined(sets)
        if not resilience.enabled():
            out = self._dispatch(sets)
            if isinstance(out, bool):
                return out
            # Forcing the device scalar is where async dispatch errors
            # and device wall time surface — its own attributed stage.
            with _stage("device_sync", self.last_stage_seconds):
                return bool(out)
        return self._verify_resilient(sets)

    def verify_signature_sets_async(self, sets):
        """Dispatch the batch and return a zero-arg resolver.

        JAX dispatch is asynchronous: by the time the resolver is
        called, the host has been free to assemble/hash the NEXT batch
        while this one runs on device — the double-buffering the
        reference gets from worker pools (beacon_processor/mod.rs:
        1004-1070) falls out of the runtime here. Pattern:

            pending = [backend.verify_signature_sets_async(b) for b in batches]
            verdicts = [resolve() for resolve in pending]

        Resilience: a failure at dispatch or at the force falls back to
        the synchronous resilient ladder (the verdict is late, never
        lost); the force itself runs under the device_sync deadline.
        """
        if not resilience.enabled():
            out = self._dispatch(sets)
            if isinstance(out, bool):
                return lambda: out
            stages = self.last_stage_seconds

            def resolve_raw() -> bool:
                with _stage("device_sync", stages):
                    return bool(out)

            return resolve_raw

        try:
            out = self._dispatch(sets)
        except Exception as exc:
            self._record_rung_failure(exc)
            return lambda: self._verify_resilient(sets)
        if isinstance(out, bool):
            return lambda: out
        stages = self.last_stage_seconds
        rung = self._last_rung

        def resolve() -> bool:
            try:
                with _stage("device_sync", stages):
                    return bool(
                        resilience.force_with_deadline(lambda: bool(out))
                    )
            except Exception as exc:
                self._record_rung_failure(exc, rung)
                return self._verify_resilient(sets)

        return resolve

    # ---------------------------------------------- pipelined dispatch

    def _verify_pipelined(self, sets) -> bool:
        """Double-buffered microbatch dispatch (ISSUE 4 tentpole).

        The batch is split into power-of-two chunks
        (common/pipeline.py); each chunk runs through the SAME _dispatch
        — pack / hash_to_curve / scalars / msm_schedule stage wrappers,
        per-stage transient retry, error attribution — but its verdict
        scalar is left un-forced. JAX dispatch is asynchronous, so while
        the device executes chunk i's verify program the host is already
        packing chunk i+1: that host time is hidden behind device
        compute and lands in bls_pipeline_overlap_seconds. Verdicts
        combine through a device-side AND; only the final force pays a
        sync.

        Resilience composes per chunk exactly like a whole-batch call:
        a chunk whose dispatch raises feeds the rung's breaker and
        degrades down the ladder via _verify_resilient; an open breaker
        routes the chunk straight to the degraded rungs; a transient
        failure at the final force re-dispatches every in-flight chunk
        (the failed async buffers are poisoned), a permanent one
        degrades all of them."""
        global _LAST_STAGES, _LAST_PATH
        chunks = pipeline.split(sets)
        run = pipeline.PipelineRun(len(sets), len(chunks))
        combined: dict[str, float] = {}
        res_on = resilience.enabled()
        pending: list = []  # chunks whose device scalar is in flight
        acc = None          # device-side AND of in-flight verdicts
        host_false = False  # a structurally/degraded-False chunk
        for chunk in chunks:
            out = None
            if res_on:
                br = resilience.breaker(self._ladder()[0])
                if not br.allow():
                    # Open breaker: degrade this chunk without
                    # attempting the primary rung, like a whole-batch
                    # call would.
                    if not self._verify_resilient(chunk):
                        host_false = True
                else:
                    try:
                        out = self._dispatch(chunk)
                    except Exception as exc:
                        self._record_rung_failure(exc)
                        if not self._verify_resilient(chunk):
                            host_false = True
            else:
                out = self._dispatch(chunk)
            for k, v in self.last_stage_seconds.items():
                combined[k] = combined.get(k, 0.0) + v
            run.note_chunk(self.last_stage_seconds)
            if isinstance(out, bool) and not out:
                host_false = True
            elif out is not None and not isinstance(out, bool):
                acc = out if acc is None else jnp.logical_and(acc, out)
                pending.append(chunk)
            if host_false:
                break  # one False chunk decides the whole batch

        verdict = not host_false
        if verdict and acc is not None:
            verdict = self._force_pipelined(acc, pending, combined)

        _LAST_STAGES = combined
        self.last_stage_seconds = combined
        self.last_path = (self.last_path or "") + "+pipeline"
        _LAST_PATH = self.last_path
        run.finish()
        return verdict

    def _force_pipelined(self, acc, pending, stages) -> bool:
        """Force the combined device verdict, with _verify_once's
        device_sync semantics: transient failures re-dispatch the
        in-flight chunks under the bounded retry policy, anything else
        trips the breaker and degrades every pending chunk."""
        res_on = resilience.enabled()
        policy = resilience.retry_policy()
        attempt = 0
        while True:
            try:
                with _stage("device_sync", stages):
                    if res_on:
                        verdict = bool(
                            resilience.force_with_deadline(lambda: bool(acc))
                        )
                    else:
                        return bool(acc)
                rung = self._last_rung or self._ladder()[0]
                resilience.breaker(rung).record_success()
                return verdict
            except Exception as exc:
                if not res_on:
                    raise
                category, kind = resilience.classify(exc)
                if (category != resilience.TRANSIENT
                        or attempt >= policy.max_retries):
                    self._record_rung_failure(exc)
                    return all(
                        self._verify_resilient(c) for c in pending
                    )
                attempt += 1
                resilience.RETRIES_TOTAL.inc(stage="device_sync", kind=kind)
                policy.sleep(attempt)
                acc = None
                try:
                    for chunk in pending:
                        out = self._dispatch(chunk)
                        if isinstance(out, bool):
                            if not out:
                                return False
                        else:
                            acc = (
                                out if acc is None
                                else jnp.logical_and(acc, out)
                            )
                except Exception as redispatch_exc:
                    # The re-dispatch died with the device still sick:
                    # degrade every pending chunk down the ladder, same
                    # as the non-transient branch (and as _verify_once's
                    # re-dispatch failures, caught by _verify_resilient).
                    self._record_rung_failure(redispatch_exc)
                    return all(
                        self._verify_resilient(c) for c in pending
                    )
                if acc is None:
                    # Every chunk resolved to a host bool — a recovered
                    # call with no further force, so the rung's breaker
                    # records the success here (the acc path records it
                    # at the next successful force above).
                    rung = self._last_rung or self._ladder()[0]
                    resilience.breaker(rung).record_success()
                    return True

    # ------------------------------------------------ resilience ladder
    # Which rung the last _dispatch ran on ("fused" | "classic" |
    # "native") — breaker bookkeeping for the async resolver.
    _last_rung: str | None = None

    def _ladder(self) -> list[str]:
        """The degradation ladder from the configured primary path down
        (all rungs return bit-identical verdicts; tests pin this)."""
        first = "fused" if _fused_choice() == "1" else "classic"
        rungs = list(resilience.LADDER)
        return rungs[rungs.index(first):]

    def _verify_resilient(self, sets) -> bool:
        """Walk the ladder: first rung whose breaker admits the call
        and whose dispatch survives (with per-stage transient retries)
        answers. Failures feed the rung's breaker — permanent ones trip
        it straight to open; the bottom rung is always attempted."""
        ladder = self._ladder()
        last_exc: Exception | None = None
        for i, rung in enumerate(ladder):
            br = resilience.breaker(rung)
            if not br.allow() and i < len(ladder) - 1:
                continue  # open breaker: degrade without attempting
            try:
                verdict = self._verify_once(
                    sets, path_override=None if i == 0 else rung
                )
            except Exception as exc:
                category, _ = resilience.classify(exc)
                br.record_failure(
                    permanent=category == resilience.PERMANENT
                )
                last_exc = exc
                continue
            br.record_success()
            if i > 0:
                resilience.DEGRADED_TOTAL.inc(path=rung)
                _LOG.warn(
                    "BLS dispatch degraded",
                    rung=rung,
                    path=self.last_path,
                    cause=str(last_exc)[:200] if last_exc
                    else "breaker open",
                )
            return verdict
        raise last_exc

    def _verify_once(self, sets, path_override=None) -> bool:
        """One rung's dispatch + device_sync force. A transient failure
        at the force is retried by RE-DISPATCHING the batch (the failed
        async buffer is poisoned; only a fresh dispatch can recover),
        under the same bounded policy as the per-stage retries. The
        force runs under the LHTPU_SYNC_DEADLINE_S deadline so a wedged
        transfer becomes a classified transient, not a hang."""
        policy = resilience.retry_policy()
        attempt = 0
        while True:
            out = self._dispatch(sets, path_override=path_override)
            if isinstance(out, bool):
                return out
            try:
                with _stage("device_sync", self.last_stage_seconds):
                    return bool(
                        resilience.force_with_deadline(lambda: bool(out))
                    )
            except Exception as exc:
                category, kind = resilience.classify(exc)
                if (not resilience.enabled()
                        or category != resilience.TRANSIENT
                        or attempt >= policy.max_retries):
                    raise
                attempt += 1
                resilience.RETRIES_TOTAL.inc(stage="device_sync", kind=kind)
                policy.sleep(attempt)

    def _record_rung_failure(self, exc, rung: str | None = None) -> None:
        category, _ = resilience.classify(exc)
        rung = rung or self._last_rung or self._ladder()[0]
        resilience.breaker(rung).record_failure(
            permanent=category == resilience.PERMANENT
        )

    def _host_rung_verify(self, sets, stages) -> bool:
        """The bottom rung: native C++ when loadable, else the pure-
        Python oracle — the last resort must always exist, and a slow
        verdict beats a zeroed bench (reference: SURVEY §7.3 "keep a
        host CPU fallback path")."""
        nb = _try_load_native()

        def run() -> bool:
            if nb is not None:
                self.last_path = "native-fallback"
                return bool(nb.verify_signature_sets(sets))
            from .crypto.bls.api import verify_signature_sets_python

            self.last_path = "python-fallback"
            _LOG.warn(
                "native BLS unavailable on degraded dispatch; using the "
                "pure-Python oracle", sets=len(sets),
            )
            return bool(verify_signature_sets_python(sets))

        verdict = _retry_stage("native_fallback", stages, run)
        global _LAST_PATH
        _LAST_PATH = self.last_path
        DISPATCH_BATCHES.inc(path=self.last_path)
        return verdict

    # ------------------------------------------------ poison triage
    # ISSUE 5 tentpole: per-set verdicts at amortized batch cost. One
    # grouped dispatch names the guilty group(s); refinement rounds
    # re-dispatch ONLY the poisoned groups by slicing the already-packed
    # limb grids / table indices / hash-to-curve outputs — zero pack,
    # hash_to_curve or scalars stage time after round 1 (those stages
    # dominate the old host bisection's re-dispatch cost per
    # bls_dispatch_stage_seconds).

    def verify_signature_sets_triaged(self, sets) -> list:
        """Per-set verdicts for a batch, bit-identical to running the
        python oracle per set.

        Route: grouped device dispatch + pack-once refinement
        (_triage_device); LHTPU_VERDICT_GROUPS=0 or any failure the
        resilience layer can't retry in place degrades to the host-side
        budgeted bisection over verify_signature_sets — the ladder
        semantics of the scalar entry point, per set."""
        global _LAST_TRIAGE
        sets = list(sets)
        n = len(sets)
        if n == 0:
            return []
        out = [False] * n
        # Host-side structural rejections are per-set here (the scalar
        # entry point fails the whole batch; reference:
        # impls/blst.rs:79-88).
        live_idx = [
            i for i, s in enumerate(sets)
            if s.signing_keys and not s.signature.is_infinity()
        ]
        _LAST_TRIAGE = {
            "enabled": True,
            "sets": n,
            "groups": 0,
            "rounds": 0,
            "dispatches": 0,
            "clean_groups": 0,
            "poisoned_groups": 0,
            "structural_rejects": n - len(live_idx),
            "fallback": None,
        }
        if not live_idx:
            return out
        live = [sets[i] for i in live_idx]
        if _verdict_groups() == 0:
            verdicts = self._triage_host_bisect(live, reason="disabled")
        elif not resilience.enabled():
            verdicts = self._triage_device(live)
        else:
            # Gate the device path on the primary rung's breaker: after
            # a permanent fault opens it, triage degrades WITHOUT
            # re-attempting until the cooldown admits a half-open probe
            # — whose success here re-closes the breaker and re-promotes
            # the serving path (the soak's recovery guarantee).
            rung = self._ladder()[0]
            br = resilience.breaker(rung)
            if not br.allow():
                resilience.DEGRADED_TOTAL.inc(path="triage-host-bisect")
                verdicts = self._triage_host_bisect(
                    live, reason="breaker-open"
                )
            else:
                try:
                    verdicts = self._triage_device(live)
                except Exception as exc:
                    self._record_rung_failure(exc, rung=rung)
                    resilience.DEGRADED_TOTAL.inc(path="triage-host-bisect")
                    _LOG.warn(
                        "poison triage degraded to host bisection",
                        cause=str(exc)[:200],
                    )
                    verdicts = self._triage_host_bisect(
                        live, reason=f"degraded: {type(exc).__name__}"
                    )
                else:
                    br.record_success()
        for i, v in zip(live_idx, verdicts):
            out[i] = bool(v)
        return out

    def _triage_host_bisect(self, sets, reason: str) -> list:
        """Degraded triage: the pre-ISSUE-5 host bisection over the
        scalar resilient entry point (crypto/bls/api.bisect_verify_sets)
        — correct per-set verdicts at O(log n) full re-dispatches."""
        _LAST_TRIAGE["fallback"] = reason
        from .crypto.bls.api import bisect_verify_sets

        return bisect_verify_sets(sets, backend=self.name)

    def _pack_for_triage(self, sets, stages) -> _TriagePack:
        """Assemble one chunk's padded device inputs through the normal
        pack / hash_to_curve / scalars stage wrappers, but RETAIN every
        grid on the host (_TriagePack) so refinement rounds slice
        instead of re-packing. Same data layout as _dispatch's assembly;
        no MSM schedule (grouped cores keep the per-lane scalar scan)."""
        n = len(sets)
        S = _next_pow2(n)
        K = _next_pow2(max(len(s.signing_keys) for s in sets))
        total_keys = sum(len(s.signing_keys) for s in sets)
        DISPATCH_BATCH_SETS.observe(n)
        DISPATCH_BATCH_KEYS.observe(total_keys)

        from .crypto.bls.curve import g1_infinity, g2_infinity

        inf1, inf2 = g1_infinity(), g2_infinity()
        pk = _TriagePack(n=n, S=S, K=K)

        def run_pack():
            table_args = self._table_gather_args(sets, S, K)
            if table_args is not None:
                pk.tx, pk.ty = table_args[0], table_args[1]
                pk.idx = np.asarray(table_args[2])
                pk.pinf = np.asarray(table_args[3])
            else:
                agg = None
                if _host_agg_wanted(K, S, total_keys):
                    agg = self._host_aggregate_rows(sets, S)
                if agg is not None:
                    from .ops.points import _mont_batch

                    pk.px = _mont_batch(
                        [x for x, _, _ in agg]
                    ).reshape(S, 1, 48)
                    pk.py = _mont_batch(
                        [y for _, y, _ in agg]
                    ).reshape(S, 1, 48)
                    pk.pinf = np.asarray(
                        [i for _, _, i in agg], dtype=bool
                    ).reshape(S, 1)
                    pk.K = 1
                else:
                    pk.px, pk.py, pk.pinf = self._pack_pubkey_grid(
                        sets, S, K, n, inf1
                    )
            sigs = [s.signature.point for s in sets] + [inf2] * (S - n)
            pk.sx, pk.sy, pk.sinf = g2_to_dev(sigs)

        _retry_stage("pack", stages, run_pack)
        pk.mx, pk.my, pk.minf = _retry_stage(
            "hash_to_curve", stages,
            lambda: self._hash_messages(sets, S, inf2),
        )
        pk.r_bits = _retry_stage(
            "scalars", stages, lambda: _rand_bits_array(S)
        )
        return pk

    def _dispatch_grouped(self, pk: _TriagePack, n_groups: int, stages):
        """Enqueue ONE grouped-verdict device program over a packed
        chunk; returns the un-forced device bool[n_groups]. Route
        mirrors _dispatch's (sharded-indexed / sharded / indexed /
        fused / classic, "+triage" suffixed) minus the MSM and
        host-fallback legs. Sharding additionally requires whole groups
        per chip (n_groups and S divisible by the device count)."""
        choice = _fused_choice()
        fused = choice == "1"
        self._last_rung = "fused" if fused else "classic"
        # Device-count routing. Grouped dispatches reuse RETAINED packs
        # (that is the point of triage refinement), so sharding
        # additionally requires the packed S to already divide the mesh
        # into power-of-two slices — refinement rounds whose sliced S
        # falls under the device count re-dispatch single-chip rather
        # than re-pack.
        plan = parallel_engine.plan(pk.n, pk.S, n_groups=n_groups)
        use_sharded = plan.devices > 1 and plan.S == pk.S
        if not use_sharded and plan.devices > 1:
            parallel_engine.release_probe()
            plan = parallel_engine.ShardPlan(
                1, pk.S, pk.S - pk.n, "pack-indivisible"
            )
        n_dev = plan.devices

        def run(sharded: bool):
            tail = (
                jnp.asarray(pk.sx), jnp.asarray(pk.sy), jnp.asarray(pk.sinf),
                jnp.asarray(pk.mx), jnp.asarray(pk.my), jnp.asarray(pk.minf),
                jnp.asarray(pk.r_bits),
            )
            if pk.tx is not None:
                idx, pinf = jnp.asarray(pk.idx), jnp.asarray(pk.pinf)
                if sharded:
                    resilience.maybe_inject("sharded_dispatch")
                    fn = parallel_engine.sharded_grouped_fn(
                        n_dev, n_groups, fused=fused, indexed=True
                    )
                    label = ("sharded-indexed+triage" if fused
                             else "sharded-classic-indexed+triage")
                    probe = _jit_cache_probe(fn, label)
                    ok = fn(pk.tx, pk.ty, idx, pinf, *tail)
                    self.last_path = label
                else:
                    fn = (_verify_fused_indexed_grouped_jit if fused
                          else _verify_indexed_grouped_jit)
                    label = ("indexed+triage" if fused
                             else "classic-indexed+triage")
                    probe = _jit_cache_probe(fn, label)
                    ok = fn(
                        pk.tx, pk.ty, idx, pinf,
                        (tail[0], tail[1]), tail[2],
                        (tail[3], tail[4]), tail[5], tail[6],
                        n_groups=n_groups,
                    )
                    self.last_path = label
            elif sharded:
                resilience.maybe_inject("sharded_dispatch")
                fn = parallel_engine.sharded_grouped_fn(
                    n_dev, n_groups, fused=fused
                )
                label = ("sharded+triage" if fused
                         else "sharded-classic+triage")
                probe = _jit_cache_probe(fn, label)
                ok = fn(
                    jnp.asarray(pk.px), jnp.asarray(pk.py),
                    jnp.asarray(pk.pinf), *tail,
                )
                self.last_path = label
            else:
                fn = (_verify_fused_grouped_jit if fused
                      else _verify_grouped_jit)
                label = "fused+triage" if fused else "classic+triage"
                probe = _jit_cache_probe(fn, label)
                ok = fn(
                    (jnp.asarray(pk.px), jnp.asarray(pk.py)),
                    jnp.asarray(pk.pinf),
                    (tail[0], tail[1]), tail[2],
                    (tail[3], tail[4]), tail[5], tail[6],
                    n_groups=n_groups,
                )
                self.last_path = label
            probe()
            return ok

        if use_sharded:
            try:
                ok = _retry_stage("dispatch", stages, lambda: run(True))
                parallel_engine.record_success()
            except Exception as exc:
                if not resilience.enabled():
                    raise
                category, kind = parallel_engine.record_failure(exc)
                resilience.DEGRADED_TOTAL.inc(path="sharded")
                _LOG.warn(
                    "sharded triage dispatch failed; degrading to "
                    "single-chip", devices=n_dev, category=category,
                    kind=kind,
                )
                plan = parallel_engine.ShardPlan(
                    1, pk.S, pk.S - pk.n, "degraded:" + kind
                )
                ok = _retry_stage("dispatch", stages, lambda: run(False))
                self.last_path += "+sharded-fallback"
        else:
            ok = _retry_stage("dispatch", stages, lambda: run(False))
        parallel_engine.record_dispatch(
            plan, path=self.last_path, n_sets=pk.n
        )
        TRIAGE_DISPATCHES.inc()
        if _LAST_TRIAGE.get("enabled"):
            _LAST_TRIAGE["dispatches"] = _LAST_TRIAGE.get("dispatches", 0) + 1
        global _LAST_PATH
        _LAST_PATH = self.last_path
        DISPATCH_BATCHES.inc(path=self.last_path)
        return ok

    def _triage_force(self, okd, pk: _TriagePack, n_groups: int, stages):
        """Force one grouped verdict vector to host bools, with the
        device_sync semantics of _verify_once: the sync runs under the
        LHTPU_SYNC_DEADLINE_S deadline, and a transient failure is
        retried by RE-DISPATCHING — from the retained pack, so even the
        retry pays no pack/hash time. Non-transients raise to the
        caller's host-bisection fallback."""
        res_on = resilience.enabled()
        policy = resilience.retry_policy()
        attempt = 0
        while True:
            sync: dict[str, float] = {}
            try:
                with _stage("device_sync", sync):
                    if res_on:
                        vec = resilience.force_with_deadline(
                            lambda: np.asarray(okd)
                        )
                    else:
                        vec = np.asarray(okd)
                return np.asarray(vec, dtype=bool)
            except Exception as exc:
                category, kind = resilience.classify(exc)
                if (not res_on or category != resilience.TRANSIENT
                        or attempt >= policy.max_retries):
                    raise
                attempt += 1
                resilience.RETRIES_TOTAL.inc(stage="device_sync", kind=kind)
                policy.sleep(attempt)
                okd = self._dispatch_grouped(pk, n_groups, stages)
            finally:
                stages["device_sync"] = (
                    stages.get("device_sync", 0.0)
                    + sync.get("device_sync", 0.0)
                )

    def _triage_device(self, live) -> list:
        """Grouped-dispatch triage over structurally-valid sets.

        Round 1 packs once (chunked through the pipeline policy above
        LHTPU_PIPELINE_MIN_SETS, so chunk i+1's host pack hides behind
        chunk i's device verify exactly like the scalar path) and
        dispatches G = LHTPU_VERDICT_GROUPS verdict groups per chunk.
        Refinement rounds slice the retained packs down to the poisoned
        groups and re-dispatch at group size cur_gs / G — geometric, so
        the dispatch count is O(log_G poisoned-group-span), bottoming
        out at group size 1 where each verdict is the EXACT per-set
        pairing check (no host re-verification needed)."""
        global _LAST_STAGES, _LAST_PATH
        n = len(live)
        stages: dict[str, float] = {}
        _LAST_STAGES = stages
        self.last_stage_seconds = stages
        self._last_rung = None
        VG = _verdict_groups()

        out = np.zeros(n, dtype=bool)
        packs: list = []   # [(offset, _TriagePack)] in batch order
        flight: list = []  # [(offset, length, gs, G, device vector)]

        pipelined = pipeline.should_pipeline(n)
        spans = pipeline.triage_chunks(n) if pipelined else [(0, n)]
        run = (
            pipeline.PipelineRun(n, len(spans), mode="triage")
            if pipelined else None
        )
        for off, length in spans:
            chunk_stages: dict[str, float] = {}
            pk = self._pack_for_triage(live[off:off + length], chunk_stages)
            G = min(VG, pk.S)
            okd = self._dispatch_grouped(pk, G, chunk_stages)
            for k, v in chunk_stages.items():
                stages[k] = stages.get(k, 0.0) + v
            if run is not None:
                run.note_chunk(chunk_stages)
            packs.append((off, pk))
            flight.append((off, length, pk.S // G, G, okd))

        # Partition round-1 groups into clean (all sets valid) and
        # poisoned (at least one invalid set somewhere in the group).
        suspects: list[int] = []
        n_groups_total = 0
        for (off, length, gs, G, okd), (_, pk) in zip(flight, packs):
            vec = self._triage_force(okd, pk, G, stages)
            for j in range(G):
                lo = j * gs
                if lo >= length:
                    break  # pure-padding groups (always read True)
                hi = min(lo + gs, length)
                n_groups_total += 1
                if bool(vec[j]):
                    TRIAGE_GROUPS.inc(outcome="clean")
                    _LAST_TRIAGE["clean_groups"] += 1
                    out[off + lo:off + hi] = True
                else:
                    TRIAGE_GROUPS.inc(outcome="poisoned")
                    _LAST_TRIAGE["poisoned_groups"] += 1
                    suspects.extend(range(off + lo, off + hi))
        _LAST_TRIAGE["groups"] = n_groups_total
        rounds = 1
        cur_gs = max(gs for (_, _, gs, _, _) in flight)

        # Refinement: re-dispatch ONLY the poisoned span, sliced out of
        # the retained packs — no pack/hash_to_curve/scalars stage runs
        # past this point (the acceptance test pins the histogram
        # counts).
        while suspects:
            if cur_gs <= 1:
                # Group size 1 verdicts are exact per-set checks:
                # failing singletons are definitively invalid.
                for i in suspects:
                    out[i] = False
                break
            m = len(suspects)
            S2 = _next_pow2(m)
            gs2 = max(1, min(cur_gs // max(2, VG), S2))
            G2 = S2 // gs2
            pk2 = _slice_packs(packs, suspects)
            if pk2 is None:
                # Chunks disagree on pack mode (device table swapped
                # mid-call): degrade just the suspect sets.
                sub = self._triage_host_bisect(
                    [live[i] for i in suspects], reason="mixed pack modes"
                )
                for i, v in zip(suspects, sub):
                    out[i] = bool(v)
                break
            okd = self._dispatch_grouped(pk2, G2, stages)
            vec = self._triage_force(okd, pk2, G2, stages)
            rounds += 1
            nxt: list[int] = []
            for j in range(G2):
                lo = j * gs2
                if lo >= m:
                    break
                hi = min(lo + gs2, m)
                if bool(vec[j]):
                    TRIAGE_GROUPS.inc(outcome="clean")
                    _LAST_TRIAGE["clean_groups"] += 1
                    for t in range(lo, hi):
                        out[suspects[t]] = True
                else:
                    TRIAGE_GROUPS.inc(outcome="poisoned")
                    _LAST_TRIAGE["poisoned_groups"] += 1
                    if gs2 == 1:
                        out[suspects[lo]] = False  # exact singleton
                    else:
                        nxt.extend(suspects[lo:hi])
            suspects = nxt
            cur_gs = gs2

        _LAST_TRIAGE["rounds"] = rounds
        if resilience.enabled():
            rung = self._last_rung or self._ladder()[0]
            resilience.breaker(rung).record_success()
        if run is not None:
            self.last_path = (self.last_path or "") + "+pipeline"
            _LAST_PATH = self.last_path
            run.finish()
        return out.tolist()

    def _dispatch(self, sets, path_override: str | None = None):
        """Common assembly + device dispatch; returns a host bool (for
        structural rejections) or the un-forced device verdict scalar.

        Every phase runs inside an attributed stage (pack /
        hash_to_curve / scalars / msm_schedule / dispatch, plus
        device_sync at the force point): wall time lands in
        bls_dispatch_stage_seconds, a failure increments
        bls_dispatch_errors_total{stage=...} and is named in
        dispatch_stage_report() instead of being swallowed. Each stage
        additionally runs under _retry_stage (transient-fault retry
        re-entering at the failing stage + LHTPU_FAULT_INJECT hook).

        ``path_override`` pins one ladder rung ("fused" | "classic" |
        "native") for degraded dispatches: overridden calls skip the
        opportunistic host-fallback routing and (for "classic") the
        mesh sharding, so a rung behaves deterministically under its
        breaker."""
        global _LAST_STAGES, _LAST_PATH
        stages: dict[str, float] = {}
        _LAST_STAGES = stages
        self.last_stage_seconds = stages
        self._last_rung = None
        if not sets:
            return False
        # Host-side structural rejections (reference: impls/blst.rs:79-88).
        for s in sets:
            if not s.signing_keys:
                return False
            if s.signature.is_infinity():
                return False

        n = len(sets)
        total_keys = sum(len(s.signing_keys) for s in sets)
        DISPATCH_BATCH_SETS.observe(n)
        DISPATCH_BATCH_KEYS.observe(total_keys)

        if path_override == "native":
            self._last_rung = "native"
            return self._host_rung_verify(sets, stages)

        # Small-batch host fallback (SURVEY §7.3: "keep a host CPU
        # fallback path for singletons"): device dispatch latency
        # dwarfs tiny batches that the native C++ backend verifies in
        # milliseconds — e.g. one 512-key sync-committee set: 13.6 ms
        # native vs 329 ms device (bench config #3, before PR 1). Cost
        # model from those measurements; LHTPU_HOST_FALLBACK=0 disables,
        # the threshold
        # is LHTPU_HOST_FALLBACK_MS. TPU-only so CPU tests keep
        # exercising the device paths.
        if (
            path_override is None
            and knobs.knob("LHTPU_HOST_FALLBACK")
            and jax.default_backend() == "tpu"
        ):
            est_native_ms = (
                HOST_FALLBACK_MS_PER_SET * n
                + HOST_FALLBACK_MS_PER_KEY * total_keys
            )
            if est_native_ms < knobs.knob("LHTPU_HOST_FALLBACK_MS"):
                nb = _try_load_native()
                if nb is not None:
                    self.last_path = "native-fallback"
                    self._last_rung = "native"
                    _LAST_PATH = "native-fallback"
                    DISPATCH_BATCHES.inc(path="native-fallback")
                    return _retry_stage(
                        "native_fallback", stages,
                        lambda: bool(nb.verify_signature_sets(sets)),
                    )

        S = _next_pow2(n)
        K = _next_pow2(max(len(s.signing_keys) for s in sets))

        # Path choice up front (it shapes the padding).
        choice = {"fused": "1", "classic": "0"}.get(
            path_override, _fused_choice()
        )
        self._last_rung = "fused" if choice == "1" else "classic"
        # Device-count routing (parallel/engine.py): the plan may re-pad
        # the set axis so every chip gets a power-of-two local slice
        # (pt_tree_sum in the scan fallback requires it); infinity lanes
        # are inert. Forced sharding is never silently dropped to one
        # chip — only the engine's breaker (an earlier sharded permanent
        # fault) or a rung override can.
        plan = parallel_engine.plan(n, S, path_override=path_override)
        n_dev = plan.devices
        use_sharded = n_dev > 1
        S = plan.S

        from .crypto.bls.curve import g1_infinity, g2_infinity

        inf1, inf2 = g1_infinity(), g2_infinity()

        def run_pack():
            # HBM-table fast path: every set carries validator indices the
            # device table covers -> gather on device, no coordinate
            # upload. Composes with sharding (the table is replicated per
            # chip and the gather happens inside the shard).
            table_args = self._table_gather_args(sets, S, K)

            agg = None  # host-aggregated rows; only on the non-table path
            px = py = pinf = None
            if table_args is None:
                # Host pubkey aggregation pays n*mean_K serial CPU point
                # adds to collapse the grid to K=1; worth it only when the
                # [S, K_pad] grid is mostly padding (mixed-K batches —
                # measured 6.6x on BASELINE config #2 at max_K/mean_K 6.6).
                # Uniform-K batches keep the device aggregation tree, and
                # CPU test runs keep exercising it (TPU-gated like the
                # native fallback above). LHTPU_HOST_AGG=0/1 overrides.
                if _host_agg_wanted(K, S, total_keys):
                    agg = self._host_aggregate_rows(sets, S)
                if agg is not None:
                    # Mixed-K batches: per-set pubkey aggregation on the
                    # native CPU backend (exactly the reference's split —
                    # blst aggregates each set's keys on CPU, then one
                    # multi-pairing: impls/blst.rs:36-119). Shipping a K=1
                    # grid replaces an [S, K_pad] grid whose padding waste
                    # is max_K/mean_K (measured 6.6x on BASELINE config
                    # #2, where this path took the device from 0.84x
                    # native to parity-beating).
                    from .ops.points import _mont_batch

                    px = _mont_batch(
                        [x for x, _, _ in agg]
                    ).reshape(S, 1, 48)
                    py = _mont_batch(
                        [y for _, y, _ in agg]
                    ).reshape(S, 1, 48)
                    pinf = np.asarray(
                        [i for _, _, i in agg], dtype=bool
                    ).reshape(S, 1)
                else:
                    # Pubkeys: [S, K] affine grid, padding at infinity
                    # (rows come from the cross-call limb cache when
                    # enabled — validators repeat every epoch).
                    px, py, pinf = self._pack_pubkey_grid(
                        sets, S, K, n, inf1
                    )

            sigs = [s.signature.point for s in sets] + [inf2] * (S - n)
            sx, sy, sinf = g2_to_dev(sigs)
            return table_args, agg, px, py, pinf, sx, sy, sinf

        table_args, agg, px, py, pinf, sx, sy, sinf = _retry_stage(
            "pack", stages, run_pack
        )

        mx, my, minf = _retry_stage(
            "hash_to_curve", stages,
            lambda: self._hash_messages(sets, S, inf2),
        )

        r_u64, r_bits = _retry_stage(
            "scalars", stages, lambda: _rand_scalars(S)
        )

        # Bucketed-MSM schedule for the RLC signature accumulator
        # (host-side — the scalars are host CSPRNG output; ops/msm.py).
        # None -> the cores keep their per-lane scalar-mul scan.
        def run_msm_schedule():
            msm_sched = None
            if choice == "1" and knobs.knob("LHTPU_MSM_VERIFY"):
                from .ops import msm as _msm

                skip = np.arange(S) >= n
                if use_sharded:
                    L = _msm.max_rounds(S // n_dev)
                    msm_sched = _msm.build_schedule_sharded(
                        r_u64, L, n_dev, skip
                    )
                else:
                    msm_sched = _msm.build_schedule(
                        r_u64, _msm.max_rounds(S), skip
                    )
            return msm_sched

        msm_sched = _retry_stage("msm_schedule", stages, run_msm_schedule)

        # Transfer + async enqueue (a jit-cache miss makes this stage the
        # trace+compile — bls_jit_cache_events_total disambiguates).
        # ``sharded``/``sched`` are parameters (not closed over) so the
        # sharded-fault fallback below can re-run single-chip on the
        # SAME packed grids: the sharded padding is still a power of
        # two, so verdicts are bit-identical either way.
        fused = choice == "1"

        def run_device_dispatch(sharded: bool, sched):
            msm_args = (
                ()
                if sched is None
                else (jnp.asarray(sched[0]), jnp.asarray(sched[1]))
            )
            tail = (
                (jnp.asarray(sx), jnp.asarray(sy)),
                jnp.asarray(sinf),
                (jnp.asarray(mx), jnp.asarray(my)),
                jnp.asarray(minf),
                jnp.asarray(r_bits),
            )
            if sharded and table_args is not None:
                # Fast paths composed: HBM-table gather + shard_map
                # over a ("dp",) mesh (+ fused kernels on TPU).
                resilience.maybe_inject("sharded_dispatch")
                tx, ty, idx, tinf = table_args
                fn = parallel_engine.sharded_verify_fn(
                    n_dev, fused=fused, indexed=True,
                    with_msm=bool(msm_args),
                )
                label = ("sharded-indexed" if fused
                         else "sharded-classic-indexed")
                probe = _jit_cache_probe(fn, label)
                ok = fn(
                    tx, ty, jnp.asarray(idx), jnp.asarray(tinf),
                    tail[0][0], tail[0][1], tail[1],
                    tail[2][0], tail[2][1], tail[3], tail[4], *msm_args,
                )[0]
                self.last_path = label
            elif sharded:
                # One code path to N chips: the verify core inside
                # shard_map over a ("dp",) mesh (parallel/sharding.py).
                resilience.maybe_inject("sharded_dispatch")
                fn = parallel_engine.sharded_verify_fn(
                    n_dev, fused=fused, with_msm=bool(msm_args)
                )
                label = "sharded" if fused else "sharded-classic"
                probe = _jit_cache_probe(fn, label)
                ok = fn(
                    jnp.asarray(px), jnp.asarray(py), jnp.asarray(pinf),
                    tail[0][0], tail[0][1], tail[1],
                    tail[2][0], tail[2][1], tail[3], tail[4], *msm_args,
                )[0]
                self.last_path = label
            elif table_args is not None:
                tx, ty, idx, tinf = table_args
                fn = (_verify_fused_indexed_jit if fused
                      else _verify_indexed_jit)
                label = "indexed" if fused else "classic-indexed"
                probe = _jit_cache_probe(fn, label)
                ok = fn(tx, ty, jnp.asarray(idx), jnp.asarray(tinf), *tail,
                        *msm_args)
                self.last_path = label
            else:
                fn = _verify_fused_jit if fused else _verify_jit
                probe = _jit_cache_probe(
                    fn, "fused" if fused else "classic"
                )
                ok = fn((jnp.asarray(px), jnp.asarray(py)),
                        jnp.asarray(pinf), *tail, *msm_args)
                self.last_path = "fused" if fused else "classic"
            probe()
            return ok

        if use_sharded:
            try:
                ok = _retry_stage(
                    "dispatch", stages,
                    lambda: run_device_dispatch(True, msm_sched),
                )
                parallel_engine.record_success()
            except Exception as exc:
                if not resilience.enabled():
                    raise
                # Chip loss / permanent sharded fault (or exhausted
                # transient budget): trip the sharded breaker and
                # answer from ONE chip with the same grids. The MSM
                # schedule is per-chip-shaped, so the fallback reverts
                # to the in-core scalar-mul scan (same verdict).
                category, kind = parallel_engine.record_failure(exc)
                resilience.DEGRADED_TOTAL.inc(path="sharded")
                _LOG.warn(
                    "sharded dispatch failed; degrading to single-chip",
                    devices=n_dev, category=category, kind=kind,
                )
                plan = parallel_engine.ShardPlan(
                    1, S, S - n, "degraded:" + kind
                )
                ok = _retry_stage(
                    "dispatch", stages,
                    lambda: run_device_dispatch(False, None),
                )
                self.last_path += "+sharded-fallback"
        else:
            ok = _retry_stage(
                "dispatch", stages,
                lambda: run_device_dispatch(False, msm_sched),
            )
        if table_args is None and agg is not None:
            self.last_path += "+host-agg"
        parallel_engine.record_dispatch(plan, path=self.last_path, n_sets=n)
        _LAST_PATH = self.last_path
        DISPATCH_BATCHES.inc(path=self.last_path)
        return ok

    @staticmethod
    def _pack_pubkey_grid(sets, S: int, K: int, n: int, inf1):
        """[S, K] pubkey limb grid, padding lanes at infinity.

        With the cross-call cache enabled (LHTPU_INPUT_CACHE, default
        on), each distinct pubkey's Montgomery limb rows are limbified
        once and parked in blsrt.PUBKEY_ROW_CACHE's numpy arena; a warm
        batch rebuilds the grid with dict lookups plus one fancy-index
        gather — no bigint math. Misses are limbified in ONE vectorized
        g1_to_dev batch, so the cold path is exactly the uncached path
        plus the insert (bit-identical rows either way). Padding lanes
        are zero-coordinate infinity, which is precisely what
        g1_to_dev(inf1) produces.

        A batch with more DISTINCT keys than the arena has slots cannot
        go through insert-then-gather: the miss-insert loop's LRU
        evictions would reuse slots already recorded in idx (batch hits
        or earlier misses) before the gather runs, silently corrupting
        the grid. Such batches build uncached (counted as ``bypass``
        cache events). Within capacity the order is safe: lookup
        refreshes every batch hit to MRU and inserts land MRU, so
        evictions only ever claim rows no lane of this batch
        references."""
        from . import blsrt

        keys = None
        if blsrt.input_caches_enabled():
            cache = blsrt.PUBKEY_ROW_CACHE
            flat_pks = [pk for s in sets for pk in s.signing_keys]
            # serialized-bytes keys straight off the lazy-deserialize
            # slot; pubkey_cache_key derives (and memoizes) the same
            # canonical form for keys built from raw points
            keys = [pk._bytes for pk in flat_pks]
            if any(k is None for k in keys):
                keys = [blsrt.pubkey_cache_key(pk) for pk in flat_pks]
            if len(set(keys)) > cache.capacity:
                blsrt.CACHE_EVENTS.inc(
                    len(keys), cache=cache.name, event="bypass"
                )
                keys = None
        if keys is None:
            pk_rows = []
            for s in sets:
                row = [pk.point for pk in s.signing_keys]
                row += [inf1] * (K - len(row))
                pk_rows.append(row)
            pk_rows += [[inf1] * K] * (S - n)
            flat = [p for row in pk_rows for p in row]
            px, py, pinf = g1_to_dev(flat)
            return (
                px.reshape(S, K, 48),
                py.reshape(S, K, 48),
                pinf.reshape(S, K),
            )

        idx, misses = cache.lookup(keys)
        if misses:
            mx, my, minf = g1_to_dev([flat_pks[i].point for i in misses])
            for j, i in enumerate(misses):
                idx[i] = cache.insert(
                    keys[i], mx[j], my[j], bool(minf[j])
                )
        gx, gy, ginf = cache.gather(idx)
        if len(flat_pks) == S * K:
            # every lane is a real key (uniform-K, no row padding): the
            # gather IS the grid, skip the zero-fill + scatter
            return (
                gx.reshape(S, K, 48),
                gy.reshape(S, K, 48),
                ginf.reshape(S, K),
            )
        px = np.zeros((S * K, 48), np.int32)
        py = np.zeros((S * K, 48), np.int32)
        pinf = np.ones((S * K,), bool)
        pos = [
            si * K + ki
            for si, s in enumerate(sets)
            for ki in range(len(s.signing_keys))
        ]
        pos_a = np.asarray(pos, np.int64)
        px[pos_a] = gx
        py[pos_a] = gy
        pinf[pos_a] = ginf
        return (
            px.reshape(S, K, 48),
            py.reshape(S, K, 48),
            pinf.reshape(S, K),
        )

    @staticmethod
    def _host_aggregate_rows(sets, S: int):
        """Per-set pubkey aggregation on the native CPU backend, padded
        to ``S`` rows with infinity. Returns [(x_int, y_int, inf)] of
        length S, or None when the native library is unavailable or a
        set carries an infinity pubkey (the [S, K] grid path keeps the
        device-side aggregation-tree semantics for those).

        This is the CPU half of the reference's mixed-K split: blst
        aggregates each set's keys on CPU, then runs one multi-pairing
        (impls/blst.rs:36-119)."""
        nb = _try_load_native()
        if nb is None:
            return None
        rows = []
        for s in sets:
            pts = [pk.point for pk in s.signing_keys]
            if any(p.infinity for p in pts):
                return None
            rows.append(pts)
        try:
            agg = nb.g1_aggregate_rows(rows)
        except ValueError:
            return None
        return agg + [(0, 0, True)] * (S - len(sets))

    @staticmethod
    def _table_gather_args(sets, S: int, K: int):
        """(table_x, table_y, idx[S,K], lane_inf[S,K]) when every set
        carries validator indices the registered HBM table covers, else
        None (host-coordinate fallback — e.g. VC-side or pre-import
        keys)."""
        from . import blsrt

        table = blsrt.get_device_table()
        if table is None or len(table) == 0:
            return None
        rows = []
        for s in sets:
            idxs = s.signing_key_indices
            if idxs is None or len(idxs) != len(s.signing_keys):
                return None
            if idxs and max(idxs) >= len(table):
                return None
            rows.append(idxs)
        rows += [[]] * (S - len(sets))
        idx, inf = table.gather_args(rows, K)
        tx, ty = table.device_arrays()
        return tx, ty, idx, inf


register_backend("jax", JaxBackend())
