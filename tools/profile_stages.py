"""Stage-level wall-time profile of the fused verifier + hash path on TPU.

Times each device stage of jax_backend._verify_core_fused and the
hash-to-G2 pipeline separately (block_until_ready around each), plus the
host-side assembly costs, at the bench shape S=2048, K=1. Guides kernel
optimization: run after kernel changes to see which stage moved.

With ``--json`` the human-readable lines go to stderr and stdout gets
ONE parseable JSON line — {"metric": "bls_stage_profile", "stages_ms":
{...}} — the same per-stage breakdown shape bench.py embeds, so a
round's BENCH json can carry a device-stage profile.

``--devices N`` switches to the multi-chip profile (ISSUE 8): one warm
sharded verify on an N-way mesh (forced host devices off-TPU), per-shard
stage attribution, and the cross-chip fold round measured in isolation
with its share of the device dispatch stage.

Usage:  python tools/profile_stages.py [S] [--json] [--devices N]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

JSON_MODE = "--json" in sys.argv

#: label -> milliseconds, accumulated by record()/timeit for --json
STAGES_MS: dict[str, float] = {}


def record(label: str, ms: float) -> None:
    STAGES_MS[label] = round(ms, 3)
    print(f"{label:42s} {ms:10.1f} ms",
          file=sys.stderr if JSON_MODE else sys.stdout)

def _devices_arg() -> int | None:
    """``--devices N`` — profile the SHARDED dispatch at an N-way mesh
    instead of the single-chip kernel stages; None when absent."""
    if "--devices" not in sys.argv:
        return None
    i = sys.argv.index("--devices")
    if i + 1 < len(sys.argv):
        try:
            return max(1, int(sys.argv[i + 1]))
        except ValueError:
            pass
    return 8


DEVICES = _devices_arg()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "")

# The host mesh must be forced BEFORE jax initializes (XLA reads the
# flag once, at backend init); only affects the CPU platform.
if DEVICES and DEVICES > 1:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + f" --xla_force_host_platform_device_count={DEVICES}"
        ).strip()

import jax
import jax.numpy as jnp

from lighthouse_tpu.common.compile_cache import configure

configure()

from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet
from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
from lighthouse_tpu.jax_backend import _rand_bits_array
from lighthouse_tpu.ops import tkernel as tk
from lighthouse_tpu.ops import tkernel_calls as tc
from lighthouse_tpu.ops.points import (
    FP2_OPS, FP_OPS, g1_to_dev, g2_to_dev, pt_from_affine, pt_tree_sum,
    pt_tree_sum_axis,
)
from lighthouse_tpu.ops.pairing import fp12_tree_prod
from lighthouse_tpu.utils import next_pow2


def timeit(label, fn, reps=3):
    jax.block_until_ready(fn())  # warm / compile, synchronized
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
        jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps * 1e3
    record(label, dt)
    return dt


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    S = int(args[0]) if args else 2048
    K = 1
    print(f"device={jax.devices()[0].platform} S={S} K={K}",
          file=sys.stderr if JSON_MODE else sys.stdout)

    sks = [SecretKey.from_int(i + 101) for i in range(S)]
    msgs = [i.to_bytes(32, "big") for i in range(S)]
    sets = [
        SignatureSet.single_pubkey(sk.sign(m), sk.public_key(), m)
        for sk, m in zip(sks, msgs)
    ]

    # ------------------------------------------------ host assembly costs
    t0 = time.perf_counter()
    px, py, pinf = g1_to_dev([s.signing_keys[0].point for s in sets])
    record('host g1_to_dev (pubkeys)', (time.perf_counter()-t0)*1e3)
    px, py, pinf = px.reshape(S, K, 48), py.reshape(S, K, 48), pinf.reshape(S, K)
    t0 = time.perf_counter()
    sx, sy, sinf = g2_to_dev([s.signature.point for s in sets])
    record('host g2_to_dev (sigs)', (time.perf_counter()-t0)*1e3)
    t0 = time.perf_counter()
    mpts = [hash_to_g2(m) for m in msgs]
    record('host hash_to_g2 python x S', (time.perf_counter()-t0)*1e3)
    mx, my, minf = g2_to_dev(mpts)
    t0 = time.perf_counter()
    r_bits = _rand_bits_array(S)
    record('host rand bits', (time.perf_counter()-t0)*1e3)

    pk = (jnp.asarray(px), jnp.asarray(py))
    pinf_d = jnp.asarray(pinf)
    sig = (jnp.asarray(sx), jnp.asarray(sy))
    sinf_d = jnp.asarray(sinf)
    msg = (jnp.asarray(mx), jnp.asarray(my))
    minf_d = jnp.asarray(minf)
    bits = jnp.asarray(r_bits)
    jax.block_until_ready((pk, sig, msg, bits))

    # ------------------------------------------------ device stage timings
    # pk aggregation tree (K=1: near no-op) + to-affine
    pk_j = pt_from_affine(FP_OPS, pk[0], pk[1], pinf_d)
    agg = pt_tree_sum_axis(FP_OPS, pk_j, axis=1, axis_size=K)
    agg = jax.block_until_ready(agg)
    agg_t = tuple(tk.batch_to_t(c) for c in agg)
    agg_t = jax.block_until_ready(agg_t)

    timeit("to_affine_g1 (agg)", lambda: tc.to_affine_g1_t(agg_t))
    ax, ay, ainf = tc.to_affine_g1_t(agg_t)
    ainf_row = ainf[None, :].astype(jnp.int32)
    bits_t = jnp.transpose(bits)
    sig_t = (tk.batch_to_t(sig[0]), tk.batch_to_t(sig[1]))
    sig_t = jax.block_until_ready(sig_t)
    sinf_row = sinf_d[None, :].astype(jnp.int32)

    timeit("scalar_mul_g1 (RLC pk)", lambda: tc.scalar_mul_g1_t(ax, ay, ainf_row, bits_t))
    rpk = jax.block_until_ready(tc.scalar_mul_g1_t(ax, ay, ainf_row, bits_t))
    timeit("scalar_mul_g2 (RLC sig)", lambda: tc.scalar_mul_g2_t(sig_t[0], sig_t[1], sinf_row, bits_t))
    rsig = jax.block_until_ready(tc.scalar_mul_g2_t(sig_t[0], sig_t[1], sinf_row, bits_t))
    timeit("subgroup_check_g2_fast", lambda: tc.subgroup_check_g2_fast_t(sig_t[0], sig_t[1], sinf_row))

    rsig_c = tuple(tk.batch_from_t(c) for c in rsig)
    timeit("pt_tree_sum rsig (XLA glue)", lambda: pt_tree_sum(FP2_OPS, rsig_c, S))
    sig_acc = jax.block_until_ready(pt_tree_sum(FP2_OPS, rsig_c, S))
    sig_acc_t = tuple(tk.batch_to_t(c[None]) for c in sig_acc)
    timeit("to_affine_g2 (sig acc, 1 lane)", lambda: tc.to_affine_g2_t(sig_acc_t))
    timeit("to_affine_g1 (rpk)", lambda: tc.to_affine_g1_t(rpk))

    rx, ry, rinf = jax.block_until_ready(tc.to_affine_g1_t(rpk))
    sax, say, sainf = jax.block_until_ready(tc.to_affine_g2_t(sig_acc_t))
    from lighthouse_tpu.ops.limb import neg as limb_neg
    from lighthouse_tpu.ops.points import G1_GEN_DEV
    neg_g1 = (G1_GEN_DEV[0][:, None], limb_neg(G1_GEN_DEV[1])[:, None])
    g1_x = jnp.concatenate([rx, neg_g1[0]], axis=-1)
    g1_y = jnp.concatenate([ry, neg_g1[1]], axis=-1)
    g1_inf = jnp.concatenate([rinf, jnp.zeros((1,), bool)])
    msg_t = (tk.batch_to_t(msg[0]), tk.batch_to_t(msg[1]))
    g2_x = jnp.concatenate([msg_t[0], sax], axis=-1)
    g2_y = jnp.concatenate([msg_t[1], say], axis=-1)
    g2_inf = jnp.concatenate([minf_d, sainf])
    args = jax.block_until_ready((g1_x, g1_y, g1_inf, g2_x, g2_y, g2_inf))

    timeit("miller_loop kernel (S+1 lanes)",
           lambda: tc.miller_loop_kernel_t((g1_x, g1_y), g1_inf, (g2_x, g2_y), g2_inf))
    f = jax.block_until_ready(
        tc.miller_loop_kernel_t((g1_x, g1_y), g1_inf, (g2_x, g2_y), g2_inf))

    from lighthouse_tpu.ops import tower
    M = next_pow2(S + 1)
    f_c = tk.batch_from_t(f)
    pad = M - (S + 1)
    ones = jnp.broadcast_to(tower.FP12_ONE, (pad, *tower.FP12_ONE.shape))
    f_cp = jax.block_until_ready(jnp.concatenate([f_c, ones]))
    timeit("fp12_tree_prod (XLA glue)", lambda: fp12_tree_prod(f_cp, M))
    f1 = jax.block_until_ready(fp12_tree_prod(f_cp, M))
    fe1 = timeit("final_exp kernel (1 lane)",
                 lambda: tc.final_exp_kernel_t(tk.batch_to_t(f1[None])))

    # Grouped-verdict overhead (ISSUE 5): poison triage folds the Miller
    # product per group, so the final exponentiation runs [G]-batched
    # instead of on one collapsed lane. The delta between these two rows
    # is the clean-batch price of carrying G verdicts per dispatch.
    from lighthouse_tpu.jax_backend import _verdict_groups
    G = _verdict_groups() or 32
    fG = jax.block_until_ready(jnp.broadcast_to(f1[None], (G, *f1.shape)))
    feG = timeit(f"final_exp kernel ({G} verdict lanes)",
                 lambda: tc.final_exp_kernel_t(tk.batch_to_t(fG)))
    record("grouped_verdict_final_exp_overhead", feG - fe1)

    # ------------------------------------------------ hash path stages
    from lighthouse_tpu.ops.htc import DST, hash_to_field_dev
    from lighthouse_tpu.ops.tkernel_htc import (
        _cofactor_t, _interpret, _map_to_g2_fused, _map_to_g2_resident_t,
        _sswu_iso_t,
    )

    t0 = time.perf_counter()
    u = jnp.asarray(hash_to_field_dev(msgs, DST))
    u = jax.block_until_ready(u)
    record('host hash_to_field (SHA)', (time.perf_counter()-t0)*1e3)

    # chained A/B path (LHTPU_HTC_RESIDENT=0): per-kernel attribution
    n = u.shape[0]
    flat = jnp.moveaxis(u, 1, 0).reshape(2 * n, 2, 48)
    ut = jax.block_until_ready(tk.batch_to_t(flat))
    timeit("sswu+iso kernel (2S lanes)", lambda: _sswu_iso_t(ut, _interpret()))
    X, Y, Z = jax.block_until_ready(_sswu_iso_t(ut, _interpret()))
    F2 = tk.fp2_ops_t()
    from lighthouse_tpu.ops.points import pt_add
    Q = jax.block_until_ready(pt_add(
        F2, (X[..., :n], Y[..., :n], Z[..., :n]),
        (X[..., n:], Y[..., n:], Z[..., n:])))
    timeit("cofactor kernel (S lanes)", lambda: _cofactor_t(Q, _interpret()))
    Qc = jax.block_until_ready(_cofactor_t(Q, _interpret()))
    timeit("to_affine_g2 (hash out)", lambda: tc.to_affine_g2_t(Qc))
    # resident program (ISSUE 10 tentpole b): same math, one pallas_call
    us = jax.block_until_ready(jnp.moveaxis(u, 0, -1))
    timeit("map_resident (sswu..cof fused)",
           lambda: _map_to_g2_resident_t(us, _interpret()))
    timeit("hash full _map_to_g2_fused", lambda: _map_to_g2_fused(u))

    # ------------------------------------------- dedup sub-stage profile
    # The backend's htc_dedup/htc_map/htc_cofactor split (detail.stages)
    # under protocol-shaped duplication: S rows collapsing to S/dup
    # distinct messages. dup=1 is the worst case (no sharing); dup=64 is
    # the mainnet committee shape (ISSUE 10 tentpole c).
    from lighthouse_tpu import blsrt
    from lighthouse_tpu.jax_backend import JaxBackend
    from lighthouse_tpu.crypto.bls.curve import g2_infinity

    be = JaxBackend()
    inf2 = g2_infinity()
    for dup in (1, 64):
        dmsgs = [
            (i // dup).to_bytes(32, "big") for i in range(S)
        ]
        sub: dict[str, float] = {}
        blsrt.reset_input_caches()
        be._hash_message_bytes(dmsgs, S, inf2, stages=sub)  # warm/compile
        sub.clear()
        blsrt.reset_input_caches()
        t0 = time.perf_counter()
        out = be._hash_message_bytes(dmsgs, S, inf2, stages=sub)
        jax.block_until_ready(out)
        total = (time.perf_counter() - t0) * 1e3
        for stage in ("htc_dedup", "htc_map", "htc_cofactor"):
            record(f"{stage} (dup={dup})", sub.get(stage, 0.0) * 1e3)
        record(f"hash_message_bytes e2e (dup={dup})", total)

    # ------------------------------------------- counted-op model rows
    # Per-set instance counts of the pairing hot bodies (ISSUE 18): the
    # stage-level evidence that a knob moved the CARRY/MAC mix, not just
    # the headline ms. Abstract traces only — no compiles.
    op_model = counted_op_model()
    for cfg, counts in op_model["configs"].items():
        print(f"op_model[{cfg}]  " + "  ".join(
            f"{k}={v}" for k, v in sorted(counts.items())),
            file=sys.stderr if JSON_MODE else sys.stdout)

    # ------------------------------------------- pipelined overlap report
    # One end-to-end verify through the pipelined microbatch engine
    # (common/pipeline.py): per host stage, how many seconds ran hidden
    # behind device compute vs exposed in front of it. Skipped when the
    # pipeline is disabled or S is below LHTPU_PIPELINE_MIN_SETS.
    overlap = profile_pipeline_overlap(sets)

    if JSON_MODE:
        print(json.dumps({
            "metric": "bls_stage_profile",
            "stages_ms": STAGES_MS,
            "detail": {"S": S, "K": K,
                       "device": jax.devices()[0].platform,
                       "verdict_groups": G,
                       "overlap": overlap,
                       "stages": {"op_model": op_model}},
        }), flush=True)


def counted_op_model() -> dict:
    """Per-set counted-op model of the PAIRING hot path (ISSUE 18).

    Counts op INSTANCES (a stacked call-site = 1, matching the README
    roofline methodology) by abstractly tracing the Miller-loop bodies
    — doubling step + sparse f*line product, and the mixed-add body —
    with tkernel's trace-time counters under each knob configuration,
    then extrapolates with the static schedule (63 dbl + 5 dbl_add
    bodies for the BLS12-381 |x|). jax.eval_shape only: no XLA compile,
    so this costs trace time (~seconds), not compile minutes.

    Emitted metrics per config: schoolbook (VPU) MACs, serial / KS /
    MXU carry-chain instances, lazy w_norm passes, MXU MACs. Stages the
    knobs do not touch (ladders, sswu, host) are unchanged by
    construction and omitted — compare configs row-to-row."""
    from lighthouse_tpu.crypto.bls.constants import X as _BLS_X
    from lighthouse_tpu.ops import tkernel_pairing as tp

    n_dbl = abs(_BLS_X).bit_length() - 1
    n_add = bin(abs(_BLS_X)).count("1") - 1

    fp = jax.ShapeDtypeStruct((48, 1), jnp.int32)
    fp2 = jax.ShapeDtypeStruct((2, 48, 1), jnp.int32)
    f12 = jax.ShapeDtypeStruct((2, 3, 2, 48, 1), jnp.int32)

    # bodies are (re)defined per config: jax.eval_shape caches traces on
    # (function identity, avals), and a cache hit skips the Python trace
    # the counters live in — a stale closure would count zero
    def make_bodies():
        def dbl_body(f, X, Y, Z, xp, yp):
            if tk._lazy_enabled():
                T2, line_w = tp._dbl_step_lazy((X, Y, Z))
                return tp._mul_line_sparse_lazy(f, line_w, xp, yp), T2
            T2, line = tp._dbl_step((X, Y, Z))
            return tp._mul_line_sparse(f, line, xp, yp), T2

        def add_body(f, X, Y, Z, xq, yq, xp, yp):
            if tk._lazy_enabled():
                Ta, line_w = tp._add_step_lazy((X, Y, Z), (xq, yq))
                return tp._mul_line_sparse_lazy(f, line_w, xp, yp), Ta
            Ta, line = tp._add_step((X, Y, Z), (xq, yq))
            return tp._mul_line_sparse(f, line, xp, yp), Ta

        return dbl_body, add_body

    def trace_counts(fn, *argspecs):
        with tk.count_ops() as counts:
            jax.eval_shape(fn, *argspecs)
        return counts

    configs = {
        "strict": {},
        "lazy": {"LHTPU_LAZY_REDUCE": "1"},
        "mxu_carry": {"LHTPU_MXU_CARRY": "1"},
        "lazy+mxu_carry": {"LHTPU_LAZY_REDUCE": "1",
                           "LHTPU_MXU_CARRY": "1"},
    }
    knob_names = ("LHTPU_LAZY_REDUCE", "LHTPU_MXU_CARRY")
    saved = {k: os.environ.get(k) for k in knob_names}
    out: dict[str, dict[str, int]] = {}
    try:
        for name, env in configs.items():
            for k in knob_names:
                os.environ.pop(k, None)
            os.environ.update(env)
            dbl_body, add_body = make_bodies()
            dbl = trace_counts(dbl_body, f12, fp2, fp2, fp2, fp, fp)
            add = trace_counts(
                add_body, f12, fp2, fp2, fp2, fp2, fp2, fp, fp)
            total = {
                k: n_dbl * dbl.get(k, 0) + n_add * add.get(k, 0)
                for k in set(dbl) | set(add)
            }
            out[name] = {
                "schoolbook_macs": total.get("conv_mac", 0)
                + total.get("fold_vpu_mac", 0),
                "carry_serial": total.get("carry_serial", 0),
                "carry_ks": total.get("carry_ks", 0),
                "carry_mxu": total.get("carry_mxu", 0),
                "w_norm_passes": total.get("w_norm", 0),
                "mont_products": total.get("mont_product", 0),
                "mxu_macs": total.get("mxu_mac", 0),
            }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {
        "unit": "per pairing lane (63 dbl + 5 dbl_add bodies)",
        "configs": out,
    }


def profile_multichip(n_dev: int) -> None:
    """``--devices N`` (ISSUE 8): stage attribution of a SHARDED verify.

    Runs a warm end-to-end verify with the dispatch engine forced onto
    an N-way mesh and reports the host stages (pack / hash / scalars)
    that stay serial, the device dispatch stage that now runs with
    S/N sets per shard, and — separately measured via the engine's fold
    probe — the cross-chip fold round that is the sharding overhead,
    as a share of the device stage."""
    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet
    from lighthouse_tpu.parallel import engine

    out = sys.stderr if JSON_MODE else sys.stdout
    tpu = jax.devices()[0].platform == "tpu"
    S = int(os.environ.get("PROFILE_SETS", "2048" if tpu else "8"))
    print(f"device={jax.devices()[0].platform} devices={n_dev} S={S} "
          f"(multichip profile)", file=out)

    sks = [SecretKey.from_int(i + 7) for i in range(S)]
    msgs = [bytes([(i % 255) + 1]) * 32 for i in range(S)]
    sets = [
        SignatureSet.single_pubkey(sks[i].sign(msgs[i]),
                                   sks[i].public_key(), msgs[i])
        for i in range(S)
    ]

    saved = {
        k: os.environ.get(k)
        for k in ("LHTPU_DEVICES", "LHTPU_SHARDED_VERIFY", "LHTPU_PIPELINE")
    }
    os.environ["LHTPU_DEVICES"] = str(n_dev)
    os.environ["LHTPU_SHARDED_VERIFY"] = "1" if n_dev > 1 else "0"
    os.environ["LHTPU_PIPELINE"] = "0"
    try:
        be = jb.JaxBackend()
        assert be.verify_signature_sets(sets)   # compile / cache load
        t0 = time.perf_counter()
        assert be.verify_signature_sets(sets)   # steady state
        wall = time.perf_counter() - t0

        rep = jb.dispatch_stage_report()
        par = rep.get("parallel") or {}
        stages_ms = rep.get("stages_ms") or {}
        per_shard = par.get("sets_per_chip")
        for stage, ms in sorted(stages_ms.items()):
            suffix = (f"  ({per_shard} sets/shard x {n_dev})"
                      if stage == "dispatch" and n_dev > 1 else "")
            record(f"{stage}{suffix}", ms)
        record("e2e (warm)", wall * 1e3)

        fold_ms = engine.measure_fold_ms(n_dev) if n_dev > 1 else 0.0
        dispatch_ms = stages_ms.get("dispatch") or 0.0
        fold_share = (round(fold_ms / dispatch_ms, 4)
                      if dispatch_ms > 0 else 0.0)
        record("cross_chip_fold (probe)", fold_ms)
        print(f"multichip: path={rep.get('path')} "
              f"mesh={par.get('mesh')} pad_waste={par.get('pad_waste')} "
              f"fold_share_of_dispatch={fold_share}", file=out)

        if JSON_MODE:
            print(json.dumps({
                "metric": "bls_stage_profile_multichip",
                "stages_ms": STAGES_MS,
                "detail": {
                    "S": S,
                    "device": jax.devices()[0].platform,
                    "devices": n_dev,
                    "sets_per_shard": per_shard,
                    "fold_ms": fold_ms,
                    "fold_share": fold_share,
                    "path": rep.get("path"),
                    "parallel": par,
                },
            }), flush=True)
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val


def profile_pipeline_overlap(sets) -> dict:
    """Run one pipelined verify and report host-hidden vs host-exposed
    seconds per dispatch stage (None-shaped dict when the batch doesn't
    pipeline). Warm-path numbers: the first call pays compiles and cold
    caches, the second is the steady state the pipeline targets."""
    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.common import pipeline as pl

    out = sys.stderr if JSON_MODE else sys.stdout
    if not pl.should_pipeline(len(sets)):
        print(f"pipeline: skipped (enabled={pl.enabled()} "
              f"S={len(sets)} min_sets={pl.min_sets()})", file=out)
        return {"enabled": False}

    be = jb.JaxBackend()
    assert be.verify_signature_sets(sets)   # compiles + cold caches
    t0 = time.perf_counter()
    assert be.verify_signature_sets(sets)   # steady state
    wall = time.perf_counter() - t0
    pipe = jb.dispatch_stage_report().get("pipeline") or {}
    record("pipelined e2e (warm)", wall * 1e3)
    print(f"pipeline: chunks={pipe.get('chunks')} "
          f"chunk_size={pipe.get('chunk_size')} "
          f"overlap={pipe.get('overlap_s')}s "
          f"exposed={pipe.get('host_exposed_s')}s", file=out)
    for stage, d in sorted((pipe.get("stages") or {}).items()):
        print(f"  {stage:20s} hidden {d['hidden_s']*1e3:8.1f} ms   "
              f"exposed {d['exposed_s']*1e3:8.1f} ms", file=out)
    return pipe


if __name__ == "__main__":
    if DEVICES is not None:
        profile_multichip(DEVICES)
    else:
        main()
