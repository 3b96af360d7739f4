"""Decompose the device->e2e throughput gap (VERDICT r3 weak #6 / item 7).

The headline bench's device-only number pre-stages every operand; e2e
runs `verify_signature_sets` from SignatureSet objects. The drop between
them has three candidate sinks:

  1. host assembly  — Python/numpy work per batch: structural checks,
     pubkey/signature limb conversion (`g1_to_dev`/`g2_to_dev`),
     message dedup, CSPRNG scalars, bucketed-MSM `build_schedule`;
  2. device hashing — the SSWU+cofactor hash-to-G2 program for the
     batch's distinct messages (device-only pre-hashes; a real slot
     has ~64 distinct messages, this measures the bench's worst case
     where every set carries its own);
  3. dispatch       — per-call launch latency (not measured on the
     chip yet; hidden by pipelining in the async path).

This tool times (1) exactly as `_dispatch` runs it, component by
component, on any platform (host work is platform-independent), and —
on TPU — times (2) as the standalone `hash_to_g2_fused_dev` program.
The pipelined e2e rate then decomposes as
    1 / rate = max(host_per_batch, hash_dev + verify_dev) / S
which says which side to attack (reference analog: the worker-pool
sizing question in beacon_processor/mod.rs:1004-1070).

Usage: python tools/profile_host_share.py [S]   (default 1024)
Host-assembly timings are platform-independent: JAX_PLATFORMS=cpu runs
that part without a chip.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    S = int(sys.argv[1]) if len(sys.argv) > 1 else 1024

    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.common.compile_cache import configure

    configure()

    from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet
    from lighthouse_tpu.jax_backend import _rand_scalars
    from lighthouse_tpu.ops import msm as _msm
    from lighthouse_tpu.ops.points import g1_to_dev, g2_to_dev

    print(f"building {S} signed sets (one-time, not measured)...", flush=True)
    sks = [SecretKey.from_int(i + 101) for i in range(S)]
    msgs = [i.to_bytes(32, "big") for i in range(S)]
    sets = [
        SignatureSet.single_pubkey(sk.sign(m), sk.public_key(), m)
        for sk, m in zip(sks, msgs)
    ]

    def t(label: str, fn, reps: int = 3):
        fn()  # warm (allocations, caches)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        dt = (time.perf_counter() - t0) / reps * 1e3
        print(f"  {label:34s} {dt:9.2f} ms/batch", flush=True)
        return dt, out

    print(f"host assembly components at S={S}:", flush=True)
    total = 0.0

    dt, _ = t("structural checks", lambda: [
        bool(s.signing_keys) and not s.signature.is_infinity() for s in sets
    ])
    total += dt

    dt, _ = t("pubkeys g1_to_dev", lambda: g1_to_dev(
        [s.signing_keys[0].point for s in sets]
    ))
    total += dt

    dt, _ = t("signatures g2_to_dev", lambda: g2_to_dev(
        [s.signature.point for s in sets]
    ))
    total += dt

    def dedup():
        distinct, index = [], {}
        for s in sets:
            m = s.message
            if m not in index:
                index[m] = len(distinct)
                distinct.append(m)
        return distinct

    dt, distinct = t("message dedup", dedup)
    total += dt

    # expand_message_xmd is the host half of hash-to-G2 (hashlib SHA-256);
    # the SSWU/cofactor half is the device program timed below.
    from lighthouse_tpu.crypto.bls.constants import DST
    from lighthouse_tpu.crypto.bls.hash_to_curve import expand_message_xmd

    dt, _ = t("expand_message_xmd (host SHA)", lambda: [
        expand_message_xmd(m, DST, 256) for m in distinct
    ])
    total += dt

    dt, (r_u64, r_bits) = t("CSPRNG scalars", lambda: _rand_scalars(S))
    total += dt

    dt, sched = t("MSM build_schedule", lambda: _msm.build_schedule(
        r_u64, _msm.max_rounds(S)
    ))
    total += dt

    # Upload: numpy -> device transfer of the assembled operands.
    px, py, pinf = g1_to_dev([s.signing_keys[0].point for s in sets])
    sx, sy, sinf = g2_to_dev([s.signature.point for s in sets])

    def upload():
        args = [jnp.asarray(a) for a in (px, py, pinf, sx, sy, sinf,
                                         r_bits, sched[0], sched[1])]
        jax.block_until_ready(args)
        return args

    dt, _ = t("device upload (block)", upload)
    total += dt

    print(f"  {'TOTAL host per batch':34s} {total:9.2f} ms/batch", flush=True)
    print(f"  host-implied ceiling: {S / total * 1e3:,.0f} sets/s", flush=True)

    if jax.default_backend() == "tpu":
        from lighthouse_tpu.ops.tkernel_htc import hash_to_g2_fused_dev

        def hash_dev():
            out = hash_to_g2_fused_dev(distinct)
            jax.block_until_ready(out)
            return out

        dt, _ = t("device hash-to-G2 program", hash_dev)
        print(
            f"  (device hash at D={len(distinct)} distinct msgs; the "
            f"verify program's own time is bench.py's device-only line)",
            flush=True,
        )
    else:
        print("(not on TPU: device hash-to-G2 program not timed)", flush=True)


if __name__ == "__main__":
    main()
