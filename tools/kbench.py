"""Micro-benchmark individual fused kernels on the current device.

Usage: python tools/kbench.py [--fresh] [S] [name ...]

Names: scalar_g1 scalar_g2 subgroup subgroup_full to_affine_g1
       to_affine_g2 miller sswu sswu_iso cofactor psi_subgroup
       map_resident mont_mul_dense fp2_mul line_eval final_exp

Each kernel is compiled (persistent cache), warmed, then timed over
REPS=5 with block_until_ready. Inputs are generator-point lanes — timing
is data-independent (constant-time chains).

``--fresh`` runs each requested row in its OWN subprocess: one cold
python → jax → kernel lifecycle per row, so a number can never ride
device state left by an earlier kernel. The parent stays off JAX until
every child is done (one process holds the chip at a time). Default
rows under --fresh are the ISSUE 10 hash-side trio (sswu_iso, cofactor, psi_subgroup) whose
MXU-ladder/resident wins must be confirmed per-kernel from cold."""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPS = int(os.environ.get("KBENCH_REPS", "5"))


#: default rows for --fresh: the hash-side kernels whose ISSUE 10 wins
#: are claimed per-kernel (cold process each, no shared device state) —
#: including map_resident, whose PR-10 claim previously had no cold row —
#: plus the carry-chain trio (mont_mul_dense, fp2_mul, line_eval) that
#: measures the LHTPU_LAZY_REDUCE / LHTPU_MXU_CARRY bar per-kernel.
FRESH_NAMES = (
    "sswu_iso", "cofactor", "psi_subgroup", "map_resident",
    "mont_mul_dense", "fp2_mul", "line_eval",
)


def run_fresh(S: int, names) -> int:
    """One subprocess per row: python -> jax init -> single kernel.

    The child is this same script with one name; its stdout rows are
    re-emitted under a ``fresh`` prefix so a sweep reads as one table.
    Returns the count of failed children (nonzero exit / no row)."""
    import subprocess

    failed = 0
    for name in names:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), str(S), name],
            capture_output=True, text=True,
        )
        rows = [
            ln for ln in proc.stdout.splitlines()
            if ln and not ln.startswith("device=")
        ]
        if proc.returncode != 0 or not rows:
            failed += 1
            tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
            print(f"fresh {name:22s} FAILED rc={proc.returncode} "
                  f"{' | '.join(tail)}")
        else:
            for ln in rows:
                print(f"fresh {ln}")
        sys.stdout.flush()
    return failed


def _fresh_main() -> int:
    argv = [a for a in sys.argv[1:] if a != "--fresh"]
    S = int(argv[0]) if argv else 2048
    names = argv[1:] or list(FRESH_NAMES)
    print(f"device=fresh-subprocess S={S} reps={REPS}")
    return 1 if run_fresh(S, names) else 0


# The --fresh parent never imports JAX: a process that has touched it
# holds the chip, and each child needs the chip in turn.
if __name__ == "__main__" and "--fresh" in sys.argv:
    sys.exit(_fresh_main())

import jax
import jax.numpy as jnp

from lighthouse_tpu.common.compile_cache import configure

configure()

from lighthouse_tpu.jax_backend import _rand_bits_array
from lighthouse_tpu.ops import tkernel as tk
from lighthouse_tpu.ops import tkernel_calls as tc
from lighthouse_tpu.ops.points import G1_GEN_DEV, G2_GEN_DEV


def timeit(label, fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(REPS):
        jax.block_until_ready(fn())
    dt = (time.perf_counter() - t0) / REPS * 1e3
    print(f"{label:28s} {dt:9.1f} ms   (first call {compile_s:.1f}s)")
    sys.stdout.flush()


def main():
    argv = sys.argv[1:]
    S = int(argv[0]) if argv else 2048
    names = argv[1:] or [
        "scalar_g1", "scalar_g2", "subgroup", "to_affine_g1",
        "to_affine_g2", "miller", "sswu", "cofactor", "final_exp",
    ]
    print(f"device={jax.devices()[0].platform} S={S} reps={REPS}")

    g1x = jnp.broadcast_to(jnp.asarray(G1_GEN_DEV[0])[:, None], (48, S))
    g1y = jnp.broadcast_to(jnp.asarray(G1_GEN_DEV[1])[:, None], (48, S))
    g2x = jnp.broadcast_to(jnp.asarray(G2_GEN_DEV[0])[..., None], (2, 48, S))
    g2y = jnp.broadcast_to(jnp.asarray(G2_GEN_DEV[1])[..., None], (2, 48, S))
    inf_row = jnp.zeros((1, S), jnp.int32)
    bits_t = jnp.transpose(jnp.asarray(_rand_bits_array(S)))
    jax.block_until_ready((g1x, g1y, g2x, g2y, bits_t))

    jac1 = (g1x, g1y, jnp.broadcast_to(tk._c("R"), (48, S)))
    jac2 = (g2x, g2y, jnp.broadcast_to(
        jnp.concatenate([tk._c("R")[None], jnp.zeros((1, 48, 1), jnp.int32)]),
        (2, 48, S)))

    for name in names:
        if name == "scalar_g1":
            timeit("scalar_mul_g1", lambda: tc.scalar_mul_g1_t(g1x, g1y, inf_row, bits_t))
        elif name == "scalar_g2":
            timeit("scalar_mul_g2", lambda: tc.scalar_mul_g2_t(g2x, g2y, inf_row, bits_t))
        elif name == "subgroup":
            timeit("subgroup_fast (psi)", lambda: tc.subgroup_check_g2_fast_t(g2x, g2y, inf_row))
        elif name == "subgroup_full":
            timeit("subgroup_full ([r]Q)", lambda: tc.subgroup_check_g2_t(g2x, g2y, inf_row))
        elif name == "to_affine_g1":
            timeit("to_affine_g1", lambda: tc.to_affine_g1_t(jac1))
        elif name == "to_affine_g2":
            timeit("to_affine_g2", lambda: tc.to_affine_g2_t(jac2))
        elif name == "miller":
            timeit("miller_loop", lambda: tc.miller_loop_kernel_t(
                (g1x, g1y), inf_row[0] != 0, (g2x, g2y), inf_row[0] != 0))
        elif name in ("sswu", "sswu_iso"):
            from lighthouse_tpu.ops.tkernel_htc import _interpret, _sswu_iso_t
            u = g2x  # any Fp2 lanes work as field input
            timeit("sswu+iso", lambda: _sswu_iso_t(u, _interpret()))
        elif name == "cofactor":
            from lighthouse_tpu.ops.tkernel_htc import _cofactor_t, _interpret
            timeit("cofactor", lambda: _cofactor_t(jac2, _interpret()))
        elif name == "psi_subgroup":
            # same kernel as "subgroup"; named row so the ISSUE 10
            # ladder-stacking win reads per-kernel in fresh sweeps
            timeit("psi_subgroup", lambda: tc.subgroup_check_g2_fast_t(
                g2x, g2y, inf_row))
        elif name == "map_resident":
            from lighthouse_tpu.ops.tkernel_htc import (
                _interpret,
                _map_to_g2_resident_t,
            )
            us = jnp.broadcast_to(
                jnp.asarray(G2_GEN_DEV[0])[None, ..., None], (2, 2, 48, S)
            )
            timeit("map_resident (sswu..cof)", lambda:
                   _map_to_g2_resident_t(us, _interpret()))
        elif name == "mont_mul_dense":
            # dependent chain so the carry path is on the critical path,
            # not hidden behind the conv's MXU throughput
            @jax.jit
            def _mm16(x, y):
                for _ in range(16):
                    x = tk.mont_mul_t(x, y)
                return x
            timeit("mont_mul_dense (x16)", lambda: _mm16(g1x, g1y))
        elif name == "fp2_mul":
            @jax.jit
            def _fp2x8(x, y):
                for _ in range(8):
                    x = tk.fp2_mul_t(x, y)
                return x
            timeit("fp2_mul (x8)", lambda: _fp2x8(g2x, g2y))
        elif name == "line_eval":
            # one Miller-loop body iteration: doubling step + sparse
            # f*line product, lazy/strict chosen by knob at trace time
            from lighthouse_tpu.ops import tkernel_pairing as tp

            @jax.jit
            def _line(f, X, Y, Z, xp, yp):
                if tk._lazy_enabled():
                    T2, line_w = tp._dbl_step_lazy((X, Y, Z))
                    return tp._mul_line_sparse_lazy(f, line_w, xp, yp)
                T2, line = tp._dbl_step((X, Y, Z))
                return tp._mul_line_sparse(f, line, xp, yp)

            f12 = tp.fp12_one_t(g1x)
            timeit("line_eval (dbl+sparse)", lambda: _line(
                f12, jac2[0], jac2[1], jac2[2], g1x, g1y))
        elif name == "final_exp":
            f = jnp.broadcast_to(
                jnp.zeros((2, 3, 2, 48, 1), jnp.int32).at[0, 0, 0].set(tk._c("R")),
                (2, 3, 2, 48, min(S, 128)),
            )
            timeit("final_exp", lambda: tc.final_exp_kernel_t(f))
        else:
            print(f"unknown kernel: {name}")


if __name__ == "__main__":
    main()
