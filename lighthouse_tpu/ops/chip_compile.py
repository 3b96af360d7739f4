"""Compiles of the main-path kernels for a described, unattached TPU.

The TPU compiler is installed wherever jaxlib is, and compiles for a chip
described by ``jax.experimental.topologies`` without one attached. That
is the only way off the chip to run Mosaic itself: interpret mode (the
CPU suite) never lowers a kernel, and ``jax.export`` of a public wrapper
off-TPU exports the interpret-mode discharge, with no kernel in it.

Each case is a jitted kernel entry with ``interpret=False`` and
argument shapes at the production width (the whole fused verify program
is ``jax_backend.fused_program``). Off the chip the caller steers the
trace-time platform gate (``tkernel.on_tpu``) so the MXU fold and the
scoped-VMEM grant are those of the chip: the tests with
``monkeypatch``, ``tools/lowering_smoke.py`` with :func:`steer_tpu`.
"""

from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp

from . import tkernel as tk
from .limb import N_LIMBS

S_REAL = 4096  # the gossip batch of BASELINE config #4 (bench.py headline)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def kernel_cases(S: int = S_REAL) -> dict:
    """name -> (jitted fn, positional arg shapes, static kwargs).

    Lane counts are those each kernel sees on the fused path for an
    S-set batch: S+1 Miller pairs, one final-exponentiation lane (one
    128-lane tile), the MSM's 256 bucket lanes over max_rounds(S)
    rounds, S messages through the resident hash-to-G2 map."""
    from . import msm, tkernel_calls as tc, tkernel_htc as th

    L = msm.max_rounds(S)
    W = msm._LANES
    g1 = (_i32(N_LIMBS, S), _i32(N_LIMBS, S))
    g2 = (_i32(2, N_LIMBS, S), _i32(2, N_LIMBS, S))
    row = _i32(1, S)
    bits = _i32(64, S)
    off = {"interpret": False}
    return {
        "scalar_mul_g1": (tc._scalar_mul_t, (*g1, row, bits),
                          {"g2": False, **off}),
        "scalar_mul_g2": (tc._scalar_mul_t, (*g2, row, bits),
                          {"g2": True, **off}),
        "subgroup_g2_fast": (tc._subgroup_check_g2_fast, (*g2, row), off),
        "msm_accum": (msm._accum_t, (_i32(L, 2, N_LIMBS, W),
                                     _i32(L, 2, N_LIMBS, W),
                                     _i32(L, 1, W)), off),
        "map_to_g2_resident": (th._map_to_g2_resident_t,
                               (_i32(2, 2, N_LIMBS, S),), off),
        "miller": (tc._miller_t,
                   (_i32(N_LIMBS, S + 1), _i32(N_LIMBS, S + 1),
                    _i32(1, S + 1), _i32(2, N_LIMBS, S + 1),
                    _i32(2, N_LIMBS, S + 1), _i32(1, S + 1)), off),
        "final_exp": (tc._final_exp_t, (_i32(2, 3, 2, N_LIMBS, 1),), off),
    }


def place(args, sharding):
    """The same shapes, committed to ``sharding`` (a described device);
    static flags pass through."""
    return jax.tree_util.tree_map(
        lambda s: (jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
                   if isinstance(s, jax.ShapeDtypeStruct) else s),
        args,
    )


def compile_case(fn, args, static, sharding) -> dict:
    """Trace, lower and compile one case for ``sharding``'s chip.
    Raises what the chip's compiler raises. Returns the timings, whether
    a Mosaic kernel is in the program, and the memory analysis."""
    t0 = time.perf_counter()
    lowered = fn.lower(*place(args, sharding), **static)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    text = compiled.as_text()
    return {
        "lower_s": t1 - t0,
        "compile_s": t2 - t1,
        "tpu_custom_call": "tpu_custom_call" in text,
        "memory": str(compiled.memory_analysis()),
    }


@contextlib.contextmanager
def steer_tpu(knob_env: dict | None = None):
    """Trace as the chip would: the platform gate reads TPU, the given
    LHTPU_* knobs are set, and jit caches are dropped on entry and exit
    (knobs are read at trace time and are not part of the cache key)."""
    import os

    saved_gate = tk.on_tpu
    saved_env = {k: os.environ.get(k) for k in (knob_env or {})}
    jax.clear_caches()
    tk.on_tpu = lambda: True
    os.environ.update(knob_env or {})
    try:
        yield
    finally:
        tk.on_tpu = saved_gate
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        jax.clear_caches()


def describe_v5e():
    """(topology, one-chip sharding) of a described v5e 2x2 host."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return topo, SingleDeviceSharding(topo.devices[0])

