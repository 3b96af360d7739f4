"""Fast-tier Mosaic lowering smoke (ADVICE r4 medium).

The CPU-only fast tier could not catch a TPU lowering regression: the
fused Pallas path is TPU-gated and its digits are bit-exact on XLA:CPU,
so the LHTPU_KS_CARRY=1 default that zeroed BENCH_r04 passed the whole
suite clean. ``jax.export`` with ``platforms=['tpu']`` runs the real
Pallas->Mosaic lowering pass on any host — but only for a kernel called
with ``interpret=False``: the public wrappers pick interpret mode off
the chip, and exporting them yields the interpret-mode discharge with no
kernel in it. So the inner jitted kernel is exported directly, and the
module must carry a ``tpu_custom_call``.

Mosaic's own compile (VMEM budget, tiling) is tests/test_chip_compile.py
and ``tools/lowering_smoke.py``; this test pins the cheapest kernel that
still exercises every carry primitive (add/sub/canonical/mont_mul ride
inside the G1 group law), under BOTH carry-path defaults and the
production MXU-fold configuration.
"""

import jax
import jax.export  # noqa: F401 — jax.export is lazy; attribute access
                   # alone raises AttributeError on this jax version
import jax.numpy as jnp
import pytest

from lighthouse_tpu.jax_backend import _rand_bits_array
from lighthouse_tpu.ops import tkernel_calls as tc
from lighthouse_tpu.ops.points import G1_GEN_DEV


@pytest.mark.parametrize("ks", ["0", "1"])
def test_scalar_mul_g1_lowers_for_tpu(monkeypatch, ks):
    # LHTPU_KS_CARRY is read at TRACE time and is not part of the jit
    # cache key: without clearing, the second ks value would silently
    # reuse the first value's cached jaxpr and the parametrization would
    # be vacuous (ADVICE r5 — verified: the pre-fix kernel passed ks=1
    # in-process but failed Mosaic lowering in a fresh one).
    jax.clear_caches()
    monkeypatch.setenv("LHTPU_KS_CARRY", ks)
    # Production TPU traces run with the MXU fold on; lower that
    # program, not the CPU conv fallback.
    monkeypatch.setenv("LHTPU_MXU_FOLD", "1")

    S = 128
    g1x = jnp.broadcast_to(jnp.asarray(G1_GEN_DEV[0])[:, None], (48, S))
    g1y = jnp.broadcast_to(jnp.asarray(G1_GEN_DEV[1])[:, None], (48, S))
    inf_row = jnp.zeros((1, S), jnp.int32)
    bits_t = jnp.transpose(jnp.asarray(_rand_bits_array(S)))

    exp = jax.export.export(
        jax.jit(lambda x, y, i, b: tc._scalar_mul_t(
            x, y, i, b, g2=False, interpret=False)),
        platforms=["tpu"],
    )(g1x, g1y, inf_row, bits_t)
    assert "tpu_custom_call" in exp.mlir_module()
