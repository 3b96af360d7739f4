"""Pallas TPU kernel for the Montgomery multiply hot primitive.

This is the fused-kernel replacement announced in ops/limb.py: the XLA
path materializes the [*, 2304] outer product, the [*, 96] convolution
columns, and 48 scan steps as separate HLO ops with loop state bouncing
through HBM; here the whole CIOS pipeline — schoolbook convolution,
48-digit Montgomery fold, and final carry normalization — runs inside
one Pallas program per batch tile with every intermediate held in VMEM.

Layout strategy — **limbs on sublanes, batch on lanes**: operands are
fed transposed as [48, T] tiles. That makes every step of both loops a
statically-sliced full-width VPU op:

* convolution: ``t[i:i+48, :] += b * a[i, :]`` for i in 0..47 (the true
  2304-MAC schoolbook, unrolled with static sublane windows — no MXU
  detour through the 96×-redundant one-hot matmul the XLA path uses);
* Montgomery fold: read digit row i, derive the quotient digit m, add
  ``m * p`` into rows i..i+47, push the carry into row i+1;
* normalization: sequential carry walk over rows 48..95.

Everything is int32; column/row values stay < 2^23 (48·255² conv bound
plus fold contributions) so no mid-kernel carries are needed, matching
ops/limb.py's invariants (inputs [0, 2p), output [0, 2p), limbs
normalized). `m = (t_i · (-p⁻¹)) & 255` relies on int32 wraparound
preserving the low 8 bits, same as the XLA path.

Opt-in: ``limb.set_mont_mul_impl("pallas")`` (or LHTPU_PALLAS_MONT_MUL=1)
before building jitted programs; equivalence is property-tested against
the XLA path and the big-int oracle, and re-checked on the real chip by
bench.py's exactness gate when enabled there.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tkernel as tk
from .limb import LIMB_BITS, LIMB_MASK, N_LIMBS, NINV8, P, int_to_limbs

TILE_T = 512  # batch elements (lanes) per grid step

_ROWS = 2 * N_LIMBS  # 96 product rows

_P_COL = np.asarray(int_to_limbs(P)).reshape(N_LIMBS, 1)
_P0 = int(_P_COL[0, 0])


def _mont_mul_kernel(a_ref, b_ref, p_ref, out_ref, t_ref):
    p_col = p_ref[:]                                   # [48, 1]
    b_all = b_ref[:]                                   # [48, T]

    # schoolbook convolution into the 96 digit rows (static windows)
    t_ref[0:N_LIMBS, :] = b_all * a_ref[0, :][None, :]
    t_ref[N_LIMBS:_ROWS, :] = jnp.zeros_like(t_ref[N_LIMBS:_ROWS, :])
    for i in range(1, N_LIMBS):
        t_ref[i:i + N_LIMBS, :] += b_all * a_ref[i, :][None, :]

    # CIOS fold: one digit per step, division by R row-by-row
    for i in range(N_LIMBS):
        trow = t_ref[i, :]
        m = (trow * NINV8) & LIMB_MASK                 # int32 wrap keeps low 8
        t_ref[i:i + N_LIMBS, :] += p_col * m[None, :]
        t_ref[i + 1, :] += (trow + m * _P0) >> LIMB_BITS

    # carry-normalize rows 48..95 into the output tile
    carry = jnp.zeros_like(t_ref[0, :])
    for k in range(N_LIMBS):
        v = t_ref[N_LIMBS + k, :] + carry
        out_ref[k, :] = v & LIMB_MASK
        carry = v >> LIMB_BITS


def _mont_mul_kernel_mxu(a_ref, b_ref, p_ref, out_ref, t_ref):
    """LHTPU_MXU_CARRY variant: same conv + CIOS fold, but the final
    48-step carry row-walk becomes banded-Toeplitz regroup matmuls +
    a Kogge-Stone prefix (tk._carry_norm_mxu — consts-free, so it
    traces inside the kernel body without the bound_consts bundle)."""
    p_col = p_ref[:]
    b_all = b_ref[:]

    t_ref[0:N_LIMBS, :] = b_all * a_ref[0, :][None, :]
    t_ref[N_LIMBS:_ROWS, :] = jnp.zeros_like(t_ref[N_LIMBS:_ROWS, :])
    for i in range(1, N_LIMBS):
        t_ref[i:i + N_LIMBS, :] += b_all * a_ref[i, :][None, :]

    for i in range(N_LIMBS):
        trow = t_ref[i, :]
        m = (trow * NINV8) & LIMB_MASK
        t_ref[i:i + N_LIMBS, :] += p_col * m[None, :]
        t_ref[i + 1, :] += (trow + m * _P0) >> LIMB_BITS

    out, _ = tk._carry_norm_mxu(
        t_ref[N_LIMBS:_ROWS, :], bound=(1 << 23) + 255
    )
    out_ref[:] = out


@functools.partial(jax.jit, static_argnames=("interpret", "mxu_carry"))
def _mont_mul_flat(a, b, interpret: bool = False, mxu_carry: bool = False):
    """a, b: int32[M, 48] → int32[M, 48] (transposition handled here)."""
    m = a.shape[0]
    # small batches get a lane-width tile instead of padding to TILE_T
    tile = min(TILE_T, max(128, -(-m // 128) * 128))
    m_pad = -(-m // tile) * tile
    at = jnp.transpose(a)
    bt = jnp.transpose(b)
    if m_pad != m:
        pad = ((0, 0), (0, m_pad - m))
        at = jnp.pad(at, pad)
        bt = jnp.pad(bt, pad)

    spec_in = pl.BlockSpec((N_LIMBS, tile), lambda i: (0, i))
    out = pl.pallas_call(
        _mont_mul_kernel_mxu if mxu_carry else _mont_mul_kernel,
        out_shape=jax.ShapeDtypeStruct((N_LIMBS, m_pad), jnp.int32),
        grid=(m_pad // tile,),
        in_specs=[spec_in, spec_in,
                  pl.BlockSpec((N_LIMBS, 1), lambda i: (0, 0))],
        out_specs=spec_in,
        scratch_shapes=[pltpu.VMEM((_ROWS, tile), jnp.int32)],
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(at, bt, jnp.asarray(_P_COL))
    return jnp.transpose(out[:, :m] if m_pad != m else out)


def mont_mul_pallas(a, b):
    """Drop-in mont_mul: a*b*R^{-1} mod p, batched over leading axes,
    closed on [0, 2p). Interprets on non-TPU backends so the suite's
    CPU mesh exercises the same kernel semantics."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape).reshape(-1, N_LIMBS)
    b = jnp.broadcast_to(b, shape).reshape(-1, N_LIMBS)
    interpret = not tk.on_tpu()
    return _mont_mul_flat(
        a, b, interpret=interpret, mxu_carry=tk._mxu_carry_enabled()
    ).reshape(shape)
