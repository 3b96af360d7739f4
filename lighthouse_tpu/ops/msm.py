"""Bucketed (windowed shared-bucket) multi-scalar multiplication for the
RLC signature accumulator — sum_i r_i * S_i over the whole set axis.

Round 2's fused verifier computed this with a per-set 64-step
double-and-add scan (ops/tkernel_calls.scalar_mul_g2_t: 64 doublings +
64 conditional additions on EVERY lane — ~430 ms at S=2048, the second
largest kernel). This module replaces it with the amortized scheme
blst's multi-aggregate check uses on CPU (reference:
crypto/bls/src/impls/blst.rs:114-116 cites "Fast verification of
multiple BLS signatures"), laid out TPU-first:

    r_i = sum_w 16^w * d_{i,w}           (16 windows of 4 bits)
    sum_i r_i S_i = sum_w 16^w * sum_{d=1..15} d * B[w, d]
    B[w, d] = sum_{i: d_{i,w} = d} S_i   (240 shared buckets)

The KEY TPU twist: the blinding scalars are generated on the HOST
(jax_backend._rand_bits_array — they must be CSPRNG, not traced), so the
host can precompute the entire bucket-accumulation schedule as a dense
[rounds, 240] index grid: round r adds the r-th point of every bucket's
list (one batched 240-lane mixed addition per round, no scatter, no
bucket conflicts — the conflict-freedom is BY CONSTRUCTION of the grid).
The device then runs:

  * accumulation kernel — grid over rounds; each step gathers nothing
    (points pre-gathered by XLA into [rounds, 240] order) and performs
    ONE masked pt_add_mixed into a VMEM-resident [240]-lane Jacobian
    accumulator. ~L rounds where L = max bucket load (~6 sigma above
    the binomial mean; the host falls back to the scalar-mul path in
    the astronomically rare overflow case).
  * reduce kernel — two stride-16 shift-add trees weight each bucket
    by its digit (sum-of-suffix-sums identity; 8 complete additions at
    full lane width), then a Horner combine over the 16 window lanes
    (4 doublings + 1 addition per window in one fori body).

Work: L*240 mixed adds (~50k point-op-lanes at S=2048) versus the
scan's 128*S (~262k) — and the accumulation phase has ZERO doublings.

Used for the G2 signature accumulator; the per-set [r_i]agg_pk_i lanes
cannot share buckets (each output is separate) and keep the scan.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import tkernel as tk
from .points import pt_add, pt_add_mixed, pt_double
from .tkernel import N_LIMBS

WINDOW_BITS = 4
N_WINDOWS = 64 // WINDOW_BITS          # 16 (RAND_BITS = 64)
N_DIGITS = (1 << WINDOW_BITS) - 1      # 15 nonzero digits
N_BUCKETS = N_WINDOWS * N_DIGITS       # 240
_LANES = 256                           # buckets padded to lane tiles


def max_rounds(n_sets: int) -> int:
    """Static bucket-depth bound for an n-set batch: binomial mean
    n/16 plus ~6 sigma, rounded up — P(overflow) ~ 1e-7 per batch;
    the caller checks the actual schedule and falls back."""
    mean = n_sets / (1 << WINDOW_BITS)
    bound = int(mean + 6.0 * math.sqrt(mean + 16) + 8)
    return -(-bound // 8) * 8


def build_schedule(r_u64: np.ndarray, L: int, skip=None):
    """Host scheduler: scalars -> (idx[L, 240] int32, valid[L, 240] bool).

    idx[r, b] is the set index whose point is added into bucket b at
    round r (0 + valid=False for exhausted slots). ``skip`` optionally
    marks set indices to leave out (padding lanes). Returns None when a
    bucket exceeds L (caller falls back to the scan path).

    Fully vectorized (argsort by bucket + per-bucket position via
    first-occurrence offsets): this runs on the dispatch critical path
    of every verify batch, so no per-element Python loops.
    """
    r = np.asarray(r_u64, np.uint64)
    shifts = (np.arange(N_WINDOWS, dtype=np.uint64) * np.uint64(WINDOW_BITS))
    digits = ((r[None, :] >> shifts[:, None]) & np.uint64(N_DIGITS)).astype(
        np.int64
    )  # [W, S]
    if skip is not None:
        digits[:, np.asarray(skip, bool)] = 0
    wi, si = np.nonzero(digits)
    # digit-major lane layout: lane = (digit-1)*16 + w. The reduce
    # kernel's shift-add trees assume stride-16 digit groups.
    b = (digits[wi, si] - 1) * N_WINDOWS + wi
    order = np.argsort(b, kind="stable")
    b_sorted = b[order]
    i_sorted = si[order]
    first = np.searchsorted(b_sorted, np.arange(N_BUCKETS), side="left")
    counts = (
        np.searchsorted(b_sorted, np.arange(N_BUCKETS), side="right") - first
    )
    if len(b_sorted) and counts.max() > L:
        return None
    pos = np.arange(len(b_sorted)) - first[b_sorted]
    idx = np.zeros((L, N_BUCKETS), np.int32)
    idx[pos, b_sorted] = i_sorted
    valid = np.arange(L)[:, None] < counts[None, :]
    return idx, valid


def build_schedule_sharded(r_u64: np.ndarray, L: int, n_dev: int, skip=None):
    """Per-shard schedules with LOCAL indices: [n_dev, L, 240] grids for
    an S axis split evenly over n_dev chips (each chip MSMs its local
    sets; partials fold over the mesh axis like the old tree sums)."""
    S = len(r_u64)
    assert S % n_dev == 0, "set axis must be padded to a device multiple"
    per = S // n_dev
    idxs, valids = [], []
    for c in range(n_dev):
        sl = slice(c * per, (c + 1) * per)
        out = build_schedule(
            r_u64[sl], L, None if skip is None else skip[sl]
        )
        if out is None:
            return None
        idxs.append(out[0])
        valids.append(out[1])
    return np.stack(idxs), np.stack(valids)


# ------------------------------------------------------------- kernels


@functools.partial(jax.jit, static_argnames=("interpret",))
def _accum_t(gx, gy, valid, interpret: bool):
    """gx/gy: [L, 2, 48, LANES] pre-gathered affine rounds (transposed
    layout, lanes = buckets); valid: [L, 1, LANES] int32. Returns the
    bucket Jacobians [3, 2, 48, LANES] via one masked mixed addition per
    sequential grid step into a VMEM-resident accumulator block."""
    L = gx.shape[0]
    RB = 8  # rounds per grid step (amortizes per-step grid overhead;
    #         max_rounds() guarantees L % 8 == 0)
    assert L % RB == 0, "schedule depth must be a multiple of 8"
    in_specs = [
        pl.BlockSpec((RB, 2, N_LIMBS, _LANES), lambda r: (r, 0, 0, 0)),
        pl.BlockSpec((RB, 2, N_LIMBS, _LANES), lambda r: (r, 0, 0, 0)),
        pl.BlockSpec((RB, 1, _LANES), lambda r: (r, 0, 0)),
        pl.BlockSpec((tk.N_CONSTS, N_LIMBS, 1), lambda r: (0, 0, 0)),
        pl.BlockSpec((tk.N_MONT_ROWS, N_LIMBS), lambda r: (0, 0)),
    ]
    out_spec = pl.BlockSpec((3, 2, N_LIMBS, _LANES), lambda r: (0, 0, 0, 0))

    def kernel(x_ref, y_ref, v_ref, c_ref, mont_ref, out_ref):
        with tk.bound_consts(c_ref[:], mont=mont_ref[:]):
            F = tk.fp2_ops_t()
            r = pl.program_id(0)

            @pl.when(r == 0)
            def _init():
                x0 = x_ref[0]
                one = jnp.broadcast_to(F.one, x0.shape)
                out_ref[0] = one
                out_ref[1] = one
                out_ref[2] = jnp.zeros_like(x0)

            def step(i, acc):
                q_inf = v_ref[i, 0, :] == 0
                return pt_add_mixed(F, acc, (x_ref[i], y_ref[i]), q_inf)

            acc = (out_ref[0], out_ref[1], out_ref[2])
            acc = jax.lax.fori_loop(0, RB, step, acc)
            out_ref[0], out_ref[1], out_ref[2] = acc

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((3, 2, N_LIMBS, _LANES), jnp.int32),
        grid=(L // RB,),
        in_specs=in_specs,
        out_specs=out_spec,
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(gx, gy, valid, jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP))


def _tree_kernel(b_ref, consts_ref, mont_ref, out_ref):
    """Weighted bucket reduction at full 256-lane width.

    Lanes are digit-major (lane = (digit-1)*16 + w, lanes >= 240
    infinity). Two stride-16 shift-add trees compute
        T[w] = sum_d d * B[d, w]      (at lanes 0..15)
    via the sum-of-suffix-sums identity. Mosaic handles lane-axis
    concat shifts; leading-batch tiny-lane layouts do NOT lower
    ('Not implemented: Sublane broadcast'), hence this formulation.
    """
    with tk.bound_consts(consts_ref[:], mont=mont_ref[:]):
        F = tk.fp2_ops_t()
        P = (b_ref[0], b_ref[1], b_ref[2])

        def shift_down(Q, sh):
            # lane i <- i+sh; vacated top lanes become infinity (Z=0)
            def mv(c):
                return jnp.concatenate(
                    [c[..., sh:], jnp.zeros_like(c[..., :sh])], axis=-1
                )
            return tuple(mv(c) for c in Q)

        for _ in range(2):
            for sh in (16, 32, 64, 128):
                P = pt_add(F, P, shift_down(P, sh))
        out_ref[0], out_ref[1], out_ref[2] = P


def _horner_kernel(t_ref, consts_ref, mont_ref, out_ref):
    """sum_w 16^w * T[w] -> lane 0.

    buf holds T ROTATED so lane 0 is the current window; per fori step:
    4 doublings + 1 masked addition + rotate-right-by-one (rotation,
    not shift: the next window must wrap back into lane 0).
    """
    with tk.bound_consts(consts_ref[:], mont=mont_ref[:]):
        F = tk.fp2_ops_t()
        T = (t_ref[0], t_ref[1], t_ref[2])
        lanes = T[0].shape[-1]

        def rot_left(Q, sh):
            def mv(c):
                return jnp.concatenate(
                    [c[..., sh:], c[..., :sh]], axis=-1
                )
            return tuple(mv(c) for c in Q)

        def rot_right1(Q):
            def mv(c):
                return jnp.concatenate(
                    [c[..., -1:], c[..., :-1]], axis=-1
                )
            return tuple(mv(c) for c in Q)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
        is0 = (lane == 0)[0]
        one = jnp.broadcast_to(F.one, T[0].shape)
        inf = (one, one, jnp.zeros_like(T[0]))

        def lane0_only(Q):
            return tuple(F.select(is0, c, i) for c, i in zip(Q, inf))

        acc = lane0_only(rot_left(T, N_WINDOWS - 1))     # w = 15
        buf = rot_left(T, N_WINDOWS - 2)                 # w = 14 at lane 0

        def horner_step(_, carry):
            acc, buf = carry
            for _ in range(WINDOW_BITS):
                acc = pt_double(F, acc)
            acc = pt_add(F, acc, lane0_only(buf))
            return (acc, rot_right1(buf))

        acc, _ = jax.lax.fori_loop(
            0, N_WINDOWS - 1, horner_step, (acc, buf)
        )
        out_ref[0], out_ref[1], out_ref[2] = acc


def _f3_call(kernel, operand, interpret: bool):
    in_specs = [
        pl.BlockSpec((3, 2, N_LIMBS, _LANES), lambda: (0, 0, 0, 0)),
        pl.BlockSpec((tk.N_CONSTS, N_LIMBS, 1), lambda: (0, 0, 0)),
        pl.BlockSpec((tk.N_MONT_ROWS, N_LIMBS), lambda: (0, 0)),
    ]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((3, 2, N_LIMBS, _LANES), jnp.int32),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((3, 2, N_LIMBS, _LANES), lambda: (0, 0, 0, 0)),
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(operand, jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _reduce_t(acc, interpret: bool):
    """acc: the accumulation kernel's [3, 2, 48, 256] bucket block ->
    [3, 2, 48, 256] with the MSM point in lane 0. Two kernels (tree,
    Horner) — as one program the live set overflowed the 16 MB scoped
    VMEM limit by 64K."""
    T = _f3_call(_tree_kernel, acc, interpret)
    return _f3_call(_horner_kernel, T, interpret)


def _interpret() -> bool:
    return not tk.on_tpu()


def msm_g2(sx, sy, idx, valid):
    """sum_i r_i * S_i from classic-layout affine signatures.

    sx/sy: [S, 2, 48] int32 Montgomery affine (infinity lanes must not
    appear in the schedule — the scheduler's ``skip``); idx/valid: the
    host schedule [L, 240]. Returns a single Jacobian point as
    transposed-layout tensors ([2,48], [2,48], [2,48] — trailing lane
    axis squeezed).
    """
    # XLA pre-gather into round-major bucket order, then to the
    # transposed kernel layout with buckets on lanes (padded to 256):
    # sx[idx] -> [L, 240, 2, 48]; kernel wants [L, 2, 48, LANES].
    gx = jnp.moveaxis(sx[idx], 1, -1)            # [L, 2, 48, 240]
    gy = jnp.moveaxis(sy[idx], 1, -1)
    pad = _LANES - N_BUCKETS
    gx = jnp.pad(gx, ((0, 0), (0, 0), (0, 0), (0, pad)))
    gy = jnp.pad(gy, ((0, 0), (0, 0), (0, 0), (0, pad)))
    v = jnp.pad(valid.astype(jnp.int32), ((0, 0), (0, pad)))[:, None, :]

    acc = _accum_t(gx, gy, v, _interpret())      # [3, 2, 48, 256]
    out = _reduce_t(acc, _interpret())           # MSM point in lane 0
    # classic-layout single point ([2,48] per coordinate)
    return tuple(out[i, ..., 0] for i in range(3))
