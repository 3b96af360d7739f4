"""Optimal-ate pairing on the transposed layout (ops/tkernel.py) — the
arithmetic bodies of the fused Miller/final-exp Pallas kernels.

Mirrors ops/pairing.py step-for-step (same Jacobian division-free line
evaluation, same scaling factors annihilated by the final exponentiation,
same HHT hard-part chain) with the limb axis on sublanes and batch on
lanes. Loop bit tables are passed in by the caller (jnp arrays under XLA,
SMEM refs inside Pallas kernels — see tkernel.pow_bits_t for why).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..crypto.bls.constants import X
from . import tkernel as tk
from .points import pt_from_affine
from .tkernel import (
    add_t,
    fp2_double_t,
    fp2_mul_fp_t,
    fp2_mul_t,
    fp2_neg_t,
    fp2_sqr_t,
    fp2_sub_t,
    fp2_triple_t,
    fp12_conj_t,
    fp12_mul_t,
    fp12_one_t,
    fp12_sqr_t,
)

_X_ABS = -X
# Miller bits: below the leading bit, MSB first (pairing.py _X_BITS).
MILLER_BITS_NP = np.asarray([int(b) for b in bin(_X_ABS)[3:]], np.int32)
MILLER_NBITS = len(MILLER_BITS_NP)


def _stk(xs, axis):
    return jnp.stack(xs, axis=axis)


def _muln2(*pairs):
    """Independent Fp2 products at one dependency level, looped.

    Stacking these into one Karatsuba call measured SLOWER on v5e (the
    transposed Montgomery engine is bandwidth-bound at fp2 width —
    points.FieldOps.muln note); the dependency-level grouping is kept
    because it documents the schedule and is what a cheaper-wide-rows
    engine would stack. Object identity marks squarings (pairs pass
    (v, v)), keeping the dedicated 2-row sqr formula in play."""
    return tuple(
        fp2_sqr_t(a) if a is b else fp2_mul_t(a, b) for a, b in pairs
    )


def _embed_line(A, B, C, xp, yp):
    """Sparse line -> dense Fp12 (pairing.py _embed_line, transposed)."""
    z = jnp.zeros(jnp.broadcast_shapes(A.shape, B.shape), jnp.int32)
    c0 = _stk([A, fp2_mul_fp_t(B, xp), z], -4)
    c1 = _stk([z, fp2_mul_fp_t(C, yp), z], -4)
    return _stk([c0, c1], -5)


def _f6c(a, i):
    return a[..., i, :, :, :]


def _mul_line_sparse(f, line, xp, yp):
    """f * line with the line kept sparse: the embedded element has only
    slots (c0.c0, c0.c1, c1.c1) = (A, B*xp, C*yp) non-zero, so the
    Karatsuba fp12 product needs 13 fp2 muls instead of the dense 18 —
    and skips all the multiply-by-zero Montgomery work the dense embed
    pays (blst calls this mul_by_xy00z0; VERDICT r1 item 4).

    All 13 Fp2 products are mutually independent once (B·xp, C·yp) are
    known — laid out flat at that one dependency level (_muln2), with
    the two line-scalings as one stacked fp-width multiplication."""
    A, B, C = line
    bc = fp2_mul_fp_t(jnp.stack([B, C]), jnp.stack([xp, yp]))
    bxp, cyp = bc[0], bc[1]

    f0, f1 = f[..., 0, :, :, :, :], f[..., 1, :, :, :, :]
    f00, f01c, f02 = _f6c(f0, 0), _f6c(f0, 1), _f6c(f0, 2)
    g0, g1, g2 = _f6c(f1, 0), _f6c(f1, 1), _f6c(f1, 2)
    fs = add_t(f0, f1)
    s0, s1, s2 = _f6c(fs, 0), _f6c(fs, 1), _f6c(fs, 2)
    Bc = add_t(bxp, cyp)

    (m0, m1, mx, mu, mv,
     w2, w0, w1,
     n0, n1, nx, nu, nv) = _muln2(
        (f00, A), (f01c, bxp), (add_t(f00, f01c), add_t(A, bxp)),
        (f02, bxp), (f02, A),
        (g2, cyp), (g0, cyp), (g1, cyp),
        (s0, A), (s1, Bc), (add_t(s0, s1), add_t(A, Bc)),
        (s2, Bc), (s2, A),
    )
    # t0 = f0 * (A + bxp v)      (_mul_by_01 recombination)
    t0 = _stk([add_t(m0, tk.fp2_mul_by_xi_t(mu)),
               fp2_sub_t(fp2_sub_t(mx, m0), m1),
               add_t(m1, mv)], -4)
    # t1 = f1 * (cyp v)          (_mul_by_1 recombination)
    t1 = _stk([tk.fp2_mul_by_xi_t(w2), w0, w1], -4)
    # (f0+f1) * (A + (bxp+cyp) v)
    ts = _stk([add_t(n0, tk.fp2_mul_by_xi_t(nu)),
               fp2_sub_t(fp2_sub_t(nx, n0), n1),
               add_t(n1, nv)], -4)
    c0 = add_t(t0, tk.fp6_mul_by_v_t(t1))
    c1 = fp2_sub_t(fp2_sub_t(ts, t0), t1)
    return _stk([c0, c1], -5)


# ------------------------------------------------- lazy line functions
# LHTPU_LAZY_REDUCE variants (ISSUE 18 tentpole a): the whole line
# evaluation — products, doublings, the sparse f*line recombination —
# rides tkernel's redundant-limb accumulators; adds/subs/mul-by-xi are
# carry-free digit arithmetic, values reused across several products
# re-strictify in ONE grouped stacked pass (w_slim_many), and the fp12
# result normalizes ONCE per line function (w_norm over the full
# coefficient stack) instead of once per scalar op. Verdict parity with
# the strict path is mod-p exact (canonical_t-level; see the tkernel
# lazy-section comment for why raw [0, 2p) representatives may differ).


def _muln2_w(*pairs):
    """_muln2 on wide accumulators; object identity marks squarings."""
    return tuple(
        tk.w2_sqr(a) if a is b else tk.w2_mul(a, b) for a, b in pairs
    )


def _dbl_step_lazy(T):
    """_dbl_step on wide accumulators. Returns strict (loop-carried)
    point digits and the WIDE line triple for the sparse product."""
    Xc, Yc, Zc = (tk.w_strict(c) for c in T)
    A_, B_, Zh, Z_sq = _muln2_w((Xc, Xc), (Yc, Yc), (Yc, Zc), (Zc, Zc))
    A_, B_, Zh, Z_sq = tk.w_slim_many(A_, B_, Zh, Z_sq)
    XB = tk.w_add(Xc, B_)
    C_, S_ = _muln2_w((B_, B_), (XB, XB))
    C_, = tk.w_slim_many(C_)
    D_, = tk.w_slim_many(
        tk.w_double(tk.w_sub(tk.w_sub(S_, A_), C_))
    )
    E_, = tk.w_slim_many(tk.w_add(tk.w_double(A_), A_))
    F_, EX, EZ = _muln2_w((E_, E_), (E_, Xc), (E_, Z_sq))
    X3, Z3 = tk.w_slim_many(
        tk.w_sub(F_, tk.w_double(D_)), tk.w_double(Zh)
    )
    Y3a, lC = _muln2_w((E_, tk.w_sub(D_, X3)), (Z3, Z_sq))
    Y3 = tk.w_sub(
        Y3a, tk.w_double(tk.w_double(tk.w_double(C_)))
    )
    lA = tk.w_sub(EX, tk.w_double(B_))
    lB = tk.w_neg(EZ)
    return (tk.w_out(X3), tk.w_out(Y3), tk.w_out(Z3)), (lA, lB, lC)


def _add_step_lazy(T, Qaff):
    """_add_step on wide accumulators; same contract as
    :func:`_dbl_step_lazy`."""
    X1, Y1, Z1 = (tk.w_strict(c) for c in T)
    xq, yq = (tk.w_strict(c) for c in Qaff)
    Z1Z1, = tk.w_slim_many(tk.w2_sqr(Z1))
    U2, Tz = _muln2_w((xq, Z1Z1), (Z1, Z1Z1))
    S2 = tk.w2_mul(yq, Tz)
    H, r = tk.w_slim_many(
        tk.w_sub(U2, X1), tk.w_double(tk.w_sub(S2, Y1))
    )
    H2 = tk.w_double(H)
    Z1H = tk.w_add(Z1, H)
    I, HH, ZS, rr = _muln2_w((H2, H2), (H, H), (Z1H, Z1H), (r, r))
    I, = tk.w_slim_many(I)
    J, V = _muln2_w((H, I), (X1, I))
    X3, Z3 = tk.w_slim_many(
        tk.w_sub(tk.w_sub(rr, J), tk.w_double(V)),
        tk.w_sub(tk.w_sub(ZS, Z1Z1), HH),
    )
    Y3a, Y3b, lA1, lA2 = _muln2_w(
        (r, tk.w_sub(V, X3)), (Y1, J), (r, xq), (Z3, yq)
    )
    Y3 = tk.w_sub(Y3a, tk.w_double(Y3b))
    lA = tk.w_sub(lA1, lA2)
    lB = tk.w_neg(r)
    lC = Z3
    return (tk.w_out(X3), tk.w_out(Y3), tk.w_out(Z3)), (lA, lB, lC)


def _mul_line_sparse_lazy(f, line_w, xp, yp):
    """_mul_line_sparse with a WIDE line and lazy recombination; the
    fp12 result normalizes once, over the full coefficient stack."""
    A, B, C = tk.w_slim_many(*line_w)
    bc = tk.w_mont_mul(
        tk._w_stack([B, C], 0),
        tk.w_strict(jnp.stack([xp, yp])[..., None, :, :]),
    )
    bxp, cyp = tk.w_slim_many(
        tk._w_part(bc, 0, 0), tk._w_part(bc, 1, 0)
    )

    f0, f1 = f[..., 0, :, :, :, :], f[..., 1, :, :, :, :]
    f0w, f1w = tk.w_strict(f0), tk.w_strict(f1)
    f00, f01c, f02 = (tk._w_part(f0w, i, -4) for i in range(3))
    g0, g1, g2 = (tk._w_part(f1w, i, -4) for i in range(3))
    fs = tk.w_add(f0w, f1w)
    s0, s1, s2 = (tk._w_part(fs, i, -4) for i in range(3))
    Bc = tk.w_add(bxp, cyp)

    (m0, m1, mx, mu, mv,
     w2, w0, w1,
     n0, n1, nx, nu, nv) = _muln2_w(
        (f00, A), (f01c, bxp),
        (tk.w_add(f00, f01c), tk.w_add(A, bxp)),
        (f02, bxp), (f02, A),
        (g2, cyp), (g0, cyp), (g1, cyp),
        (s0, A), (s1, Bc),
        (tk.w_add(s0, s1), tk.w_add(A, Bc)),
        (s2, Bc), (s2, A),
    )
    t0 = tk._w_stack([
        tk.w_add(m0, tk.w2_mul_by_xi(mu)),
        tk.w_sub(tk.w_sub(mx, m0), m1),
        tk.w_add(m1, mv),
    ], -4)
    t1 = tk._w_stack([tk.w2_mul_by_xi(w2), w0, w1], -4)
    ts = tk._w_stack([
        tk.w_add(n0, tk.w2_mul_by_xi(nu)),
        tk.w_sub(tk.w_sub(nx, n0), n1),
        tk.w_add(n1, nv),
    ], -4)
    c0 = tk.w_add(t0, tk.w6_mul_by_v(t1))
    c1 = tk.w_sub(tk.w_sub(ts, t0), t1)
    return tk.w_norm(tk._w_stack([c0, c1], -5))


def _dbl_step(T):
    """Double T + line through T scaled by 2YZ^3 (pairing.py _dbl_step).

    4 dependency levels of Fp2 products (_muln2):
    {X², Y², Y·Z, Z²} → {B², (X+B)²} → {E², E·X, E·Z²} → {E·(D-X3), Z3·Z²}."""
    Xc, Yc, Zc = T
    A_, B_, Zh, Z_sq = _muln2((Xc, Xc), (Yc, Yc), (Yc, Zc), (Zc, Zc))
    XB = add_t(Xc, B_)
    C_, S_ = _muln2((B_, B_), (XB, XB))
    D_ = fp2_double_t(fp2_sub_t(fp2_sub_t(S_, A_), C_))
    E_ = fp2_triple_t(A_)
    F_, EX, EZ = _muln2((E_, E_), (E_, Xc), (E_, Z_sq))
    X3 = fp2_sub_t(F_, fp2_double_t(D_))
    Z3 = fp2_double_t(Zh)
    Y3a, lC = _muln2((E_, fp2_sub_t(D_, X3)), (Z3, Z_sq))
    Y3 = fp2_sub_t(Y3a, fp2_double_t(fp2_double_t(fp2_double_t(C_))))
    lA = fp2_sub_t(EX, fp2_double_t(B_))
    lB = fp2_neg_t(EZ)
    return (X3, Y3, Z3), (lA, lB, lC)


def _add_step(T, Qaff):
    """T + Q (Q affine) + line scaled by 2ZH (pairing.py _add_step).

    6 dependency levels of Fp2 products (_muln2)."""
    X1, Y1, Z1 = T
    xq, yq = Qaff
    Z1Z1 = fp2_sqr_t(Z1)
    U2, Tz = _muln2((xq, Z1Z1), (Z1, Z1Z1))
    S2 = fp2_mul_t(yq, Tz)
    H = fp2_sub_t(U2, X1)
    r = fp2_double_t(fp2_sub_t(S2, Y1))
    H2 = fp2_double_t(H)
    Z1H = add_t(Z1, H)
    I, HH, ZS, rr = _muln2((H2, H2), (H, H), (Z1H, Z1H), (r, r))
    J, V = _muln2((H, I), (X1, I))
    X3 = fp2_sub_t(fp2_sub_t(rr, J), fp2_double_t(V))
    Z3 = fp2_sub_t(fp2_sub_t(ZS, Z1Z1), HH)
    Y3a, Y3b, lA1, lA2 = _muln2(
        (r, fp2_sub_t(V, X3)), (Y1, J), (r, xq), (Z3, yq)
    )
    Y3 = fp2_sub_t(Y3a, fp2_double_t(Y3b))
    lA = fp2_sub_t(lA1, lA2)
    lB = fp2_neg_t(r)
    lC = Z3
    return (X3, Y3, Z3), (lA, lB, lC)


# Static segmentation of the Miller bit string: |x| has Hamming weight 6,
# so only 5 of the 63 iterations take the add leg. The uniform
# fori_loop-with-bit-table formulation paid the add step AND its dense
# line multiplication on EVERY iteration (then discarded it by select) —
# nearly half the kernel's work. The bits are compile-time constants, so
# the loop is laid out as dbl-only fori runs with the 5 dbl+add steps
# inlined at their exact positions.
def _miller_segments():
    segs = []  # (n_dbl_only_before, ) per add position, then tail count
    run = 0
    for b in MILLER_BITS_NP:
        if b == 1:
            segs.append(run)
            run = 0
        else:
            run += 1
    return segs, run


# Public segment layout: runs of 0-bits before each of the 5 below-leading
# set bits of |x|, plus the trailing-zero tail. Shared by the Miller loop
# and every [|x|]-style chain (subgroup psi-check, cofactor clearing).
X_ADD_RUNS, X_TAIL = _miller_segments()


def _x_run_len(seg):
    """Doubling-only steps before add ``seg`` (a traced loop index) — a
    select chain over the static X_ADD_RUNS, since kernels may not
    capture array constants."""
    n = jnp.int32(0)
    for i, run in enumerate(X_ADD_RUNS):
        n = jnp.where(seg == i, jnp.int32(run), n)
    return n


def segmented_x_walk(dbl, dbl_add):
    """Drive a double-and-add over |x|'s STATIC bit layout: callbacks get
    (acc) for a doubling-only step and (acc) for a dbl+add step. The
    caller provides the initial acc (the leading bit's value). Used by
    miller_loop_t and the subgroup kernel so the segment bookkeeping
    lives in exactly one place.

    The five segments run as ONE rolled loop (an inner doubling loop of
    per-segment length, then the dbl+add step), so each callback is
    traced once: the same operations in the same order as inlining the
    segments, at about a third of the kernel code. Mosaic's compile time
    grows faster than the code does — the inlined form took minutes per
    kernel on a described v5e (CHANGES.md, PR 21)."""

    def walk(acc):
        def segment(seg, a):
            a = jax.lax.fori_loop(0, _x_run_len(seg), lambda _i, x: dbl(x), a)
            return dbl_add(a)

        acc = jax.lax.fori_loop(0, len(X_ADD_RUNS), segment, acc)
        return jax.lax.fori_loop(0, X_TAIL, lambda _i, x: dbl(x), acc)

    return walk


def miller_loop_t(p_aff, p_inf, q_aff, q_inf, bit_src=None):
    """Batched Miller loop (pairing.py miller_loop, transposed).

    p_aff: (xp, yp) [.., 48, T]; q_aff: (xq, yq) [.., 2, 48, T]; inf
    masks [T]. The bit schedule is static (see _miller_segments);
    ``bit_src`` is accepted for signature compatibility and ignored.
    Line products are sparse (_mul_line_sparse)."""
    xp, yp = p_aff
    F2 = tk.fp2_ops_t()
    T0 = pt_from_affine(F2, q_aff[0], q_aff[1], q_inf)
    f0 = fp12_one_t(xp)

    lazy = tk._lazy_enabled()  # trace-time; default OFF keeps the jaxpr

    def dbl_only(carry):
        f, T = carry
        f = fp12_sqr_t(f)
        if lazy:
            T2, line_w = _dbl_step_lazy(T)
            return (_mul_line_sparse_lazy(f, line_w, xp, yp), T2)
        T2, line = _dbl_step(T)
        f = _mul_line_sparse(f, line, xp, yp)
        return (f, T2)

    def dbl_add(carry):
        f, T = dbl_only(carry)
        if lazy:
            Ta, line_w = _add_step_lazy(T, q_aff)
            return (_mul_line_sparse_lazy(f, line_w, xp, yp), Ta)
        Ta, line_a = _add_step(T, q_aff)
        return (_mul_line_sparse(f, line_a, xp, yp), Ta)

    walk = segmented_x_walk(dbl=dbl_only, dbl_add=dbl_add)
    f, _ = walk((f0, T0))
    f = fp12_conj_t(f)  # x < 0
    trivial = p_inf | q_inf
    return jnp.where(trivial, fp12_one_t(xp), f)


def _cyc_pow_x_t(f):
    """f^x (x negative BLS parameter), cyclotomic (pairing._cyc_pow_x).

    Laid out by |x|'s static bit pattern (segmented_x_walk): 63 squarings
    with the 5 below-leading multiplications inlined at their exact
    positions, instead of a uniform 64-step square-multiply-select ladder
    that computes and discards a dense fp12_mul on the 58 zero bits."""
    walk = segmented_x_walk(
        dbl=fp12_sqr_t,
        dbl_add=lambda a: fp12_mul_t(fp12_sqr_t(a), f),
    )
    return fp12_conj_t(walk(f))


# The full HHT final-exponentiation chain lives as a split-kernel
# pipeline in tkernel_calls._final_exp_t; _cyc_pow_x_t above is its
# x-power building block.
