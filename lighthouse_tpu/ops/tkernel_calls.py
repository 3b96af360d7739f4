"""Fused Pallas kernels for the verifier's long sequential chains.

Each kernel runs one long chain (RLC scalar-mul, subgroup check, affine
normalization via Fermat inversion, Miller loop, final exponentiation)
as a SINGLE Pallas program per batch tile: loop iterations inside a
kernel cost ~μs, versus ~0.1-1ms per XLA-level op on this stack (the
profiling that motivated this lives in ops/tkernel.py's docstring).

Conventions shared by every kernel here:

* transposed layout (ops/tkernel.py): limb axis on sublanes, batch on
  lanes; tiles of TILE lanes; grid over batch tiles;
* infinity masks travel as int32 [1, T] rows (Mosaic wants ≥2-D);
* loop bit tables and the field-constant bundle are kernel inputs
  (Pallas forbids captured array constants) — bit tables as [n, 1]
  columns read with dynamic sublane indices, constants re-bound around
  the traced body via tkernel.bound_consts;
* ``interpret=True`` off-TPU so the CPU suite executes identical
  semantics.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..crypto.bls.constants import R as CURVE_ORDER
from . import tkernel as tk
from . import tkernel_pairing as tp
from .points import pt_add, pt_add_mixed, pt_double, pt_from_affine
from .tkernel import N_LIMBS

ORDER_BITS_NP = tk.bits_msb_first(CURVE_ORDER)
ORDER_NBITS = len(ORDER_BITS_NP)


def _interpret() -> bool:
    return not tk.on_tpu()


def _col(bits_np: np.ndarray) -> jnp.ndarray:
    return jnp.asarray(bits_np.reshape(-1, 1))


def _pad_lanes(a, t_pad: int):
    if a.shape[-1] == t_pad:
        return a
    pad = [(0, 0)] * (a.ndim - 1) + [(0, t_pad - a.shape[-1])]
    return jnp.pad(a, pad)


# Every kernel tiles its lanes in blocks of TILE. Mosaic's code size, and
# its compile time faster still, grow with the block width while the work
# per lane does not: wider blocks cost a cold chip run minutes of compile
# (CHANGES.md, PR 21).
TILE = 128


def _whole_tiles(t: int) -> int:
    return -(-t // TILE) * TILE


def _specs(shapes, tile):
    """BlockSpecs tiling the last axis; constant inputs pass tile=None."""
    out = []
    for nd, tiled in shapes:
        if tiled:
            block = (*nd, tile)
            out.append(
                pl.BlockSpec(block, lambda i, _n=len(block): (0,) * (_n - 1) + (i,))
            )
        else:
            out.append(pl.BlockSpec(nd, lambda i, _n=len(nd): (0,) * _n))
    return out


# ------------------------------------------------------------- scalar mul


def _scalar_mul_kernel(g2: bool):
    def kernel(x_ref, y_ref, inf_ref, bits_ref, consts_ref, mont_ref, out_ref):
        with tk.bound_consts(consts_ref[:], mont=mont_ref[:]):
            F = tk.fp2_ops_t() if g2 else tk.fp_ops_t()
            x, y = x_ref[:], y_ref[:]
            inf = inf_ref[0, :] != 0

            zero = jnp.zeros_like(x)
            one = jnp.broadcast_to(F.one, x.shape)
            acc0 = (one, one, zero)                 # Jacobian infinity

            def step(i, acc):
                acc = pt_double(F, acc)
                cand = pt_add_mixed(F, acc, (x, y), inf)
                take = bits_ref[i, :] == 1
                return tuple(
                    jnp.where(take, c, a) for c, a in zip(cand, acc)
                )

            acc = jax.lax.fori_loop(0, bits_ref.shape[0], step, acc0)
            out_ref[:] = jnp.stack(acc)

    return kernel


@functools.partial(jax.jit, static_argnames=("g2", "interpret"))
def _scalar_mul_t(x, y, inf, bits, *, g2: bool, interpret: bool):
    """[k]Q per lane. x/y: [(2,)48,T]; inf: [1,T] int32; bits [nbits,T].
    Returns Jacobian (X, Y, Z) stacked [3, (2,) 48, T]."""
    t = x.shape[-1]
    tile = TILE
    t_pad = -(-t // tile) * tile
    x, y, inf, bits = (_pad_lanes(v, t_pad) for v in (x, y, inf, bits))
    coord = (2, N_LIMBS) if g2 else (N_LIMBS,)
    in_specs = _specs(
        [(coord, True), (coord, True), ((1,), True),
         ((bits.shape[0],), True), ((tk.N_CONSTS, N_LIMBS, 1), False),
         ((tk.N_MONT_ROWS, N_LIMBS), False)],
        tile,
    )
    out_spec = _specs([((3, *coord), True)], tile)[0]
    out = pl.pallas_call(
        _scalar_mul_kernel(g2),
        out_shape=jax.ShapeDtypeStruct((3, *coord, t_pad), jnp.int32),
        grid=(t_pad // tile,),
        in_specs=in_specs,
        out_specs=out_spec,
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(x, y, inf, bits, jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP))
    return tuple(out[i, ..., :t] for i in range(3))


def scalar_mul_g1_t(x, y, inf, bits):
    return _scalar_mul_t(x, y, inf, bits, g2=False, interpret=_interpret())


def scalar_mul_g2_t(x, y, inf, bits):
    return _scalar_mul_t(x, y, inf, bits, g2=True, interpret=_interpret())


# ---------------------------------------------------------- subgroup check


def _subgroup_kernel(x_ref, y_ref, inf_ref, obits_ref, consts_ref, mont_ref, out_ref):
    with tk.bound_consts(consts_ref[:], mont=mont_ref[:]):
        F = tk.fp2_ops_t()
        x, y = x_ref[:], y_ref[:]
        inf = inf_ref[0, :] != 0
        P0 = pt_from_affine(F, x, y, inf)

        def step(i, acc):
            acc = pt_double(F, acc)
            cand = pt_add(F, acc, P0)
            return tuple(
                jnp.where(obits_ref[i, 0] == 1, c, a)
                for c, a in zip(cand, acc)
            )

        # leading order bit consumes P0 itself (pt_scalar_mul_const)
        acc = jax.lax.fori_loop(1, ORDER_NBITS, step, P0)
        out_ref[0, :] = F.is_zero(acc[2]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _subgroup_check_g2(x, y, inf, interpret: bool):
    t = x.shape[-1]
    tile = TILE
    t_pad = -(-t // tile) * tile
    x, y, inf = (_pad_lanes(v, t_pad) for v in (x, y, inf))
    in_specs = _specs(
        [((2, N_LIMBS), True), ((2, N_LIMBS), True), ((1,), True),
         ((ORDER_NBITS, 1), False), ((tk.N_CONSTS, N_LIMBS, 1), False),
         ((tk.N_MONT_ROWS, N_LIMBS), False)],
        tile,
    )
    out = pl.pallas_call(
        _subgroup_kernel,
        out_shape=jax.ShapeDtypeStruct((1, t_pad), jnp.int32),
        grid=(t_pad // tile,),
        in_specs=in_specs,
        out_specs=_specs([((1,), True)], tile)[0],
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(x, y, inf, _col(ORDER_BITS_NP), jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP))
    return out[0, :t] != 0


def subgroup_check_g2_t(x, y, inf):
    """[r]Q == infinity per lane (points.pt_subgroup_check semantics:
    infinity passes)."""
    return _subgroup_check_g2(x, y, inf, _interpret())


def _subgroup_fast_kernel(x_ref, y_ref, inf_ref, consts_ref, mont_ref, out_ref):
    """psi(Q) == [x_bls]Q (Bowe's criterion) with the x-chain laid out by
    |x|'s STATIC bit pattern: the leading set bit initializes the
    accumulator and the remaining 5 appear as mixed adds at their exact
    positions among 63 doublings, instead of a uniform 64-step
    compute-both-and-select ladder (tkernel_pairing.segmented_x_walk —
    the Miller loop's segmentation). Q is on-curve by deserialization;
    infinity passes (pt_subgroup_check semantics). lowmem: the grouped
    -conv windows put the 256-lane body 78K over the VMEM limit."""
    with tk.bound_consts(consts_ref[:], mont=mont_ref[:], lowmem=True):
        # stacked muln in the ladder when the MXU fold amortizes it
        # (tk.ladder_stack_enabled) — the walk is this kernel's cost.
        F = tk.fp2_ops_t(stack_muln=tk.ladder_stack_enabled())
        x, y = x_ref[:], y_ref[:]
        inf = inf_ref[0, :] != 0

        walk = tp.segmented_x_walk(
            dbl=lambda a: pt_double(F, a),
            dbl_add=lambda a: pt_add_mixed(
                F, pt_double(F, a), (x, y), inf
            ),
        )
        acc = walk(pt_from_affine(F, x, y, inf))  # init = leading bit
        # x_bls < 0: [x]Q = -[|x|]Q
        Xj, Yj, Zj = acc[0], F.neg(acc[1]), acc[2]

        # psi(Q) = (conj(x)*CX, conj(y)*CY), affine
        px, py = F.muln(
            (tk.fp2_conj_t(x), tk._c2("PSI_CX")),
            (tk.fp2_conj_t(y), tk._c2("PSI_CY")),
        )

        # affine-vs-Jacobian equality without inversion:
        # px == Xj/Zj^2, py == Yj/Zj^3
        z2 = F.sqr(Zj)
        z3 = F.mul(z2, Zj)
        lhsx, lhsy = F.muln((px, z2), (py, z3))
        eq = tk.fp2_eq_t(lhsx, Xj) & tk.fp2_eq_t(lhsy, Yj)
        # [x]Q infinite while Q isn't -> not in G2 (psi(Q) finite)
        eq = eq & ~F.is_zero(Zj)
        out_ref[0, :] = (eq | inf).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _subgroup_check_g2_fast(x, y, inf, interpret: bool):
    t = x.shape[-1]
    tile = TILE
    t_pad = -(-t // tile) * tile
    x, y, inf = (_pad_lanes(v, t_pad) for v in (x, y, inf))
    in_specs = _specs(
        [((2, N_LIMBS), True), ((2, N_LIMBS), True), ((1,), True),
         ((tk.N_CONSTS, N_LIMBS, 1), False),
         ((tk.N_MONT_ROWS, N_LIMBS), False)],
        tile,
    )
    out = pl.pallas_call(
        _subgroup_fast_kernel,
        out_shape=jax.ShapeDtypeStruct((1, t_pad), jnp.int32),
        grid=(t_pad // tile,),
        in_specs=in_specs,
        out_specs=_specs([((1,), True)], tile)[0],
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(x, y, inf, jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP))
    return out[0, :t] != 0


def subgroup_check_g2_fast_t(x, y, inf):
    """Fast psi-criterion G2 membership; equivalent to
    subgroup_check_g2_t (property-tested) at ~4x the speed."""
    return _subgroup_check_g2_fast(x, y, inf, _interpret())


# ------------------------------------------------------------- to-affine


def _to_affine_kernel(g2: bool):
    def kernel(pt_ref, pinv_ref, consts_ref, mont_ref, out_ref, inf_ref):
        with tk.bound_consts(consts_ref[:], mont=mont_ref[:], pinv_bits=pinv_ref):
            F = tk.fp2_ops_t() if g2 else tk.fp_ops_t()
            X, Y, Z = pt_ref[0], pt_ref[1], pt_ref[2]
            zi = F.inv(Z)
            zi2 = F.sqr(zi)
            # canonical outputs: affine coordinates are the boundary
            # where different op schedules must agree bitwise
            # (points.pt_to_affine contract)
            out_ref[0] = tk.canonical_t(F.mul(X, zi2))
            out_ref[1] = tk.canonical_t(F.mul(Y, F.mul(zi, zi2)))
            inf_ref[0, :] = F.is_zero(Z).astype(jnp.int32)

    return kernel


@functools.partial(jax.jit, static_argnames=("g2", "interpret"))
def _to_affine_t(P, *, g2: bool, interpret: bool):
    t = P[0].shape[-1]
    tile = TILE
    t_pad = -(-t // tile) * tile
    stacked = _pad_lanes(jnp.stack(P), t_pad)
    coord = (2, N_LIMBS) if g2 else (N_LIMBS,)
    in_specs = _specs(
        [((3, *coord), True), ((tk.PINV_NBITS, 1), False),
         ((tk.N_CONSTS, N_LIMBS, 1), False),
         ((tk.N_MONT_ROWS, N_LIMBS), False)],
        tile,
    )
    out_specs = _specs([((2, *coord), True), ((1,), True)], tile)
    out, inf = pl.pallas_call(
        _to_affine_kernel(g2),
        out_shape=(
            jax.ShapeDtypeStruct((2, *coord, t_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, t_pad), jnp.int32),
        ),
        grid=(t_pad // tile,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(stacked, _col(tk.PINV_BITS_NP), jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP))
    return out[0, ..., :t], out[1, ..., :t], inf[0, :t] != 0


def _to_affine_tiled(P, g2: bool):
    # Whole tiles in: lane counts within one tile count share one trace
    # of the jitted kernel across programs (a zero Z lane is infinity).
    t = P[0].shape[-1]
    x, y, inf = _to_affine_t(
        tuple(_pad_lanes(c, _whole_tiles(t)) for c in P), g2=g2,
        interpret=_interpret(),
    )
    return x[..., :t], y[..., :t], inf[:t]


def to_affine_g1_t(P):
    """Jacobian -> affine (x, y, inf-bool); infinity lanes zeroed
    (points.pt_to_affine semantics)."""
    return _to_affine_tiled(P, g2=False)


def to_affine_g2_t(P):
    return _to_affine_tiled(P, g2=True)


# ----------------------------------------------------------- miller loop


def _miller_kernel(xp_ref, yp_ref, pinf_ref, xq_ref, yq_ref, qinf_ref,
                   consts_ref, mont_ref, out_ref):
    with tk.bound_consts(consts_ref[:], mont=mont_ref[:], lowmem=True):
        f = tp.miller_loop_t(
            (xp_ref[:], yp_ref[:]),
            pinf_ref[0, :] != 0,
            (xq_ref[:], yq_ref[:]),
            qinf_ref[0, :] != 0,
        )
        out_ref[:] = f


@functools.partial(jax.jit, static_argnames=("interpret",))
def _miller_t(xp, yp, pinf, xq, yq, qinf, interpret: bool):
    t = xp.shape[-1]
    tile = TILE
    t_pad = -(-t // tile) * tile
    xp, yp, pinf, xq, yq, qinf = (
        _pad_lanes(v, t_pad) for v in (xp, yp, pinf, xq, yq, qinf)
    )
    # padding lanes: force q_inf so they produce Fp12 one
    if t_pad != t:
        lane = jnp.arange(t_pad) >= t
        qinf = jnp.maximum(qinf, lane[None, :].astype(jnp.int32))
    in_specs = _specs(
        [((N_LIMBS,), True), ((N_LIMBS,), True), ((1,), True),
         ((2, N_LIMBS), True), ((2, N_LIMBS), True), ((1,), True),
         ((tk.N_CONSTS, N_LIMBS, 1), False),
         ((tk.N_MONT_ROWS, N_LIMBS), False)],
        tile,
    )
    out = pl.pallas_call(
        _miller_kernel,
        out_shape=jax.ShapeDtypeStruct((2, 3, 2, N_LIMBS, t_pad), jnp.int32),
        grid=(t_pad // tile,),
        in_specs=in_specs,
        out_specs=_specs([((2, 3, 2, N_LIMBS), True)], tile)[0],
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(xp, yp, pinf, xq, yq, qinf, jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP))
    return out[..., :t]


def miller_loop_kernel_t(p_aff, p_inf, q_aff, q_inf):
    """Batched Miller loop as one kernel; masks are bool [T]. Lanes go
    in as whole tiles (padding lanes at infinity give Fp12 one), so the
    verify program's S+1 pairs and the triage program's S+G pairs share
    one trace of the jitted kernel."""
    t = p_inf.shape[-1]
    t_pad = _whole_tiles(t)

    def row(m):
        return _pad_lanes(m[None, :].astype(jnp.int32), t_pad).at[
            :, t:].set(1)

    f = _miller_t(
        _pad_lanes(p_aff[0], t_pad), _pad_lanes(p_aff[1], t_pad), row(p_inf),
        _pad_lanes(q_aff[0], t_pad), _pad_lanes(q_aff[1], t_pad), row(q_inf),
        _interpret(),
    )
    return f[..., :t]


# ------------------------------------------------------- final exponentiation


# The HHT chain is a pipeline of small kernels (easy part / x-power /
# combine variants), each with ≤3 live Fp12 values, all in lowmem mode
# (fp2-level stacking only). Separate kernels compile in parallel (one
# kernel holding the whole chain took over 3x the compile wall time on a
# described v5e), and the five x-powers share ONE call site inside an
# XLA loop, so the x-power kernel compiles once (CHANGES.md, PR 21).

_F12_SHAPE = (2, 3, 2, N_LIMBS)


def _easy_exp_kernel(f_ref, pinv_ref, consts_ref, mont_ref, out_ref):
    """f^(p^6-1) then ^(p^2+1) (pairing.py final_exponentiation easy)."""
    with tk.bound_consts(consts_ref[:], mont=mont_ref[:], pinv_bits=pinv_ref, lowmem=True):
        f = f_ref[:]
        g = tk.fp12_mul_t(tk.fp12_conj_t(f), tk.fp12_inv_t(f))
        out_ref[:] = tk.fp12_mul_t(tk.fp12_frobenius2_t(g), g)


def _pow_kernel(f_ref, xm1_ref, consts_ref, mont_ref, out_ref):
    """f^x, or f^(x-1) = f^x * conj(f) on lanes whose xm1 row is set."""
    with tk.bound_consts(consts_ref[:], mont=mont_ref[:], lowmem=True):
        f = f_ref[:]
        p = tp._cyc_pow_x_t(f)
        out_ref[:] = jnp.where(
            xm1_ref[0, :] != 0, tk.fp12_mul_t(p, tk.fp12_conj_t(f)), p
        )


@functools.cache
def _comb_kernel(mode: str):
    def kernel(u_ref, v_ref, consts_ref, mont_ref, out_ref):
        with tk.bound_consts(consts_ref[:], mont=mont_ref[:], lowmem=True):
            u, v = u_ref[:], v_ref[:]
            if mode == "b":        # u * frob(v)
                out = tk.fp12_mul_t(u, tk.fp12_frobenius_t(v))
            elif mode == "c":      # u * frob2(v) * conj(v)
                out = tk.fp12_mul_t(
                    tk.fp12_mul_t(u, tk.fp12_frobenius2_t(v)),
                    tk.fp12_conj_t(v),
                )
            else:                  # "final": u * v^2 * v
                out = tk.fp12_mul_t(
                    tk.fp12_mul_t(u, tk.fp12_sqr_t(v)), v
                )
            out_ref[:] = out

    return kernel


@functools.partial(jax.jit, static_argnames=("kernel", "pinv", "interpret"))
def _f12_call(kernel, operands, rows=(), *, pinv: bool, interpret: bool):
    """One Fp12 kernel over the lanes of ``operands`` (plus int32 [1, T]
    lane ``rows``). Jitted on the kernel: a repeated call reuses one
    trace instead of re-tracing the kernel body."""
    t = operands[0].shape[-1]
    tile = TILE
    t_pad = -(-t // tile) * tile
    operands = [_pad_lanes(o, t_pad) for o in (*operands, *rows)]
    specs = ([(_F12_SHAPE, True)] * (len(operands) - len(rows))
             + [((1,), True)] * len(rows))
    extra_specs = [((tk.N_CONSTS, N_LIMBS, 1), False),
                   ((tk.N_MONT_ROWS, N_LIMBS), False)]
    extras = [jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP)]
    if pinv:
        extra_specs.insert(0, ((tk.PINV_NBITS, 1), False))
        extras.insert(0, _col(tk.PINV_BITS_NP))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((*_F12_SHAPE, t_pad), jnp.int32),
        grid=(t_pad // tile,),
        in_specs=_specs(specs + extra_specs, tile),
        out_specs=_specs([(_F12_SHAPE, True)], tile)[0],
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(*operands, *extras)
    return out[..., :t]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _final_exp_t(f, interpret: bool):
    def call(kernel, *operands, rows=(), pinv=False):
        return _f12_call(kernel, operands, rows, pinv=pinv,
                         interpret=interpret)

    g = call(_easy_exp_kernel, f, pinv=True)
    lanes = jnp.ones((1, f.shape[-1]), jnp.int32)

    # Hard part (pairing.py final_exponentiation, HHT chain): step i
    # takes p = x^x (x^(x-1) for i < 2), then
    #   i = 0, 1: x <- p            (a = g^((x-1)^2) after i = 1)
    #   i = 2:    x <- p * frob(a)  (b)
    #   i = 3:    x <- p
    #   i = 4:    x <- p * frob2(b) * conj(b)
    def step(i, carry):
        x, a, b = carry
        p = call(_pow_kernel, x, rows=(lanes * (i < 2),))
        x = jax.lax.switch(
            jnp.where(i == 2, 1, jnp.where(i == 4, 2, 0)),
            (lambda: p,
             lambda: call(_comb_kernel("b"), p, a),
             lambda: call(_comb_kernel("c"), p, b)),
        )
        return x, jnp.where(i == 1, p, a), jnp.where(i == 2, x, b)

    c, _, _ = jax.lax.fori_loop(0, 5, step, (g, g, g))
    return call(_comb_kernel("final"), c, g)


def final_exp_kernel_t(f):
    # Whole tiles in, as for the Miller loop: the verify program's one
    # lane and the triage program's G lanes share one trace.
    t = f.shape[-1]
    return _final_exp_t(_pad_lanes(f, _whole_tiles(t)), _interpret())[..., :t]
