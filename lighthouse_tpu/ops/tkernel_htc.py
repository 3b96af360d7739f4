"""Fused Pallas kernels for batched hash-to-G2 — the production TPU path.

Mirror of ops/htc.py (division-free SSWU + 3-isogeny + Budroni-Pintore
cofactor clearing) on the transposed layout, following the
pairing.py/tkernel_pairing.py twin-module precedent. Two bodies carry the
sequential depth:

  * sswu+iso body — one ~757-step sqrt_ratio exponentiation chain per
    lane plus straight-line SSWU/isogeny glue; emits Jacobian points on E2.
  * cofactor body — Budroni-Pintore h_eff as two segmented |x|-walks
    (t = [|x|]Q, t2 = [|x|]t; see _cofactor_body) plus ψ/ψ² glue,
    fused into one program: ~127 doublings + 15 complete additions.

The production path (LHTPU_HTC_RESIDENT, default on) runs BOTH bodies —
plus the Q0+Q1 point addition between them — as ONE resident Pallas
program per batch tile (_map_to_g2_kernel): both u-halves ride a leading
stack axis through the sswu body, so the intermediate Jacobian limb
grids never round-trip HBM between map and cofactor (two pallas_call
boundaries ≈ 2×3×2×48×T int32 store+load per tile, plus two grid
launches). The pre-r5 two-kernel chain (_sswu_iso_t → XLA pt_add →
_cofactor_t) is kept as the A/B + degradation path. Final affine
normalization stays in tkernel_calls.to_affine_g2_t either way (it owns
the Fermat-inversion bit table).

Parity: tests/test_htc.py compares every stage and the full pipeline
against ops/htc.py (itself RFC 9380 J.10.1-anchored); the resident and
chained drivers are bit-identical because affine coordinates are the
canonical representation boundary (points.pt_to_affine).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import knobs as _knobs
from . import tkernel as tk
from . import tkernel_calls as tc
from . import tkernel_pairing as tp
from .htc import SQRT_RATIO_BITS
from .points import pt_add, pt_double, pt_neg
from .tkernel import N_LIMBS
from .tkernel_calls import TILE, _col, _pad_lanes, _specs

SQRT_RATIO_NBITS = len(SQRT_RATIO_BITS)

def _interpret() -> bool:
    return not tk.on_tpu()


def _resident_enabled() -> bool:
    """LHTPU_HTC_RESIDENT=0/1 forces; default on. Routed into the jitted
    driver as a static arg so flipping the knob retraces (reading it
    inside the traced body would freeze the first value — the jit/knob
    staleness trap)."""
    choice = _knobs.knob("LHTPU_HTC_RESIDENT")
    if choice is not None:
        return choice == "1"
    return True


def _lmask(m):
    """Lane mask [..., T] -> [..., 1, 1, T] so selects broadcast against
    Fp2 tensors [..., 2, 48, T] for ANY leading stack axes (the resident
    kernel runs the sswu body with both u-halves on a leading axis;
    without the expansion a [L, T] mask would misalign against
    [L, 2, 48, T])."""
    return m[..., None, None, :]


# ------------------------------------------------------------ field bits


def _cpair(name: str, off: int = 0):
    """Fp2 constant rows [2, 48, 1] from the bound bundle (off selects
    within multi-element tables like SQRT_CANDS/ISO_*)."""
    i = tk._IDX[name] + 2 * off
    return tk._bundle()[i:i + 2]


def _fp2_pow_bits_t(a, bit_src, nbits: int):
    """a^e in Fq2 by square-and-multiply over a bit-table ref (MSB first,
    leading bit consumes a) — tk.pow_bits_t lifted to Fp2."""

    def body(i, acc):
        acc = tk.fp2_sqr_t(acc)
        return jnp.where(bit_src[i, 0] == 1, tk.fp2_mul_t(acc, a), acc)

    return jax.lax.fori_loop(1, nbits, body, a)


def _fp2_sgn0_t(a):
    """RFC 9380 sgn0 on transposed Montgomery Fp2 -> int32 [T]."""
    c0 = tk.canonical_t(tk.mont_mul_t(a[..., 0, :, :], tk._c("ONE_STD")))
    c1 = tk.canonical_t(tk.mont_mul_t(a[..., 1, :, :], tk._c("ONE_STD")))
    sign0 = c0[..., 0, :] & 1
    zero0 = jnp.all(c0 == 0, axis=-2).astype(jnp.int32)
    sign1 = c1[..., 0, :] & 1
    return sign0 | (zero0 & sign1)


def _sqrt_ratio_t(u, v, ebits_ref):
    """(is_square int32 [..., T], root) — htc.sqrt_ratio on the
    transposed layout; ONE exponentiation + 8 candidate checks. Leading
    -axis polymorphic (selects go through _lmask) so the resident map
    kernel can push both u-halves through one call."""
    v2 = tk.fp2_sqr_t(v)
    v4 = tk.fp2_sqr_t(v2)
    uv7 = tk.fp2_mul_t(u, tk.fp2_mul_t(tk.fp2_mul_t(v4, v2), v))
    uv15 = tk.fp2_mul_t(uv7, tk.fp2_mul_t(v4, v4))
    t = tk.fp2_mul_t(uv7, _fp2_pow_bits_t(uv15, ebits_ref, SQRT_RATIO_NBITS))

    zu = tk.fp2_mul_t(_cpair("SSWU_Z"), u)
    tz = tk.fp2_mul_t(t, _cpair("C_Z"))
    root = jnp.zeros_like(t)
    ok = jnp.zeros(t.shape[-1:], jnp.int32)
    for i in range(4):
        cand = tk.fp2_mul_t(t, _cpair("SQRT_CANDS", i))
        hit = (
            tk.fp2_eq_t(tk.fp2_mul_t(tk.fp2_sqr_t(cand), v), u).astype(jnp.int32)
            & (1 - ok)
        )
        root = jnp.where(_lmask(hit) == 1, cand, root)
        ok = ok | hit
    is_sq = ok
    for i in range(4):
        cand = tk.fp2_mul_t(tz, _cpair("SQRT_CANDS", i))
        hit = (
            tk.fp2_eq_t(tk.fp2_mul_t(tk.fp2_sqr_t(cand), v), zu).astype(jnp.int32)
            & (1 - ok)
        )
        root = jnp.where(_lmask(hit) == 1, cand, root)
        ok = ok | hit
    return is_sq, root


# --------------------------------------------------------- sswu + isogeny


def _sswu_iso_body(u, ebits_ref):
    """SSWU map + 3-isogeny, u [..., 2, 48, T] -> Jacobian (X, Y, Z) on
    E2, same leading axes. Leading-axis polymorphic (all lane selects go
    through _lmask), so the standalone kernel runs it at [2, 48, T] and
    the resident kernel at [2, 2, 48, T] with both u-halves stacked —
    doubling the row stack every Fp2 product feeds the Montgomery
    engine. Call under tk.bound_consts."""

    def c2(name, off=0):
        return _cpair(name, off)  # [2,48,1], broadcasts inside ops

    a = c2("SSWU_A")
    b = c2("SSWU_B")
    z = c2("SSWU_Z")
    one = jnp.stack([tk._c("R"), tk._c("ZERO")])  # [2,48,1]

    tv1 = tk.fp2_mul_t(z, tk.fp2_sqr_t(u))          # Z u^2
    tv2 = tk.add_t(tk.fp2_sqr_t(tv1), tv1)
    exc = tk.fp2_is_zero_t(tv2)
    num1 = tk.fp2_mul_t(b, tk.add_t(tv2, one))
    den = jnp.where(
        _lmask(exc),
        tk.fp2_mul_t(z, a),
        tk.fp2_neg_t(tk.fp2_mul_t(a, tv2)),
    )
    den2 = tk.fp2_sqr_t(den)
    gxn = tk.add_t(
        tk.add_t(
            tk.fp2_mul_t(tk.fp2_sqr_t(num1), num1),
            tk.fp2_mul_t(tk.fp2_mul_t(a, num1), den2),
        ),
        tk.fp2_mul_t(b, tk.fp2_mul_t(den2, den)),
    )
    gxd = tk.fp2_mul_t(den2, den)
    is_sq, y1 = _sqrt_ratio_t(gxn, gxd, ebits_ref)

    sq = _lmask(is_sq == 1)
    xn = jnp.where(sq, num1, tk.fp2_mul_t(tv1, num1))
    y = jnp.where(sq, y1, tk.fp2_mul_t(tk.fp2_mul_t(tv1, u), y1))
    flip = _lmask(_fp2_sgn0_t(u) != _fp2_sgn0_t(y))
    y = jnp.where(flip, tk.fp2_neg_t(y), y)

    # 3-isogeny on the fraction xn/den (htc.iso3_jacobian).
    npows = [one, xn, tk.fp2_sqr_t(xn)]
    npows.append(tk.fp2_mul_t(npows[2], xn))
    dpows = [one, den, tk.fp2_sqr_t(den)]
    dpows.append(tk.fp2_mul_t(dpows[2], den))

    def poly(name, deg):
        acc = None
        for i in range(deg + 1):
            term = tk.fp2_mul_t(
                c2(name, i), tk.fp2_mul_t(npows[i], dpows[deg - i])
            )
            acc = term if acc is None else tk.add_t(acc, term)
        return acc

    Xn = poly("ISO_XNUM", 3)
    Xd = poly("ISO_XDEN", 2)
    Yn = poly("ISO_YNUM", 3)
    Yd = poly("ISO_YDEN", 3)

    xd2 = tk.fp2_mul_t(den, Xd)
    Z = tk.fp2_mul_t(xd2, Yd)
    X = tk.fp2_mul_t(Xn, tk.fp2_mul_t(xd2, tk.fp2_sqr_t(Yd)))
    Y = tk.fp2_mul_t(
        tk.fp2_mul_t(y, Yn),
        tk.fp2_mul_t(tk.fp2_mul_t(xd2, tk.fp2_sqr_t(xd2)), tk.fp2_sqr_t(Yd)),
    )
    return X, Y, Z


def _sswu_iso_kernel(u_ref, ebits_ref, consts_ref, mont_ref, out_ref):
    with tk.bound_consts(consts_ref[:], mont=mont_ref[:]):
        out_ref[:] = jnp.stack(_sswu_iso_body(u_ref[:], ebits_ref))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sswu_iso_t(u, interpret: bool):
    t = u.shape[-1]
    # tile cap 128 (was 256): the grouped-conv engine's window buffers
    # put the 256-lane body 2.7M over the 16M scoped-VMEM limit.
    tile = TILE
    t_pad = -(-t // tile) * tile
    u = _pad_lanes(u, t_pad)
    in_specs = _specs(
        [((2, N_LIMBS), True), ((SQRT_RATIO_NBITS, 1), False),
         ((tk.N_CONSTS, N_LIMBS, 1), False),
         ((tk.N_MONT_ROWS, N_LIMBS), False)],
        tile,
    )
    out = pl.pallas_call(
        _sswu_iso_kernel,
        out_shape=jax.ShapeDtypeStruct((3, 2, N_LIMBS, t_pad), jnp.int32),
        grid=(t_pad // tile,),
        in_specs=in_specs,
        out_specs=_specs([((3, 2, N_LIMBS), True)], tile)[0],
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(u, _col(SQRT_RATIO_BITS), jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP))
    return tuple(out[i, ..., :t] for i in range(3))


# ------------------------------------------------------- cofactor clearing


def _psi_t(P, F=None):
    """ψ endomorphism. With an F namespace the two constant products ride
    ONE muln level (stacked into a single Montgomery row batch when
    F.stack_muln — the MXU-folded ladder mode); without, the original
    two-mul form (back-compat for standalone/test callers)."""
    xb, yb = tk.fp2_conj_t(P[0]), tk.fp2_conj_t(P[1])
    cx, cy = _cpair("PSI_CX"), _cpair("PSI_CY")
    if F is None:
        mx, my = tk.fp2_mul_t(xb, cx), tk.fp2_mul_t(yb, cy)
    else:
        mx, my = F.muln((xb, cx), (yb, cy))
    return (mx, my, tk.fp2_conj_t(P[2]))


def _cofactor_body(F, Q):
    """(x^2-x-1) Q + (x-1) ψ(Q) + ψ(ψ(2Q)) — htc.clear_cofactor fused,
    via two segmented |x|-walks instead of uniform bit-table chains.

    With t = [|x|]Q and t2 = [|x|]t (x < 0, so [x]Q = -t and
    [x²]Q = t2):

        (x²-x-1) Q = t2 + t - Q
        (x-1) ψ(Q) = ψ((x-1) Q) = -ψ(t + Q)

        h_eff Q = t2 + t - Q - ψ(t + Q) + ψ²(2Q)

    Each walk is |x|'s static bit layout (63 doublings, 5 adds —
    tkernel_pairing.segmented_x_walk, the same segmentation the Miller
    loop and ψ subgroup check use), so the body runs ~127 doublings +
    15 full additions instead of 190 doublings + 190 additions: ~3.9x
    fewer field ops. All additions are the complete masked pt_add
    (doubling/inverse/infinity cases selected), so pipeline points and
    padding lanes are safe; parity with the classic path is pinned on
    the affine outputs (tests/test_htc.py)."""

    def x_walk(base):
        walk = tp.segmented_x_walk(
            dbl=lambda a: pt_double(F, a),
            dbl_add=lambda a: pt_add(F, pt_double(F, a), base),
        )
        return walk(base)

    # t = [|x|]Q, then t2 = [|x|]t: ONE traced walk run twice (a loop,
    # not two inlined walks — half the kernel code, and Mosaic's compile
    # time grows faster than the code; CHANGES.md, PR 21).
    def walk_step(i, carry):
        t_kept, base = carry
        w = x_walk(base)
        return tuple(jnp.where(i == 0, a, b) for a, b in zip(w, t_kept)), w

    t, t2 = jax.lax.fori_loop(0, 2, walk_step, (Q, Q))
    term0 = pt_add(F, pt_add(F, t2, t), pt_neg(F, Q))
    term1 = pt_neg(F, _psi_t(pt_add(F, t, Q), F))
    term2 = _psi_t(_psi_t(pt_double(F, Q), F), F)
    return pt_add(F, pt_add(F, term0, term1), term2)


def _cofactor_kernel(pt_ref, consts_ref, mont_ref, out_ref):
    # lowmem: the grouped-conv window buffers put this body 628K over
    # the 16M scoped-VMEM limit at full group size.
    with tk.bound_consts(consts_ref[:], mont=mont_ref[:], lowmem=True):
        F = tk.fp2_ops_t(stack_muln=tk.ladder_stack_enabled())
        Q = (pt_ref[0], pt_ref[1], pt_ref[2])
        out_ref[:] = jnp.stack(_cofactor_body(F, Q))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _cofactor_t(P, interpret: bool):
    t = P[0].shape[-1]
    # tile cap 128, not 256: the two-walk kernel holds the walk base, the
    # accumulator and the complete-add temporaries live at once — at 256
    # lanes its VMEM stack is 16.09M, 96K over the 16M scoped limit.
    tile = TILE
    t_pad = -(-t // tile) * tile
    stacked = _pad_lanes(jnp.stack(P), t_pad)
    in_specs = _specs(
        [((3, 2, N_LIMBS), True), ((tk.N_CONSTS, N_LIMBS, 1), False),
         ((tk.N_MONT_ROWS, N_LIMBS), False)],
        tile,
    )
    out = pl.pallas_call(
        _cofactor_kernel,
        out_shape=jax.ShapeDtypeStruct((3, 2, N_LIMBS, t_pad), jnp.int32),
        grid=(t_pad // tile,),
        in_specs=in_specs,
        out_specs=_specs([((3, 2, N_LIMBS), True)], tile)[0],
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(stacked, jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP))
    return tuple(out[i, ..., :t] for i in range(3))


# ------------------------------------------------- resident fused program


def _map_to_g2_kernel(u_ref, ebits_ref, consts_ref, mont_ref, out_ref):
    """sswu+iso of each u-half → Q0+Q1 → cofactor clear, one resident
    program: the Jacobian intermediates that the two-kernel chain
    stores/reloads through HBM at each pallas_call boundary stay in
    VMEM/registers for the whole map.

    The two u-halves run as a two-step loop over ONE traced sswu body,
    summed into a Jacobian accumulator that starts at infinity (the
    masked pt_add is complete). Stacking them on a leading axis instead
    doubled the sswu part of the kernel's code, and a cold Mosaic
    compile of the kernel grew faster than that (CHANGES.md, PR 21).

    lowmem for the same reason as the standalone cofactor kernel: the
    live set (walk base + accumulator + complete-add temporaries, now
    alongside the sswu tail) needs the small grouped-conv windows; the
    scoped-VMEM headroom comes from tk.vmem_params()'s 64M grant."""
    with tk.bound_consts(consts_ref[:], mont=mont_ref[:], lowmem=True):
        F = tk.fp2_ops_t(stack_muln=tk.ladder_stack_enabled())
        u0 = u_ref[0]                                   # [2, 48, T]
        one = jnp.broadcast_to(F.one, u0.shape)
        infinity = (one, one, jnp.zeros_like(u0))

        def add_half(i, q):
            return pt_add(F, q, _sswu_iso_body(u_ref[i], ebits_ref))

        Q = jax.lax.fori_loop(0, 2, add_half, infinity)
        out_ref[:] = jnp.stack(_cofactor_body(F, Q))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _map_to_g2_resident_t(us, interpret: bool):
    """us [2, 2, 48, T]: u0/u1 of each message on the LEADING axis (not
    extra lanes like _sswu_iso_t) -> cleared Jacobian (X, Y, Z), each
    [2, 48, T]."""
    t = us.shape[-1]
    tile = TILE
    t_pad = -(-t // tile) * tile
    us = _pad_lanes(us, t_pad)
    in_specs = _specs(
        [((2, 2, N_LIMBS), True), ((SQRT_RATIO_NBITS, 1), False),
         ((tk.N_CONSTS, N_LIMBS, 1), False),
         ((tk.N_MONT_ROWS, N_LIMBS), False)],
        tile,
    )
    out = pl.pallas_call(
        _map_to_g2_kernel,
        out_shape=jax.ShapeDtypeStruct((3, 2, N_LIMBS, t_pad), jnp.int32),
        grid=(t_pad // tile,),
        in_specs=in_specs,
        out_specs=_specs([((3, 2, N_LIMBS), True)], tile)[0],
        interpret=interpret,
        compiler_params=tk.vmem_params(),
    )(us, _col(SQRT_RATIO_BITS), jnp.asarray(tk.CONSTS_NP), jnp.asarray(tk.MONT_MATS_NP))
    return tuple(out[i, ..., :t] for i in range(3))


# ---------------------------------------------------------------- driver


def _map_to_g2_fused(u):
    """u [n, 2, 2, 48] (classic layout, Montgomery) -> transposed affine
    (x, y [2,48,n], inf bool [n]) on G2. Thin knob-reading wrapper: the
    resident/chained choice enters the jitted drivers as a static arg so
    env flips retrace instead of going stale. Front (curve map) and back
    (cofactor finish) are split so the backend can time them as separate
    dispatch sub-stages; on the resident path the split is nominal — the
    fused program already cleared the cofactor, the back half only
    canonicalizes to affine."""
    resident = _resident_enabled()
    interpret = _interpret()
    Q = _map_to_g2_front_jit(u, resident, interpret)
    return _map_to_g2_back_jit(Q, resident, interpret)


@functools.partial(jax.jit, static_argnames=("resident", "interpret"))
def _map_to_g2_front_jit(u, resident: bool, interpret: bool):
    """Curve-map front half: u [n, 2, 2, 48] -> Jacobian (X, Y, Z), each
    [2, 48, n]. resident=True runs the single fused program (output is
    already cofactor-cleared); resident=False runs the standalone
    sswu+iso kernel and the Q0+Q1 complete add, leaving the cofactor for
    the back half. `cleared == resident` — callers thread that flag to
    :func:`_map_to_g2_back_jit`."""
    n = u.shape[0]
    if resident:
        us = jnp.moveaxis(u, 0, -1)  # [2, 2, 48, n], axis 0 = u-half
        return _map_to_g2_resident_t(us, interpret)
    flat = jnp.moveaxis(u, 1, 0).reshape(2 * n, 2, 48)  # u0 then u1
    ut = tk.batch_to_t(flat)
    X, Y, Z = _sswu_iso_t(ut, interpret)
    F2 = tk.fp2_ops_t()
    return pt_add(
        F2,
        (X[..., :n], Y[..., :n], Z[..., :n]),
        (X[..., n:], Y[..., n:], Z[..., n:]),
    )


@functools.partial(jax.jit, static_argnames=("cleared", "interpret"))
def _map_to_g2_back_jit(Q, cleared: bool, interpret: bool):
    """Finish half: Jacobian Q -> transposed affine (x, y, inf). Clears
    the cofactor first unless the front half already did (resident)."""
    if not cleared:
        Q = _cofactor_t(Q, interpret)
    return tc.to_affine_g2_t(Q)


def hash_to_g2_map_dev(msgs, dst=None):
    """Stage-split front of :func:`hash_to_g2_fused_dev`: host SHA-256 +
    field reduction, then the curve-map front half on device. Returns
    ``(Q, cleared)`` — Q a Jacobian (X, Y, Z) triple of [2, 48, n] jax
    arrays, cleared True when the resident program already ran the
    cofactor ladder. Feed to :func:`hash_to_g2_finish_dev`."""
    from .htc import DST as _DST
    from .htc import hash_to_field_dev

    u = jnp.asarray(hash_to_field_dev(msgs, _DST if dst is None else dst))
    return _map_to_g2_front_jit(u, _resident_enabled(), _interpret()), (
        _resident_enabled()
    )


def map_programs(n: int) -> list:
    """(jitted fn, argument avals, static kwargs) of the two programs
    :func:`hash_to_g2_map_dev` and :func:`hash_to_g2_finish_dev` run for
    ``n`` messages, with the flags they read, so a caller can build them
    before the first batch (chip_smoke.py)."""
    resident = _resident_enabled()
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    q = (i32((2, 48, n)),) * 3
    return [
        (_map_to_g2_front_jit, (i32((n, 2, 2, 48)), resident, _interpret()),
         {}),
        (_map_to_g2_back_jit, (q, resident, _interpret()), {}),
    ]


def hash_to_g2_finish_dev(Q, cleared: bool):
    """Stage-split back of :func:`hash_to_g2_fused_dev`: cofactor clear
    (unless the resident front already did) + canonical affine, results
    left on device in classic layout (x[n,2,48], y[n,2,48], inf[n])."""
    x, y, inf = _map_to_g2_back_jit(Q, cleared, _interpret())
    return tk.batch_from_t(x), tk.batch_from_t(y), inf


def hash_to_g2_fused_dev(msgs, dst=None):
    """Batched hash_to_curve through the fused kernels, results left ON
    DEVICE: messages -> classic-layout affine (x[n,2,48], y[n,2,48],
    inf[n]) jax arrays. Host side is SHA-256 + field reduction
    (htc.hash_to_field_dev); the curve mapping runs as one resident
    Pallas program (or the chained two-kernel A/B path). Keeping the
    outputs device-resident lets the verify program consume them
    without a host round-trip (the round-2 path downloaded to numpy and
    re-uploaded — two host transfers plus a sync barrier per batch;
    VERDICT r2 item 2)."""
    Q, cleared = hash_to_g2_map_dev(msgs, dst)
    return hash_to_g2_finish_dev(Q, cleared)


def hash_to_g2_fused(msgs, dst=None):
    """numpy-materializing wrapper of :func:`hash_to_g2_fused_dev`
    (tests / host consumers)."""
    x, y, inf = hash_to_g2_fused_dev(msgs, dst)
    return np.asarray(x), np.asarray(y), np.asarray(inf)
