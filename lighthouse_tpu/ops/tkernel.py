"""Transposed ("limbs on sublanes, batch on lanes") field library for the
fused Pallas verifier kernels.

Why this exists: profiling on the v5e (see pallas_mont.py history) shows
per-XLA-op dispatch overhead of ~0.1-1ms dominating the batch verifier —
the arithmetic itself is nearly free. The verifier's wall time is its
*sequential depth* (64-step RLC scalar muls, ~255-step subgroup checks,
63-step Miller loop, ~1000-step final-exp/inversion chains) times that
per-op overhead. The fix (ops/tkernel_calls.py) runs each long chain
inside ONE Pallas program, where a loop iteration costs ~μs instead of
~ms. This module is the arithmetic those programs are built from.

Layout: every Fp element is int32[..., 48, T] — limb axis on sublanes,
batch on lanes — so limb-window operations are static sublane slices.
Coefficient/stack axes sit ahead of the limb axis exactly like
ops/tower.py (Fp2 = [..., 2, 48, T], Fp6 = [..., 3, 2, 48, T],
Fp12 = [..., 2, 3, 2, 48, T]). All functions are plain jnp compositions,
usable both inside Pallas kernels and directly under XLA (tests exploit
this: transposed results are compared against ops/limb.py / ops/tower.py
bit-for-bit).

The group law is NOT re-implemented: ops/points.py is generic over a
FieldOps namespace and :class:`TFieldOps` adapts the transposed layout
(lane masks broadcast from the right, so select needs no axis padding).

Constants discipline: Pallas kernels may not close over array constants —
every constant must arrive as a kernel input. All field constants here
live in one ``CONSTS`` bundle (int32[N_CONSTS, 48, 1]); XLA-land callers
use the module default, kernel bodies rebind via ``bound_consts(c)``
around the traced body (trace-time thread-local swap).

Semantics/invariants mirror ops/limb.py exactly: Montgomery form, lazy
[0, 2p) domain, limbs normalized to [0, 255] on op exit.
"""

from __future__ import annotations

import contextlib

import numpy as np
import jax
import jax.numpy as jnp

from ..common import knobs as _knobs
from ..crypto.bls.constants import P
from . import limb as _limb
from .limb import LIMB_BITS, LIMB_MASK, N_LIMBS, NINV8
from .points import FieldOps

_ROWS = 2 * N_LIMBS

# ----------------------------------------------------------- const bundle
# Row order in the CONSTS bundle. Fp2 constants occupy two consecutive
# rows (c0, c1).
_IDX = {
    "P": 0,
    "TWO_P": 1,
    "R": 2,        # 1 in Montgomery form
    "ZERO": 3,
    "FROB6_C1": 4,     # Fp2: rows 4-5
    "FROB6_C2": 6,     # rows 6-7
    "FROB12_C1": 8,    # rows 8-9
    "PSI_CX": 10,      # rows 10-11: psi endomorphism x-coefficient
    "PSI_CY": 12,      # rows 12-13: psi endomorphism y-coefficient
    # hash-to-curve rows (ops/tkernel_htc.py): SSWU parameters, the
    # sqrt_ratio constant C_Z = Z^(1+(q-9)/16), the four 4th-root sqrt
    # candidates, the 3-isogeny coefficient tables, and a standard-domain
    # one (from-Montgomery multiplier for sgn0).
    "ONE_STD": 14,
    "SSWU_A": 15,      # rows 15-16
    "SSWU_B": 17,      # rows 17-18
    "SSWU_Z": 19,      # rows 19-20
    "C_Z": 21,         # rows 21-22
    "SQRT_CANDS": 23,  # rows 23-30 (4 x Fp2)
    "ISO_XNUM": 31,    # rows 31-38 (4 x Fp2)
    "ISO_XDEN": 39,    # rows 39-44 (3 x Fp2)
    "ISO_YNUM": 45,    # rows 45-52 (4 x Fp2)
    "ISO_YDEN": 53,    # rows 53-60 (4 x Fp2)
    # Complements 2^384 - p / 2^384 - 2p: adding them replaces the
    # signed subtractions (a - p, s - 2p) with nonnegative digit sums,
    # which is what lets the Kogge-Stone carry path assume digits >= 0
    # (binary carries) everywhere.
    "COMP_P": 61,
    "COMP_TWO_P": 62,
}
N_CONSTS = 63

# MXU Montgomery-fold matrices (mont_mul_t): the full-width quotient
# m = t_low * (-p^-1) mod 2^384 and the m*p add-back are constant
# triangular-Toeplitz matmuls (the "banded constant matrices" route onto
# the MXU — VERDICT r3 item 2). They ride as a SEPARATE 2-D kernel
# operand, NOT bundle rows: lane-1 bundle rows pad 1 -> 128 lanes in
# VMEM, so 240 extra rows would cost ~5.9 MB per kernel against the
# 16 MB scoped budget; the 2-D [240, 48] layout pads to ~123 KB.
N_MONT_ROWS = 5 * N_LIMBS  # 144 (M1^T) + 96 (M2)

# Untwist-Frobenius-twist endomorphism coefficients for E'(Fp2):
# psi(x, y) = (conj(x)*PSI_CX, conj(y)*PSI_CY), with psi(Q) = [x_bls]Q on
# G2 — the fast subgroup criterion (Bowe, "Faster subgroup checks for
# BLS12-381"). Loaded from the curve oracle's derivation
# (crypto/bls/curve.py psi/_PSI_CX/_PSI_CY) in _build_consts, like the
# Frobenius constants; psi(G) == [x]G is pinned by tests.


def _build_consts() -> np.ndarray:
    from . import tower

    c = np.zeros((N_CONSTS, N_LIMBS, 1), np.int32)

    def put(name, limbs):
        c[_IDX[name], :, 0] = np.asarray(limbs)

    put("P", _limb.int_to_limbs(P))
    put("TWO_P", _limb.int_to_limbs(2 * P))
    put("R", _limb.int_to_limbs(_limb.R_MONT))
    put("COMP_P", _limb.int_to_limbs((1 << 384) - P))
    put("COMP_TWO_P", _limb.int_to_limbs((1 << 384) - 2 * P))
    for name in ("FROB6_C1", "FROB6_C2", "FROB12_C1"):
        pair = np.asarray(getattr(tower, name))  # [2, 48] lane-limb layout
        c[_IDX[name], :, 0] = pair[0]
        c[_IDX[name] + 1, :, 0] = pair[1]
    from ..crypto.bls import curve as _curve

    for name, fq2 in (("PSI_CX", _curve._PSI_CX), ("PSI_CY", _curve._PSI_CY)):
        pair = tower.fq2_to_dev(fq2)  # Montgomery form
        c[_IDX[name], :, 0] = pair[0]
        c[_IDX[name] + 1, :, 0] = pair[1]

    put("ONE_STD", _limb.int_to_limbs(1))
    from . import htc as _htc

    def put2(name, fq2, offset=0):
        pair = tower.fq2_to_dev(fq2)
        c[_IDX[name] + 2 * offset, :, 0] = pair[0]
        c[_IDX[name] + 2 * offset + 1, :, 0] = pair[1]

    put2("SSWU_A", _htc._A)
    put2("SSWU_B", _htc._B)
    put2("SSWU_Z", _htc._Z)
    put2("C_Z", _htc._C_Z)
    for i, cand in enumerate(_htc._SQRT_CANDS):
        put2("SQRT_CANDS", cand, i)
    from ..crypto.bls.constants import (
        ISO3_X_DEN, ISO3_X_NUM, ISO3_Y_DEN, ISO3_Y_NUM,
    )
    from ..crypto.bls.fields import Fq2 as _Fq2

    for name, coeffs in (
        ("ISO_XNUM", ISO3_X_NUM), ("ISO_XDEN", ISO3_X_DEN),
        ("ISO_YNUM", ISO3_Y_NUM), ("ISO_YDEN", ISO3_Y_DEN),
    ):
        for i, t in enumerate(coeffs):
            put2(name, _Fq2(*t), i)
    return c


def _build_mont_mats() -> np.ndarray:
    """[240, 48] int32: M1^T (rows 0-143) stacked over M2 (rows 144-239).

    M1 [48, 3*48] maps the three byte-planes of t_low (plane k == digit
    shift k) to the quotient digits: m_raw[n] = sum_{i+k<=n}
    ninv[n-i-k] * plane_k[i]; terms with i+k >= 48 vanish mod 2^384 so
    the matrix is triangular and m needs NO carry normalization first
    (linearity of the low product). M2 [96, 48] is the Toeplitz of p:
    (m*p)[n] = sum_k p[n-k] * m[k]."""
    ninv_d = _limb.int_to_limbs((-pow(P, -1, 1 << 384)) % (1 << 384))
    m1 = np.zeros((N_LIMBS, 3 * N_LIMBS), np.int32)
    for k in range(3):
        for i in range(N_LIMBS):
            for n in range(i + k, N_LIMBS):
                m1[n, k * N_LIMBS + i] = ninv_d[n - i - k]
    p_d = _limb.int_to_limbs(P)
    m2 = np.zeros((2 * N_LIMBS, N_LIMBS), np.int32)
    for k in range(N_LIMBS):
        m2[k:k + N_LIMBS, k] = p_d
    return np.concatenate([m1.T, m2]).astype(np.int32)


CONSTS_NP = _build_consts()
MONT_MATS_NP = _build_mont_mats()
_P0 = int(CONSTS_NP[_IDX["P"], 0, 0])

# Current bindings (trace-time, thread-local: concurrent jit traces must
# not see each other's kernel refs). Slots: bundle, pinv_bits, lowmem —
# pinv_bits may be a ref inside kernels; lowmem=True makes fp6/fp12
# products loop instead of stacking beyond the fp2 level (VMEM: a
# fully-stacked fp12 product needs a [54, 96, T] Montgomery buffer —
# 8.5 MB at T=128 — which blows the 16 MB budget inside kernels; under
# XLA the stacking is what amortizes dispatches).
import threading as _threading

_TLS = _threading.local()


def _cur() -> list:
    if not hasattr(_TLS, "cur"):
        _TLS.cur = [None, None, False, None]  # bundle, pinv, lowmem, mont
    return _TLS.cur


def _is_tracer(v) -> bool:
    from jax.core import Tracer

    return isinstance(v, Tracer)


def _bundle():
    cur = _cur()
    if cur[0] is None:
        val = jnp.asarray(CONSTS_NP)
        if _is_tracer(val):
            # Inside a trace (e.g. a Pallas kernel body that lifted the
            # constant): usable for THIS trace but must never be cached
            # — a stale tracer in the TLS poisons every later trace.
            return val
        cur[0] = val
    return cur[0]


def _pinv_bits():
    cur = _cur()
    if cur[1] is None:
        val = jnp.asarray(PINV_BITS_NP.reshape(-1, 1))
        if _is_tracer(val):
            return val
        cur[1] = val
    return cur[1]


@contextlib.contextmanager
def bound_consts(bundle, pinv_bits=None, lowmem=False, mont=None):
    """Rebind the constant bundle (and optionally the inversion bit
    table / low-memory mode / MXU Montgomery-fold matrices) for the
    duration of a traced region — kernel bodies pass their consts input
    values/refs here."""
    cur = _cur()
    prev = cur[:]
    cur[0] = bundle
    if pinv_bits is not None:
        cur[1] = pinv_bits
    cur[2] = lowmem
    if mont is not None:
        cur[3] = mont
    try:
        yield
    finally:
        cur[:] = prev


def _lowmem() -> bool:
    return _cur()[2]


def _mont_mats():
    """[240, 48] int32 fold matrices — bound kernel operand or the
    module default (XLA-land). Same tracer-cache discipline as
    _bundle()."""
    cur = _cur()
    if cur[3] is None:
        val = jnp.asarray(MONT_MATS_NP)
        if _is_tracer(val):
            return val
        cur[3] = val
    return cur[3]


def _c(name):
    return _bundle()[_IDX[name]]


def _c2(name):
    i = _IDX[name]
    return _bundle()[i:i + 2]


# -------------------------------------------------------- layout helpers


def batch_to_t(a):
    """[B, ..., 48] -> [..., 48, B]: leading batch axis becomes lanes."""
    return jnp.moveaxis(jnp.asarray(a), 0, -1)


def batch_from_t(a):
    """[..., 48, B] -> [B, ..., 48]."""
    return jnp.moveaxis(jnp.asarray(a), -1, 0)


# ------------------------------------------------------------- carry logic


#: trace-time op-instance counter (None = off). Methodology matches the
#: README roofline: one STACKED call-site instance counts 1 regardless
#: of stack width — the serial-dependency cost the VPU pays is per
#: instance, not per stacked value. Enabled via count_ops(); zero
#: overhead when off (a dict-is-None test per instrumented call).
_OP_COUNTS: dict | None = None


def _count(event: str, n: int = 1) -> None:
    if _OP_COUNTS is not None:
        _OP_COUNTS[event] = _OP_COUNTS.get(event, 0) + n


@contextlib.contextmanager
def count_ops():
    """Collect per-instance op counts during a trace (jax.eval_shape is
    enough — no compile needed). Yields the counts dict."""
    global _OP_COUNTS
    prev, _OP_COUNTS = _OP_COUNTS, {}
    try:
        yield _OP_COUNTS
    finally:
        counts, _OP_COUNTS = _OP_COUNTS, prev


def _carry_norm(t):
    """Full carry propagation over the limb axis (-2). Signed inputs OK
    (arithmetic shift); returns (normalized limbs, carry_out[...]).

    Scan-with-roll structure (mirroring limb._carry_scan): static row-0
    access per step keeps the traced graph ~5 ops instead of ~200 — the
    unrolled form made XLA-CPU compiles of kernel bodies pathological."""
    _count("carry_serial")
    rows = t.shape[-2]

    def step(_, carry):
        t, c = carry
        v = t[..., 0, :] + c
        # rotate-by-concat (no .at/roll: Mosaic lowers neither scatter
        # nor scan in kernels; fori_loop + concatenate it can)
        t = jnp.concatenate(
            [t[..., 1:, :], (v & LIMB_MASK)[..., None, :]], axis=-2
        )
        return (t, v >> LIMB_BITS)

    t, c = jax.lax.fori_loop(
        0, rows, step, (t, jnp.zeros_like(t[..., 0, :]))
    )
    return t, c  # rows rotated full circle: original order


def _ks_enabled() -> bool:
    """Kogge-Stone carry (log-depth) vs the serial scan-with-roll.

    Default OFF: with KS on, kernels traced under fori_loop bodies emit a
    dynamic_slice that Mosaic cannot lower (r4 BENCH recorded 0.0 sets/s
    with exactly that traceback). Re-enable with LHTPU_KS_CARRY=1 only
    after tools/lowering_smoke.py passes on TPU with the flag set."""
    return bool(_knobs.knob("LHTPU_KS_CARRY"))


def _shift_rows(x, s: int, fill):
    """Shift digits toward higher significance along the limb axis (-2):
    out[i] = x[i - s], rows below s filled with ``fill``."""
    pad = jnp.full((*x.shape[:-2], s, x.shape[-1]), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-s, :]], axis=-2)


def _poison_check(t, bound: int):
    """LHTPU_KS_CHECK digit-range contract (shared by every fast carry
    path): eager inputs get a hard Python assert; traced inputs get +341
    on every digit on violation (341 mod 256 != 0, so the corruption
    survives the byte masks and no oracle-comparison test can miss it).
    Read at TRACE time — same cache-key hazard as LHTPU_KS_CARRY."""
    if _knobs.knob("LHTPU_KS_CHECK"):
        bad = jnp.any((t < 0) | (t > bound))
        if not isinstance(bad, jax.core.Tracer):
            assert not bool(bad), (
                f"fast carry: digits outside [0, {bound}]"
            )
        else:
            t = t + bad.astype(t.dtype) * 341
    return t


def _carry_norm_ks(t, bound: int):
    """Log-depth carry propagation for NONNEGATIVE digits.

    ``t``: int32[..., R, T] digits, each in [0, bound] (row 0 may carry
    one extra +1 from a complement's trailing 1 — safe, row 0 never
    receives a carry). Returns (normalized [0,255] digits, carry_out)
    with carry_out = value >> (8*R), exactly like :func:`_carry_norm`
    for nonnegative inputs.

    Structure instead of a 48-step serial chain:
    1. parallel byte-regroup passes until digits fit [0, 510]; carries
       exiting the top row accumulate into ``c_out`` (value-preserving);
    2. one Kogge-Stone prefix over (generate, propagate) bits — digits
       <= 510 make every carry binary (d + c_in <= 511 < 512), with
       g = d >= 256, p = d == 255 — six shift-combine steps for 48 rows.

    Cost: every step is a full [R, T]-tile vector op; the serial chain
    issues ~5 ops per row at 1-sublane utilization (measured v5e: 9.4
    us vs ~2 us per instance at T=512).

    NEGATIVE integer indices are forbidden in this function: jnp routes
    them through dynamic_slice, which Mosaic does not lower (the r4
    BENCH 0.0 regression); nonnegative static indices take the lax.slice
    path and lower fine.

    Call-site bound derivation (digits in [0, 255] pre-op):
      add_t:        s = a+b stacked with s+COMP_TWO_P  -> 255+255+255 = 765
      sub_t:        a+(255-b)+1 stacked with +TWO_P    -> 255+255+1+255 = 766
      canonical_t:  a+COMP_P                           -> 255+255 = 510
      mont_mul_t:   48-term convolution of 255*255 products (+fold adds)
                    < 48*255*255 + slack               -> (1<<23)+255
    Contract check: LHTPU_KS_CHECK=1 (test tiers) poisons the output on
    any bound violation — eager inputs get a hard Python assert; traced
    inputs get +341 on every digit (341 mod 256 != 0, so the corruption
    survives the byte masks), which no oracle-comparison test can miss
    (a silent near-miss is the failure mode this guards against).
    LHTPU_KS_CHECK is read at TRACE time inside jit-cached callers:
    set it before the first trace (or jax.clear_caches() after
    flipping it), otherwise already-traced kernels silently keep the
    old setting — same cache-key hazard as LHTPU_KS_CARRY.
    """
    rows = t.shape[-2]
    # The two-carry regroup branch reads c2[..., top - 1, :]; with a
    # single limb row that -1 would silently resurrect the
    # negative-index/dynamic_slice Mosaic hazard forbidden above.
    assert rows >= 2, f"_carry_norm_ks needs >= 2 limb rows, got {rows}"
    _count("carry_ks")
    top = rows - 1
    t = _poison_check(t, bound)
    c_out = jnp.zeros_like(t[..., 0, :])
    while bound > 510:
        two = bound >= (1 << (2 * LIMB_BITS))
        lo = t & LIMB_MASK
        if two:
            c1 = (t >> LIMB_BITS) & LIMB_MASK
            c2 = t >> (2 * LIMB_BITS)
            t = lo + _shift_rows(c1, 1, 0) + _shift_rows(c2, 2, 0)
            c_out = (
                c_out
                + c1[..., top, :]
                + c2[..., top - 1, :]
                + (c2[..., top, :] << LIMB_BITS)
            )
            bound = 255 + 255 + (bound >> (2 * LIMB_BITS))
        else:
            c1 = t >> LIMB_BITS
            t = lo + _shift_rows(c1, 1, 0)
            c_out = c_out + c1[..., top, :]
            bound = 255 + (bound >> LIMB_BITS)

    out, g_top = _ks_prefix(t)
    return out, c_out + g_top


def _ks_prefix(t):
    """Kogge-Stone binary-carry resolution for digits in [0, 510]:
    (generate, propagate) prefix over log2(rows) shift-combine steps.
    Returns (normalized [0, 255] digits, int32 carry out of the top
    row)."""
    rows = t.shape[-2]
    g = t >= 256
    p = t == 255
    s = 1
    while s < rows:
        g = g | (p & _shift_rows(g, s, False))
        p = p & _shift_rows(p, s, True)
        s *= 2
    c_in = _shift_rows(g, 1, False).astype(jnp.int32)
    out = (t + c_in) & LIMB_MASK
    return out, g[..., rows - 1, :].astype(jnp.int32)


def _mxu_carry_enabled() -> bool:
    """Carry regroup as banded-Toeplitz MXU matmuls (ISSUE 18 tentpole
    b). Default OFF until hardware-proven — the r4 Kogge-Stone path
    shipped default-ON without a TPU compile and zeroed the bench; this
    knob follows the same discipline. Read at trace time."""
    return bool(_knobs.knob("LHTPU_MXU_CARRY"))


def _fast_carry_enabled() -> bool:
    """Either log-depth carry path (Kogge-Stone shifts or MXU-folded
    regroup) replaces the serial scan-with-roll."""
    return _ks_enabled() or _mxu_carry_enabled()


def _fast_carry(t, bound: int):
    """Dispatch one nonnegative-digit carry normalization to the MXU
    matmul regroup (LHTPU_MXU_CARRY) or the Kogge-Stone shift regroup.
    Same contract as :func:`_carry_norm_ks`."""
    if _mxu_carry_enabled():
        return _carry_norm_mxu(t, bound)
    return _carry_norm_ks(t, bound)


def _regroup_mat(rows: int, planes: int):
    """[rows, planes*rows] f32 banded-Toeplitz regroup matrix
    ``W = [I | S1 | S2 ...]`` with S_k[i, j] = 1 iff i == j + k, built
    from iotas at trace time (NOT a closed-over array constant — kernel
    bodies may trace this; Mosaic lowers iota/compare/concat fine)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    return jnp.concatenate(
        [(i == j + k).astype(jnp.float32) for k in range(planes)], axis=1
    )


def _carry_norm_mxu(t, bound: int):
    """Carry propagation with the byte regroup folded onto the MXU.

    Same contract as :func:`_carry_norm_ks` (NONNEGATIVE digits in
    [0, bound]; returns normalized [0, 255] digits + carry_out), but
    each regroup pass — the dominant instruction cost of the shift
    form, three full-tile adds plus masks per pass — is ONE constant
    banded-Toeplitz matmul ``W @ [lo; c1; c2]`` riding the MXU, the
    same trick as :func:`_mont_fold_mxu`'s quotient planes. The final
    binary carries still resolve through the 6-step Kogge-Stone prefix
    (an exact single-matmul carry is impossible: a 255-run ripple needs
    the full 384-bit prefix, beyond any fixed-precision dot).

    Exactness: matrix entries are 0/1 and plane digits stay < 2^16
    (c2 <= bound >> 16 < 2^8 for every call-site bound), so each f32
    dot output is < 3 * 2^16 — integer-exact. Dots loop over the
    flattened leading axis like :func:`_mont_fold_mxu` (2-D MXU
    contractions; elementwise stages ride the stacked array).
    """
    rows = t.shape[-2]
    assert rows >= 2, f"_carry_norm_mxu needs >= 2 limb rows, got {rows}"
    _count("carry_mxu")
    top = rows - 1
    t = _poison_check(t, bound)
    hp = jax.lax.Precision.HIGHEST
    lead = t.shape[:-2]
    T = t.shape[-1]
    flat = t.reshape((-1, rows, T))
    L = flat.shape[0]
    c_out = jnp.zeros_like(flat[:, 0, :])

    def _dots(w, planes):
        return jnp.stack([
            jax.lax.dot_general(
                w, planes[l], (((1,), (0,)), ((), ())), precision=hp
            )
            for l in range(L)
        ]).astype(jnp.int32)

    while bound > 510:
        two = bound >= (1 << (2 * LIMB_BITS))
        lo = flat & LIMB_MASK
        if two:
            c1 = (flat >> LIMB_BITS) & LIMB_MASK
            c2 = flat >> (2 * LIMB_BITS)
            planes = jnp.concatenate([lo, c1, c2], axis=-2)
            _count("mxu_mac", 3 * rows * rows)
            flat = _dots(_regroup_mat(rows, 3), planes.astype(jnp.float32))
            c_out = (
                c_out
                + c1[:, top, :]
                + c2[:, top - 1, :]
                + (c2[:, top, :] << LIMB_BITS)
            )
            bound = 255 + 255 + (bound >> (2 * LIMB_BITS))
        else:
            c1 = flat >> LIMB_BITS
            planes = jnp.concatenate([lo, c1], axis=-2)
            _count("mxu_mac", 2 * rows * rows)
            flat = _dots(_regroup_mat(rows, 2), planes.astype(jnp.float32))
            c_out = c_out + c1[:, top, :]
            bound = 255 + (bound >> LIMB_BITS)

    out, g_top = _ks_prefix(flat)
    return (
        out.reshape((*lead, rows, T)),
        (c_out + g_top).reshape((*lead, T)),
    )


def add_t(a, b):
    """(a + b) mod-ish, in [0, 2p) (limb.add semantics).

    The sum s and s - 2p ride ONE stacked carry pass; s - 2p is
    computed as s + (2^384 - 2p) so both branches stay nonnegative
    (COMP_TWO_P constant) and the stacked pass can use the Kogge-Stone
    path. The d-branch carry bit IS the s >= 2p test.
    """
    s_raw = a + b
    shape = jnp.broadcast_shapes(s_raw.shape, _c("TWO_P").shape)
    s_raw = jnp.broadcast_to(s_raw, shape)
    if _fast_carry_enabled():
        both, carries = _fast_carry(
            jnp.stack([s_raw, s_raw + _c("COMP_TWO_P")]), bound=765
        )
        s, d = both[0], both[1]
        ge_2p = carries[1]
        return jnp.where((ge_2p == 1)[..., None, :], d, s)
    both, carries = _carry_norm(
        jnp.stack([s_raw, s_raw - _c("TWO_P")])
    )
    s, d = both[0], both[1]
    borrow = carries[1]
    return jnp.where((borrow == 0)[..., None, :], d, s)


def sub_t(a, b):
    """(a - b) mod-ish, in [0, 2p): a - b if a >= b else a - b + 2p.

    KS path: a - b rides as the complement sum a + (2^384-1 - b) + 1
    (digit-wise 255 - b, no borrows), whose carry bit is the a >= b
    test; + 2p stacks alongside."""
    shape = jnp.broadcast_shapes(a.shape, b.shape, _c("TWO_P").shape)
    if _fast_carry_enabled():
        base = jnp.broadcast_to(a + (LIMB_MASK - b), shape) + _c("ONE_STD")
        both, carries = _fast_carry(
            jnp.stack([base, base + _c("TWO_P")]), bound=766
        )
        d2, d1 = both[0], both[1]
        no_borrow = carries[0]
        return jnp.where((no_borrow == 1)[..., None, :], d2, d1)
    d_raw = jnp.broadcast_to(a - b, shape)
    both, carries = _carry_norm(
        jnp.stack([d_raw, d_raw + _c("TWO_P")])
    )
    d2, d1 = both[0], both[1]
    borrow = carries[0]
    return jnp.where((borrow == 0)[..., None, :], d2, d1)


def neg_t(a):
    return sub_t(jnp.zeros_like(a), a)


def double_t(a):
    return add_t(a, a)


_GROUP = 8  # conv limb-group size (one sublane tile)
_GROUP_LOWMEM = 2  # smaller windows where VMEM is tight (lowmem kernels)

# MXU Montgomery fold (VERDICT r3 item 2). LHTPU_MXU_FOLD=0/1 forces;
# default is on-TPU-only: in CPU interpret mode the fold's dot_generals
# inline into the outer jaxpr by the thousands and the XLA:CPU compile
# of full-pipeline programs explodes (measured: >90 GB compiler RSS on
# both the fused batch verifier and the fused AggregateVerify — the
# CIOS loop compiles fine). Decided lazily at trace time, not import
# (tests flip the platform before first use).


def on_tpu() -> bool:
    """The one platform gate for the kernels' TPU-only choices: Mosaic
    instead of interpret mode, the MXU fold, the scoped-VMEM grant.
    Read at trace time. A compile for a described (not attached) chip
    steers all three by patching this one function."""
    return jax.default_backend() == "tpu"


def _mxu_fold_enabled() -> bool:
    choice = _knobs.knob("LHTPU_MXU_FOLD")
    if choice is not None:
        return choice == "1"
    return on_tpu()


def ladder_stack_enabled() -> bool:
    """Fp2-width muln stacking for the ladder kernels (cofactor clear,
    psi subgroup check, resident hash-to-G2 map).

    Pre-fold the conv engine measured SLOWER on wide Fp2 stacks
    (scalar_mul_g2 406→548 ms — FieldOps.muln note), so Fp2 namespaces
    default to looping. The MXU fold changes the trade: its byte regroup
    and carry-estimate passes are vectorized over the stacked leading
    axis, so one muln over k products amortizes the VPU-bound portion k
    ways while the per-row dots stay the same. LHTPU_HTC_MXU_LADDER=0/1
    forces; default follows the fold. Read at trace time, like
    LHTPU_MXU_FOLD."""
    choice = _knobs.knob("LHTPU_HTC_MXU_LADDER")
    if choice is not None:
        return choice == "1"
    return _mxu_fold_enabled()


def vmem_params():
    """Mosaic compiler params raising the scoped-VMEM budget.

    The MXU fold's plane/matmul temporaries push the Miller kernel's
    scoped allocation to 16.85 MB at a 128-lane tile — 5% past
    Mosaic's 16 MB default (measured v5e compile error, r4). v5e has
    128 MB of physical VMEM; grant kernels 64 MB (LHTPU_VMEM_LIMIT_MB
    overrides) and let the scheduler keep using what it needs.
    """
    if not on_tpu():
        return None
    from jax.experimental.pallas import tpu as pltpu

    mb = int(_knobs.knob("LHTPU_VMEM_LIMIT_MB"))
    return pltpu.CompilerParams(vmem_limit_bytes=mb * 1024 * 1024)


def _mont_fold_mxu(t):
    """Montgomery fold as two constant-Toeplitz MXU matmuls.

    ``t``: int32[..., 96, T] >= 0 schoolbook-conv digits (< 2^22). Returns
    int32[..., 48, T] digits (< 2^23) representing (t + m*p) / 2^384 with
    m = t_low * (-p^-1) mod 2^384 — the full-width Montgomery quotient,
    computed at once instead of digit-by-digit (CIOS): the sequential
    fold's 48 iterations of 48-row MACs + 96-row rolls were the largest
    single block of the measured VMEM-bandwidth/instruction cost.

    Exactness: every dot is f32 with HIGHEST precision; all values stay
    below 2^24 (planes <= 255 * triangle of 144 terms -> m_raw < 9.4M;
    mp < 48*256*255 = 3.1M), so f32 arithmetic is integer-exact. The
    low half of t + m*p is == 0 mod 2^384 by construction; its carry
    into the high half is < 2^15 and is recovered exactly from the top
    six low digits (tail below digit 42 contributes < 2^-25).
    """
    _count("mxu_mac", 3 * N_LIMBS * N_LIMBS + 2 * N_LIMBS * N_LIMBS)
    lead = t.shape[:-2]
    T = t.shape[-1]
    hp = jax.lax.Precision.HIGHEST
    mats = _mont_mats()
    m1t = mats[:3 * N_LIMBS].astype(jnp.float32)        # [144, 48]
    m2c = mats[3 * N_LIMBS:].astype(jnp.float32)        # [96, 48]

    flat = t.reshape((-1, 2 * N_LIMBS, T))
    L = flat.shape[0]
    tl = flat[:, :N_LIMBS, :]
    planes = jnp.concatenate(
        [tl & LIMB_MASK, (tl >> LIMB_BITS) & LIMB_MASK,
         tl >> (2 * LIMB_BITS)], axis=-2,
    ).astype(jnp.float32)                                    # [L, 144, T]
    # Only the dots loop over L (2-D MXU contractions; a handful of
    # instructions each) — every elementwise stage below rides the
    # stacked [L, ...] arrays in one pass, keeping the traced graph
    # L-independent where it can be (the unrolled-body compile blowups
    # are a measured hazard on this stack, see _carry_norm).
    m_raw = jnp.stack([
        jax.lax.dot_general(
            m1t, planes[l], (((0,), (0,)), ((), ())), precision=hp
        )
        for l in range(L)
    ])                                                       # [L, 48, T]
    m = m_raw.astype(jnp.int32)
    zrow = jnp.zeros_like(m[:, :1, :])
    for _ in range(3):  # parallel byte regroup: digits -> [0, 256]
        lo = m & LIMB_MASK
        c1 = (m >> LIMB_BITS) & LIMB_MASK
        c2 = m >> (2 * LIMB_BITS)
        m = (lo
             + jnp.concatenate([zrow, c1[:, :-1, :]], axis=-2)
             + jnp.concatenate([zrow, zrow, c2[:, :-2, :]], axis=-2))
    mp = jnp.stack([
        jax.lax.dot_general(
            m2c, m[l].astype(jnp.float32), (((1,), (0,)), ((), ())),
            precision=hp,
        )
        for l in range(L)
    ])                                                       # [L, 96, T]
    t2 = flat + mp.astype(jnp.int32)
    est = jnp.zeros((L, T), jnp.float32)
    for n in range(N_LIMBS - 6, N_LIMBS):
        est = est + t2[:, n, :].astype(jnp.float32) * np.float32(
            2.0 ** (LIMB_BITS * (n - N_LIMBS))
        )
    c = jnp.rint(est).astype(jnp.int32)
    hi = t2[:, N_LIMBS:, :]
    out = jnp.concatenate([hi[:, :1, :] + c[:, None, :], hi[:, 1:, :]],
                          axis=-2)
    return out.reshape((*lead, N_LIMBS, T))


def _mont_fold_cios(t):
    """CIOS Montgomery fold on int32[..., 96, T] conv digits; sequential
    by construction (each limb's quotient digit m depends on the running
    row 0). Signed-digit safe: ``& LIMB_MASK`` and ``>> LIMB_BITS`` are
    mod-256 / floor on two's-complement int32. Returns the rolled
    [..., 96, T] buffer whose FIRST 48 rows are the folded result."""
    _count("fold_vpu_mac", N_LIMBS * N_LIMBS)
    p_col = _c("P")

    def fold_step(_, t):
        m = (t[..., 0, :] * NINV8) & LIMB_MASK
        head = t[..., :N_LIMBS, :] + p_col * m[..., None, :]
        carry = head[..., 0, :] >> LIMB_BITS
        row1 = head[..., 1:2, :] + carry[..., None, :]
        # consumed row 0 drops off; fresh zero row enters at the top —
        # the roll fused into the concat
        return jnp.concatenate(
            [row1, head[..., 2:, :], t[..., N_LIMBS:, :],
             jnp.zeros_like(row1)],
            axis=-2,
        )

    return jax.lax.fori_loop(0, N_LIMBS, fold_step, t)


def _mont_conv(a, b, lanes_match: bool):
    """48-term schoolbook convolution t = a * b on pre-broadcast equal
    shapes: int32[..., 48, T] x 2 -> int32[..., 96, T] digits < 48*255^2.

    Grouped static windows when lanes matched pre-broadcast (the grp
    shifted-b operands are materialized once and each group touches one
    (48+grp)-row window — far less data movement than the original
    per-limb rotate-by-concat loop; measured v5e: the engine is
    VMEM-bandwidth/instruction bound on the rolls). Products with a
    lane-1 constant operand keep the roll form: their operand broadcast
    would need a combined sublane+lane broadcast Mosaic does not
    implement."""
    _count("mont_product")
    _count("conv_mac", N_LIMBS * N_LIMBS)
    shape = a.shape
    if lanes_match and shape[-1] != 1:
        grp = _GROUP_LOWMEM if _lowmem() else _GROUP
        assert N_LIMBS % grp == 0, "conv group must divide the limb count"
        zrow = jnp.zeros_like(b[..., :1, :])

        def b_shift(k):
            parts = []
            if k:
                parts.append(
                    jnp.broadcast_to(zrow, (*shape[:-2], k, shape[-1]))
                )
            parts.append(b)
            parts.append(jnp.broadcast_to(  # grp-k >= 1 always
                zrow, (*shape[:-2], grp - k, shape[-1])
            ))
            return jnp.concatenate(parts, axis=-2)

        b_sh = [b_shift(k) for k in range(grp)]

        t = jnp.zeros((*shape[:-2], 2 * N_LIMBS, shape[-1]), jnp.int32)
        W = N_LIMBS + grp
        for g in range(N_LIMBS // grp):                  # static groups
            lo = g * grp
            seg = t[..., lo : lo + W, :]
            for k in range(grp):                         # static sub-steps
                seg = seg + b_sh[k] * a[..., lo + k : lo + k + 1, :]
            parts = [seg]
            if lo:  # Mosaic rejects zero-sized slices in concats
                parts.insert(0, t[..., :lo, :])
            if lo + W < 2 * N_LIMBS:
                parts.append(t[..., lo + W :, :])
            t = jnp.concatenate(parts, axis=-2)
    else:
        zero_rows = jnp.zeros((*shape[:-2], N_LIMBS, shape[-1]), jnp.int32)
        b96 = jnp.concatenate([b, jnp.zeros_like(b)], axis=-2)

        def conv_step(_, carry):
            t, a_buf, b_buf = carry
            t = t + b_buf * a_buf[..., 0:1, :]
            a_buf = jnp.concatenate(
                [a_buf[..., 1:, :], a_buf[..., :1, :]], axis=-2
            )
            b_buf = jnp.concatenate(
                [b_buf[..., -1:, :], b_buf[..., :-1, :]], axis=-2
            )
            return (t, a_buf, b_buf)

        t, _, _ = jax.lax.fori_loop(
            0, N_LIMBS, conv_step,
            (jnp.concatenate([zero_rows, zero_rows], axis=-2), a, b96),
        )
    return t


def mont_mul_t(a, b):
    """Montgomery product on the transposed layout; broadcast over leading
    axes. Grouped static schoolbook conv (:func:`_mont_conv`) + CIOS
    fold-with-roll + carry (or the MXU fold). The fold keeps the roll
    form either way: its per-limb m chain is sequential by construction
    (CIOS)."""
    lanes_match = a.shape[-1] == b.shape[-1]  # BEFORE broadcasting
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    t = _mont_conv(a, b, lanes_match)

    if _mxu_fold_enabled():
        # The byte regroup can leave the quotient's top digit at 256
        # (m one multiple of 2^384 high), pushing the result into
        # [2p, 2.55p); ride a stacked -2p alongside the carry pass and
        # select by borrow — same trick as add_t, restoring the strict
        # [0, 2p) contract for one near-free stacked value.
        f = _mont_fold_mxu(t)
        shape = jnp.broadcast_shapes(f.shape, _c("TWO_P").shape)
        f = jnp.broadcast_to(f, shape)
        if _fast_carry_enabled():
            both, carries = _fast_carry(
                jnp.stack([f, f + _c("COMP_TWO_P")]), bound=(1 << 23) + 255
            )
            s, d = both[0], both[1]
            ge_2p = carries[1]
            return jnp.where((ge_2p == 1)[..., None, :], d, s)
        both, carries = _carry_norm(jnp.stack([f, f - _c("TWO_P")]))
        s, d = both[0], both[1]
        borrow = carries[1]
        return jnp.where((borrow == 0)[..., None, :], d, s)

    t = _mont_fold_cios(t)
    if _fast_carry_enabled():
        out, _ = _fast_carry(t[..., :N_LIMBS, :], bound=(1 << 23) + 255)
        return out
    out, _ = _carry_norm(t[..., :N_LIMBS, :])
    return out


def mont_sqr_t(a):
    return mont_mul_t(a, a)


def bits_msb_first(e: int) -> np.ndarray:
    return np.asarray([int(b) for b in bin(e)[2:]], np.int32)


# Bits of p-2 (Fermat inversion exponent), MSB first.
PINV_BITS_NP = bits_msb_first(P - 2)
PINV_NBITS = len(PINV_BITS_NP)


def pow_bits_t(a, bit_src, nbits: int):
    """a^e by square-and-multiply; ``bit_src`` is indexable int32 bits of
    e MSB-first — an [n, 1] column — jnp array (XLA-land) or kernel input ref
    (values don't support dynamic indexing under Mosaic; refs do).
    fori_loop keeps the traced body single-copy; the leading bit consumes
    ``a`` itself."""

    def body(i, acc):
        acc = mont_sqr_t(acc)
        return jnp.where(bit_src[i, 0] == 1, mont_mul_t(acc, a), acc)

    return jax.lax.fori_loop(1, nbits, body, a)


def mont_inv_t(a):
    """Fermat inversion a^(p-2); 0 -> 0 (limb.mont_inv semantics)."""
    return pow_bits_t(a, _pinv_bits(), PINV_NBITS)


def canonical_t(a):
    """Reduce [0,2p) -> [0,p) for comparisons (limb.canonical)."""
    if _fast_carry_enabled():
        d, carry = _fast_carry(a + _c("COMP_P"), bound=510)
        return jnp.where((carry == 1)[..., None, :], d, a)
    d, borrow = _carry_norm(a - _c("P"))
    return jnp.where((borrow == 0)[..., None, :], d, a)


def is_zero_t(a):
    return jnp.all(canonical_t(a) == 0, axis=-2)


def eq_t(a, b):
    return jnp.all(canonical_t(a) == canonical_t(b), axis=-2)


# ------------------------------------------------------------------- Fp2


def _stk(xs, axis):
    return jnp.stack(xs, axis=axis)


fp2_add_t = add_t
fp2_sub_t = sub_t
fp2_neg_t = neg_t
fp2_double_t = double_t


def fp2_mul_t(a, b):
    """Karatsuba, one stacked mont_mul (tower.fp2_mul transposed)."""
    if _lazy_enabled():
        return w_norm(w2_mul(w_strict(a), w_strict(b)))
    a0, a1 = a[..., 0, :, :], a[..., 1, :, :]
    b0, b1 = b[..., 0, :, :], b[..., 1, :, :]
    t = mont_mul_t(
        _stk([a0, a1, add_t(a0, a1)], -3),
        _stk([b0, b1, add_t(b0, b1)], -3),
    )
    t0, t1, t2 = t[..., 0, :, :], t[..., 1, :, :], t[..., 2, :, :]
    return _stk([sub_t(t0, t1), sub_t(sub_t(t2, t0), t1)], -3)


def fp2_sqr_t(a):
    if _lazy_enabled():
        return w_norm(w2_sqr(w_strict(a)))
    a0, a1 = a[..., 0, :, :], a[..., 1, :, :]
    t = mont_mul_t(
        _stk([add_t(a0, a1), a0], -3),
        _stk([sub_t(a0, a1), a1], -3),
    )
    return _stk([t[..., 0, :, :], double_t(t[..., 1, :, :])], -3)


def fp2_mul_fp_t(a, k):
    return mont_mul_t(a, k[..., None, :, :])


def fp2_mul_by_xi_t(a):
    a0, a1 = a[..., 0, :, :], a[..., 1, :, :]
    return _stk([sub_t(a0, a1), add_t(a0, a1)], -3)


def fp2_conj_t(a):
    return _stk([a[..., 0, :, :], neg_t(a[..., 1, :, :])], -3)


def fp2_triple_t(a):
    return add_t(double_t(a), a)


def fp2_inv_t(a):
    s = mont_mul_t(a, a)
    norm_inv = mont_inv_t(add_t(s[..., 0, :, :], s[..., 1, :, :]))
    return _stk(
        [
            mont_mul_t(a[..., 0, :, :], norm_inv),
            mont_mul_t(neg_t(a[..., 1, :, :]), norm_inv),
        ],
        -3,
    )


def fp2_is_zero_t(a):
    return is_zero_t(a[..., 0, :, :]) & is_zero_t(a[..., 1, :, :])


def fp2_eq_t(a, b):
    return eq_t(a[..., 0, :, :], b[..., 0, :, :]) & eq_t(
        a[..., 1, :, :], b[..., 1, :, :]
    )


# --------------------------------------------------------------------- Fp6


def _f6(a, i):
    return a[..., i, :, :, :]


def fp6_mul_t(a, b):
    """Toom/Karatsuba 6-product schedule (tower.fp6_mul transposed)."""
    if _lazy_enabled():
        return w_norm(w6_mul(w_strict(a), w_strict(b)))
    a0, a1, a2 = (_f6(a, i) for i in range(3))
    b0, b1, b2 = (_f6(b, i) for i in range(3))
    pairs = [
        (a0, b0), (a1, b1), (a2, b2),
        (add_t(a1, a2), add_t(b1, b2)),
        (add_t(a0, a1), add_t(b0, b1)),
        (add_t(a0, a2), add_t(b0, b2)),
    ]
    if _lowmem():
        # In-kernel: loop the six products. Stacking them (18 mont rows)
        # measured SLOWER on v5e — the transposed Montgomery engine is
        # bandwidth-bound at fp2 width, so wider rows cost more data
        # movement than they save in issue overhead (points.muln note).
        t0, t1, t2, s12, s01, s02 = (fp2_mul_t(x, y) for x, y in pairs)
    else:
        t = fp2_mul_t(
            _stk([x for x, _ in pairs], -4), _stk([y for _, y in pairs], -4)
        )
        t0, t1, t2, s12, s01, s02 = (t[..., i, :, :, :] for i in range(6))
    c0 = add_t(fp2_mul_by_xi_t(sub_t(sub_t(s12, t1), t2)), t0)
    c1 = add_t(sub_t(sub_t(s01, t0), t1), fp2_mul_by_xi_t(t2))
    c2 = add_t(sub_t(sub_t(s02, t0), t2), t1)
    return _stk([c0, c1, c2], -4)


def fp6_neg_t(a):
    return neg_t(a)


def fp6_mul_by_v_t(a):
    return _stk([fp2_mul_by_xi_t(_f6(a, 2)), _f6(a, 0), _f6(a, 1)], -4)


def fp6_mul_fp2_t(a, k):
    return fp2_mul_t(a, k[..., None, :, :, :])


def fp6_inv_t(a):
    c0, c1, c2 = (_f6(a, i) for i in range(3))
    mp = [(c0, c0), (c1, c2), (c2, c2), (c0, c1), (c1, c1), (c0, c2)]
    if _lowmem():
        a_sq, bc, c_sq, ab, b_sq, ac = (fp2_mul_t(x, y) for x, y in mp)
    else:
        m = fp2_mul_t(
            _stk([x for x, _ in mp], -4), _stk([y for _, y in mp], -4)
        )
        a_sq, bc, c_sq, ab, b_sq, ac = (m[..., i, :, :, :] for i in range(6))
    t0 = sub_t(a_sq, fp2_mul_by_xi_t(bc))
    t1 = sub_t(fp2_mul_by_xi_t(c_sq), ab)
    t2 = sub_t(b_sq, ac)
    if _lowmem():
        n0, n1, n2 = (fp2_mul_t(x, y)
                      for x, y in [(c0, t0), (c2, t1), (c1, t2)])
    else:
        n = fp2_mul_t(_stk([c0, c2, c1], -4), _stk([t0, t1, t2], -4))
        n0, n1, n2 = (n[..., i, :, :, :] for i in range(3))
    denom = add_t(n0, fp2_mul_by_xi_t(add_t(n1, n2)))
    d_inv = fp2_inv_t(denom)
    if _lowmem():
        return _stk([fp2_mul_t(x, d_inv) for x in (t0, t1, t2)], -4)
    return fp2_mul_t(_stk([t0, t1, t2], -4), d_inv[..., None, :, :, :])


def fp6_frobenius_t(a):
    c = fp2_conj_t(a)
    return _stk(
        [
            c[..., 0, :, :, :],
            fp2_mul_t(c[..., 1, :, :, :], _c2("FROB6_C1")),
            fp2_mul_t(c[..., 2, :, :, :], _c2("FROB6_C2")),
        ],
        -4,
    )


# -------------------------------------------------------------------- Fp12


def _w(a, i):
    return a[..., i, :, :, :, :]


def fp12_one_t(shape_like):
    """Fp12 one broadcast to a batch: shape_like is any [.., 48, T] Fp."""
    lanes = shape_like.shape[-1]
    one = jnp.broadcast_to(_c("R"), (N_LIMBS, lanes))
    zero = jnp.zeros((N_LIMBS, lanes), jnp.int32)

    def f2(x0, x1):
        return _stk([x0, x1], -3)

    def f6(a, b, c):
        return _stk([a, b, c], -4)

    z2 = f2(zero, zero)
    c0 = f6(f2(one, zero), z2, z2)
    c1 = f6(z2, z2, z2)
    return _stk([c0, c1], -5)


def fp12_mul_t(a, b):
    if _lazy_enabled():
        return w_norm(w12_mul(w_strict(a), w_strict(b)))
    a0, a1 = _w(a, 0), _w(a, 1)
    b0, b1 = _w(b, 0), _w(b, 1)
    if _lowmem():
        t0 = fp6_mul_t(a0, b0)
        t1 = fp6_mul_t(a1, b1)
        s = fp6_mul_t(add_t(a0, a1), add_t(b0, b1))
    else:
        t = fp6_mul_t(
            _stk([a0, a1, add_t(a0, a1)], -5),
            _stk([b0, b1, add_t(b0, b1)], -5),
        )
        t0, t1, s = (t[..., i, :, :, :, :] for i in range(3))
    c0 = add_t(t0, fp6_mul_by_v_t(t1))
    c1 = sub_t(sub_t(s, t0), t1)
    return _stk([c0, c1], -5)


def fp12_sqr_t(a):
    if _lazy_enabled():
        return w_norm(w12_sqr(w_strict(a)))
    a0, a1 = _w(a, 0), _w(a, 1)
    if _lowmem():
        t0 = fp6_mul_t(a0, a1)
        s = fp6_mul_t(add_t(a0, a1), add_t(a0, fp6_mul_by_v_t(a1)))
    else:
        t = fp6_mul_t(
            _stk([a0, add_t(a0, a1)], -5),
            _stk([a1, add_t(a0, fp6_mul_by_v_t(a1))], -5),
        )
        t0, s = t[..., 0, :, :, :, :], t[..., 1, :, :, :, :]
    c0 = sub_t(sub_t(s, t0), fp6_mul_by_v_t(t0))
    c1 = double_t(t0)
    return _stk([c0, c1], -5)


def fp12_conj_t(a):
    return _stk([_w(a, 0), fp6_neg_t(_w(a, 1))], -5)


def fp12_inv_t(a):
    a0, a1 = _w(a, 0), _w(a, 1)
    if _lowmem():
        s0, s1 = fp6_mul_t(a0, a0), fp6_mul_t(a1, a1)
    else:
        s = fp6_mul_t(_stk([a0, a1], -5), _stk([a0, a1], -5))
        s0, s1 = s[..., 0, :, :, :, :], s[..., 1, :, :, :, :]
    denom = sub_t(s0, fp6_mul_by_v_t(s1))
    d_inv = fp6_inv_t(denom)
    if _lowmem():
        o0, o1 = fp6_mul_t(a0, d_inv), fp6_mul_t(a1, d_inv)
    else:
        o = fp6_mul_t(_stk([a0, a1], -5), _stk([d_inv, d_inv], -5))
        o0, o1 = o[..., 0, :, :, :, :], o[..., 1, :, :, :, :]
    return _stk([o0, fp6_neg_t(o1)], -5)


def fp12_frobenius_t(a):
    c0 = fp6_frobenius_t(_w(a, 0))
    c1 = fp6_mul_fp2_t(fp6_frobenius_t(_w(a, 1)), _c2("FROB12_C1"))
    return _stk([c0, c1], -5)


def fp12_frobenius2_t(a):
    return fp12_frobenius_t(fp12_frobenius_t(a))


def fp12_eq_t(a, b):
    return jnp.all(
        canonical_t(a) == canonical_t(b), axis=(-5, -4, -3, -2)
    )


def fp12_is_one_t(a):
    return fp12_eq_t(a, fp12_one_t(a[..., 0, 0, 0, :, :]))


# --------------------------------------------------------- lazy reduction
# ISSUE 18 tentpole (a): redundant-limb accumulators. The strict ops
# above pay one stacked carry pass + compare-select restore per add/sub
# and a [0, 2p) restore per product; the w_* forms below carry WIDE
# (signed, multi-byte) limbs through whole add/sub/mul-by-xi chains and
# normalize once — a single stacked carry pass per chain (w_norm), the
# add_t trick generalized. Every op updates a trace-time bound ledger
# (value and digit ranges as exact Python ints) and the exactness
# preconditions of the conv / MXU fold / f32 carry estimate are ASSERTED
# at trace time instead of assumed — the [0, 2p) invariant of
# ops/limb.py becomes a per-chain ledger.
#
# Correctness domain: lazy values agree with the strict path mod p (the
# Montgomery quotient of a wide product differs from the strict one by
# multiples of R, so raw [0, 2p) representatives may differ by p) —
# parity is therefore canonical_t-level, which is what every verdict
# comparison uses. Gated by LHTPU_LAZY_REDUCE, default OFF (r4 rule:
# carry reworks ship default-OFF until hardware-proven).

_R384 = 1 << 384


def _lazy_enabled() -> bool:
    """Lazy-reduction tower arithmetic (read at TRACE time — same
    cache-key hazard as LHTPU_KS_CARRY: flip before first trace)."""
    return bool(_knobs.knob("LHTPU_LAZY_REDUCE"))


class _Wide:
    """Redundant-limb accumulator: ``d`` int32[..., 48, T] signed digits
    plus exact Python-int bounds — value in [vmin, vmax], every digit in
    [dmin, dmax]. Plain Python container, NOT a pytree: it must never
    cross a fori_loop/scan boundary (ledgers are trace-time state)."""

    __slots__ = ("d", "vmin", "vmax", "dmin", "dmax")

    def __init__(self, d, vmin: int, vmax: int, dmin: int, dmax: int):
        assert vmin <= vmax and dmin <= dmax
        # int32 headroom for the next few elementwise ops
        assert -(1 << 30) < dmin and dmax < (1 << 30), (
            "lazy ledger: digit bound near int32 overflow — missing "
            "squeeze in the chain"
        )
        self.d = d
        self.vmin, self.vmax = vmin, vmax
        self.dmin, self.dmax = dmin, dmax


def w_strict(x) -> _Wide:
    """Wrap strict [0, 2p) digits (any coefficient layout: Fp, Fp2, Fp6,
    Fp12 — the ledger is per-tensor, conservatively shared by slots)."""
    return _Wide(x, 0, 2 * P - 1, 0, 255)


def w_add(a: _Wide, b: _Wide) -> _Wide:
    return _Wide(a.d + b.d, a.vmin + b.vmin, a.vmax + b.vmax,
                 a.dmin + b.dmin, a.dmax + b.dmax)


def w_sub(a: _Wide, b: _Wide) -> _Wide:
    """Plain digit-wise subtraction — digits (and the value) may go
    negative; the ledger tracks it and w_norm/w_squeeze restore."""
    return _Wide(a.d - b.d, a.vmin - b.vmax, a.vmax - b.vmin,
                 a.dmin - b.dmax, a.dmax - b.dmin)


def w_double(a: _Wide) -> _Wide:
    return _Wide(a.d * 2, 2 * a.vmin, 2 * a.vmax, 2 * a.dmin, 2 * a.dmax)


def w_neg(a: _Wide) -> _Wide:
    return _Wide(-a.d, -a.vmax, -a.vmin, -a.dmax, -a.dmin)


def _w_stack(ws, axis: int) -> _Wide:
    return _Wide(
        jnp.stack([w.d for w in ws], axis),
        min(w.vmin for w in ws), max(w.vmax for w in ws),
        min(w.dmin for w in ws), max(w.dmax for w in ws),
    )


def _w_part(w: _Wide, i: int, axis: int) -> _Wide:
    """Slice one coefficient index off a static axis, sharing the (per-
    tensor, hence conservative) ledger."""
    idx = [slice(None)] * w.d.ndim
    idx[axis] = i
    return _Wide(w.d[tuple(idx)], w.vmin, w.vmax, w.dmin, w.dmax)


def w_norm(w: _Wide):
    """Restore strict [0, 2p) digits with ONE stacked carry pass.

    Generalizes add_t's stacked-complement trick to arbitrary ledgers:
    after a nonneg value shift (+j0*2p, digit-wise via the TWO_P row),
    row_j = d + j*COMP_TWO_P for j = 0..jhi has value
    V + j*(2^384 - 2p), whose carry-out is >= j  iff  V >= j*2p — a
    monotone predicate in j. All rows ride one carry pass; the largest
    true j selects V - j*2p in [0, 2p). Digits in [0, 255] on exit.

    Fast-carry eligible only when digits are nonnegative; otherwise the
    signed serial pass (value-exact for signed digits) resolves it.
    """
    _count("w_norm")
    j0 = 0 if w.vmin >= 0 else -(w.vmin // (2 * P))
    d = w.d + j0 * _c("TWO_P") if j0 else w.d
    vmax = w.vmax + j0 * 2 * P
    dmax = w.dmax + j0 * 255
    jhi = vmax // (2 * P)
    assert jhi <= 64, (
        f"w_norm: value bound {vmax / float(2 * P):.1f}*2p too wide — "
        "missing squeeze in the chain"
    )
    rows = jnp.stack([d + j * _c("COMP_TWO_P") for j in range(jhi + 1)])
    if w.dmin >= 0 and _fast_carry_enabled():
        out, carries = _fast_carry(rows, bound=dmax + jhi * 255)
    else:
        out, carries = _carry_norm(rows)
    res = out[0]  # j = 0 always eligible when V < 2p
    for j in range(1, jhi + 1):
        sel = carries[j] >= j
        if j < jhi:
            sel = sel & jnp.logical_not(carries[j + 1] >= (j + 1))
        res = jnp.where(sel[..., None, :], out[j], res)
    return res


def w_out(w: _Wide):
    """Strict digits for a value leaving the lazy domain (loop-carried
    state, kernel outputs). Identity when the ledger already PROVES
    [0, 2p) value and [0, 255] digits — re-wrapping with w_strict is
    then sound — else one stacked norm. Never hand ``w.d`` to strict
    code directly: a slim that didn't trip leaves 510-digit / 4p-value
    tensors behind, and w_strict would then understate the ledger."""
    if w.vmin >= 0 and w.vmax < 2 * P and w.dmax <= 255:
        return w.d
    return w_norm(w)


def w_squeeze(w: _Wide) -> _Wide:
    """Full re-strictification (digits AND value): w_norm + fresh
    ledger. Invoked automatically at product boundaries whose inputs
    would break the conv/fold exactness bounds."""
    return w_strict(w_norm(w))


def _w_slim(w: _Wide, cap: int = 8) -> _Wide:
    """Re-strictify at a tower-level boundary when the ledger went
    signed or wider than cap*2p — one stacked pass covers every product
    slot of the level at once (vs strict's pass per scalar op), and it
    keeps the downstream w_norm stacks shallow. Also triggers on wide
    digits (> 510, one lazy-add of headroom) so values reused across
    several products squeeze ONCE here instead of per-product inside
    w_mont_mul."""
    if w.vmin < 0 or w.vmax > cap * 2 * P or w.dmax > 510:
        return w_squeeze(w)
    return w


def w_slim_many(*ws):
    """Slim several same-shape accumulators in ONE stacked carry pass
    (stack -> slim -> unstack); a no-op passthrough when every ledger is
    already strict-shaped."""
    s = _w_slim(_w_stack(list(ws), 0))
    return tuple(_w_part(s, i, 0) for i in range(len(ws)))


def w_mont_mul(a: _Wide, b: _Wide) -> _Wide:
    """Montgomery product of wide operands, WITHOUT the final [0, 2p)
    compare-select restore — the output stays a ledgered accumulator
    (digits < 2^24, value < a.vmax*b.vmax/R + 2p).

    Exactness is digit-driven, asserted here: the int32 conv and the
    MXU fold's f32 planes/carry-estimate stay integer-exact up to
    48*510^2 conv digits (< 2^24 - 2^22, the m*p fold margin), so
    operands are auto-squeezed when signed or wider than 510."""
    if a.dmin < 0 or a.dmax > 510:
        a = w_squeeze(a)
    if b.dmin < 0 or b.dmax > 510:
        b = w_squeeze(b)
    assert N_LIMBS * a.dmax * b.dmax < (1 << 24) - (1 << 22), (
        "lazy mont: conv digit bound breaks fold exactness"
    )
    lanes_match = a.d.shape[-1] == b.d.shape[-1]
    shape = jnp.broadcast_shapes(a.d.shape, b.d.shape)
    t = _mont_conv(
        jnp.broadcast_to(a.d, shape), jnp.broadcast_to(b.d, shape),
        lanes_match,
    )
    if _mxu_fold_enabled():
        # regroup can leave the quotient m one multiple of 2^384 high
        # (top digit 256): m < 1.004 * 2^384 -> m*p/R < 2p
        f = _mont_fold_mxu(t)
        vmax = a.vmax * b.vmax // _R384 + 2 * P
    else:
        f = _mont_fold_cios(t)[..., :N_LIMBS, :]
        vmax = a.vmax * b.vmax // _R384 + P
    return _Wide(f, 0, vmax, 0, 1 << 24)


def w2_mul(a: _Wide, b: _Wide) -> _Wide:
    """Fp2 Karatsuba on wide operands (coefficient axis -3), one stacked
    lazy mont — the three products' sub/add recombination stays wide."""
    a0, a1 = _w_part(a, 0, -3), _w_part(a, 1, -3)
    b0, b1 = _w_part(b, 0, -3), _w_part(b, 1, -3)
    t = w_mont_mul(
        _w_stack([a0, a1, w_add(a0, a1)], -3),
        _w_stack([b0, b1, w_add(b0, b1)], -3),
    )
    t0, t1, t2 = (_w_part(t, i, -3) for i in range(3))
    return _w_stack([w_sub(t0, t1), w_sub(w_sub(t2, t0), t1)], -3)


def w2_sqr(a: _Wide) -> _Wide:
    a0, a1 = _w_part(a, 0, -3), _w_part(a, 1, -3)
    t = w_mont_mul(
        _w_stack([w_add(a0, a1), a0], -3),
        _w_stack([w_sub(a0, a1), a1], -3),
    )
    return _w_stack([_w_part(t, 0, -3), w_double(_w_part(t, 1, -3))], -3)


def w2_mul_by_xi(a: _Wide) -> _Wide:
    a0, a1 = _w_part(a, 0, -3), _w_part(a, 1, -3)
    return _w_stack([w_sub(a0, a1), w_add(a0, a1)], -3)


def w6_mul(a: _Wide, b: _Wide) -> _Wide:
    """fp6_mul_t's Toom/Karatsuba 6-product schedule, recombined wide."""
    a0, a1, a2 = (_w_part(a, i, -4) for i in range(3))
    b0, b1, b2 = (_w_part(b, i, -4) for i in range(3))
    pairs = [
        (a0, b0), (a1, b1), (a2, b2),
        (w_add(a1, a2), w_add(b1, b2)),
        (w_add(a0, a1), w_add(b0, b1)),
        (w_add(a0, a2), w_add(b0, b2)),
    ]
    if _lowmem():
        t0, t1, t2, s12, s01, s02 = (
            _w_slim(w2_mul(x, y)) for x, y in pairs
        )
    else:
        t = _w_slim(w2_mul(
            _w_stack([x for x, _ in pairs], -4),
            _w_stack([y for _, y in pairs], -4),
        ))
        t0, t1, t2, s12, s01, s02 = (_w_part(t, i, -4) for i in range(6))
    c0 = w_add(w2_mul_by_xi(w_sub(w_sub(s12, t1), t2)), t0)
    c1 = w_add(w_sub(w_sub(s01, t0), t1), w2_mul_by_xi(t2))
    c2 = w_add(w_sub(w_sub(s02, t0), t2), t1)
    return _w_stack([c0, c1, c2], -4)


def w6_mul_by_v(a: _Wide) -> _Wide:
    return _w_stack(
        [w2_mul_by_xi(_w_part(a, 2, -4)), _w_part(a, 0, -4),
         _w_part(a, 1, -4)],
        -4,
    )


def w12_mul(a: _Wide, b: _Wide) -> _Wide:
    a0, a1 = _w_part(a, 0, -5), _w_part(a, 1, -5)
    b0, b1 = _w_part(b, 0, -5), _w_part(b, 1, -5)
    if _lowmem():
        t0 = _w_slim(w6_mul(a0, b0))
        t1 = _w_slim(w6_mul(a1, b1))
        s = _w_slim(w6_mul(w_add(a0, a1), w_add(b0, b1)))
    else:
        t = _w_slim(w6_mul(
            _w_stack([a0, a1, w_add(a0, a1)], -5),
            _w_stack([b0, b1, w_add(b0, b1)], -5),
        ))
        t0, t1, s = (_w_part(t, i, -5) for i in range(3))
    c0 = w_add(t0, w6_mul_by_v(t1))
    c1 = w_sub(w_sub(s, t0), t1)
    return _w_stack([c0, c1], -5)


def w12_sqr(a: _Wide) -> _Wide:
    a0, a1 = _w_part(a, 0, -5), _w_part(a, 1, -5)
    if _lowmem():
        t0 = _w_slim(w6_mul(a0, a1))
        s = _w_slim(w6_mul(w_add(a0, a1), w_add(a0, w6_mul_by_v(a1))))
    else:
        t = _w_slim(w6_mul(
            _w_stack([a0, w_add(a0, a1)], -5),
            _w_stack([a1, w_add(a0, w6_mul_by_v(a1))], -5),
        ))
        t0, s = _w_part(t, 0, -5), _w_part(t, 1, -5)
    c0 = w_sub(w_sub(s, t0), w6_mul_by_v(t0))
    c1 = w_double(t0)
    return _w_stack([c0, c1], -5)


# ---------------------------------------------------------------- FieldOps


class TFieldOps(FieldOps):
    """FieldOps adapter for the transposed layout: lane masks broadcast
    from the right (batch IS the trailing axis), so select needs no axis
    padding; `zero`/`one` are [.., 48, 1] columns broadcasting over T."""

    def select(self, mask, a, b):
        return jnp.where(mask, a, b)


def fp_ops_t() -> TFieldOps:
    """FP FieldOps bound to the CURRENT constant bundle (call inside
    bound_consts when tracing a kernel body)."""
    return TFieldOps(
        mul=mont_mul_t, sqr=mont_sqr_t, add=add_t, sub=sub_t,
        neg=neg_t, double=double_t, inv=mont_inv_t,
        is_zero=is_zero_t, eq=eq_t,
        zero=jnp.zeros((N_LIMBS, 1), jnp.int32), one=_c("R"), ndim_tail=2,
        canon=canonical_t,
    )


def fp2_ops_t(stack_muln: bool = False) -> TFieldOps:
    """Fp2 FieldOps; ``stack_muln`` default False (Fp2 mont rows are
    bandwidth-bound on the conv engine — see FieldOps.muln). The ladder
    kernels opt in via ladder_stack_enabled() where the MXU fold
    amortizes stacked rows."""
    zero2 = jnp.zeros((2, N_LIMBS, 1), jnp.int32)
    one2 = jnp.concatenate(
        [_c("R")[None], jnp.zeros((1, N_LIMBS, 1), jnp.int32)]
    )
    return TFieldOps(
        mul=fp2_mul_t, sqr=fp2_sqr_t, add=fp2_add_t, sub=fp2_sub_t,
        neg=fp2_neg_t, double=fp2_double_t, inv=fp2_inv_t,
        is_zero=fp2_is_zero_t, eq=fp2_eq_t,
        zero=zero2, one=one2, ndim_tail=3,
        canon=canonical_t,
        stack_muln=stack_muln,
    )
