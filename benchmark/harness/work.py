"""The work batch verification of BLS signature sets needs, counted in
381-bit field multiplications of the textbook algorithm and converted
to int8 operations. It depends on the algorithm, not on how a program
lays out its limbs, so it reads the same whatever implements it.

Verifying n single-pubkey sets (sig_i, pk_i, m_i) with one verdict by
a random linear combination:

    prod_i e([r_i] pk_i, H(m_i)) * e(-g1, sum_i [r_i] sig_i) == 1

needs, for each set, a 64-bit G1 scalar multiplication, a 64-bit G2
scalar multiplication and an addition into the signature sum, the
signature's subgroup check (psi(Q) == [x]Q, a 64-bit G2 multiplication
and one psi), and one pair's share of a multi-Miller loop; for each
verdict, the check pair's Miller loop, the loop's shared Fp12 squarings
and one final exponentiation; and for each distinct message, one
hash-to-G2. Squarings count as multiplications. The counts follow the
usual formulas: Fp2 multiplication by Karatsuba (3 Fp), Fp2 squaring
(2 Fp), Jacobian doubling 2M + 5S and mixed addition 7M + 4S,
projective Miller doubling and addition steps with sparse line
multiplications, Granger-Scott cyclotomic squarings in the hard part
of the final exponentiation, and RFC 9380's hash-to-G2 with the
Budroni-Pintore cofactor clearing.
"""

from __future__ import annotations

# One 381-bit Montgomery multiplication on 8-bit limbs: a 48 x 48 limb
# product and a 48 x 48 reduction, each limb product a multiply and an
# add: 2 * 48 * 48 * 2 int8 operations.
INT8_OPS_PER_FP_MUL = 2 * 48 * 48 * 2

FP2_MUL = 3
FP2_SQR = 2
FP6_MUL = 6 * FP2_MUL
FP12_MUL = 3 * FP6_MUL                 # 54
FP12_SQR = 2 * FP6_MUL                 # 36
FP12_SPARSE_MUL = 13 * FP2_MUL         # 39
FP12_CYCLO_SQR = 9 * FP2_SQR           # 18

X_BITS = 64        # |x| = 0xd201000000010000
X_WEIGHT = 6       # its set bits: 63 doublings and 5 additions

G1_DBL = 2 + 5                         # Fp
G1_MADD = 7 + 4
G2_DBL = 2 * FP2_MUL + 5 * FP2_SQR     # 16
G2_MADD = 7 * FP2_MUL + 4 * FP2_SQR    # 29
G2_ADD = 11 * FP2_MUL + 5 * FP2_SQR    # 43

RAND_BITS = 64


def scalar_mul(bits: int, dbl: int, madd: int) -> int:
    """Double-and-add for a ``bits``-bit scalar with half its bits set."""
    return (bits - 1) * dbl + (bits // 2) * madd


# Miller loop of the optimal ate pairing, per pair (doubling step with
# its line, 3M + 6S in Fp2 and 4 Fp for the line's scaling by P, then a
# sparse multiplication; the same for the addition steps, 11M + 2S).
MILLER_DBL_STEP = 3 * FP2_MUL + 6 * FP2_SQR + 4 + FP12_SPARSE_MUL
MILLER_ADD_STEP = 11 * FP2_MUL + 2 * FP2_SQR + 4 + FP12_SPARSE_MUL
MILLER_PER_PAIR = ((X_BITS - 1) * MILLER_DBL_STEP
                   + (X_WEIGHT - 1) * MILLER_ADD_STEP)
MILLER_SHARED = (X_BITS - 1) * FP12_SQR

# Final exponentiation: the easy part (an Fp12 inversion, about 600
# multiplications with its Fp inversion, two multiplications and a
# Frobenius), the hard part (five exponentiations by |x| in the
# cyclotomic subgroup, about ten Fp12 multiplications, Frobenius maps).
EXP_BY_X = (X_BITS - 1) * FP12_CYCLO_SQR + (X_WEIGHT - 1) * FP12_MUL
FINAL_EXP = 600 + 2 * FP12_MUL + 5 * EXP_BY_X + 10 * FP12_MUL + 60

# Hash-to-G2: two SSWU maps (each dominated by the square-root ratio,
# an exponentiation of about 762 bits in Fp2, plus the 3-isogeny), one
# addition, and the cofactor clearing (two multiplications by x and
# a few additions and psi maps).
SQRT_RATIO = 762 * FP2_SQR + 381 * FP2_MUL
SSWU_MAP = SQRT_RATIO + 30 * FP2_MUL
CLEAR_COFACTOR = 2 * scalar_mul(X_BITS, G2_DBL, G2_MADD) + 4 * G2_ADD + 30
HASH_TO_G2 = 2 * SSWU_MAP + G2_ADD + CLEAR_COFACTOR

PER_SET = (scalar_mul(RAND_BITS, G1_DBL, G1_MADD)
           + scalar_mul(RAND_BITS, G2_DBL, G2_MADD) + G2_ADD
           + scalar_mul(X_BITS, G2_DBL, G2_MADD) + 20
           + MILLER_PER_PAIR)
PER_VERDICT = MILLER_SHARED + MILLER_PER_PAIR + FINAL_EXP


def fp_muls(sets: int, verdicts: int, distinct_messages: int) -> int:
    """381-bit multiplications to verify ``sets`` single-pubkey sets
    with ``verdicts`` verdicts over ``distinct_messages`` messages."""
    return (sets * PER_SET + verdicts * PER_VERDICT
            + distinct_messages * HASH_TO_G2)


def int8_ops(sets: int, verdicts: int, distinct_messages: int) -> int:
    return fp_muls(sets, verdicts, distinct_messages) * INT8_OPS_PER_FP_MUL
