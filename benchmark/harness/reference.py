"""The plain reference of the signature-set semantics, independent of
the program: a single-pubkey set (sig, validator v, message m) is valid
iff sig is the signature of m under v's key. The configuration's
registry gives v the secret key v + 1, and a BLS signature is unique
(sig = [sk]H(m), with H RFC 9380's hash-to-G2), so validity is the
equality sig == [v + 1]H(m) of points of E2: a point off the curve or
outside G2, another signer or another message all fail it. A batch
verdict is the AND of its sets' validity.

Runs in the generator's worker processes; never imports JAX.
"""

from __future__ import annotations

from . import bls

_H_MEMO: dict = {}


def _hash(msg: bytes):
    h = _H_MEMO.get(msg)
    if h is None:
        if len(_H_MEMO) > 4096:
            _H_MEMO.clear()
        h = _H_MEMO[msg] = bls.jac(bls.hash_to_g2(msg), bls.Fp2)
    return h


def set_valid(validator: int, msg: bytes, sig192: bytes) -> bool:
    """Whether the set (sig, validator's key, msg) verifies."""
    sig = bls.g2_from_bytes(sig192)
    if not bls.on_curve(sig, bls.Fp2, bls.B2):
        return False
    want = bls.mul(_hash(msg), validator + 1, bls.Fp2)
    return bls.eq(bls.jac(sig, bls.Fp2), want, bls.Fp2)


def sets_valid(items) -> list:
    """[(validator, msg, sig192)] -> [valid] (one worker's share)."""
    return [set_valid(v, m, s) for v, m, s in items]


def batch_verdict(items, first=(), submit=None, parts: int = 1) -> bool:
    """AND of the sets' validity. Indices in ``first`` are checked
    before the rest and end the check when one is invalid (the AND is
    decided); the rest are split over ``parts`` jobs of ``submit``."""
    first = list(first)
    if first and not all(sets_valid([items[i] for i in first])):
        return False
    rest = [it for i, it in enumerate(items) if i not in set(first)]
    if not rest:
        return True
    n = max(1, parts)
    chunks = [rest[i::n] for i in range(n)]
    if submit is None:
        return all(all(sets_valid(c)) for c in chunks)
    futures = [submit(sets_valid, c) for c in chunks if c]
    return all(all(f.result()) for f in futures)
