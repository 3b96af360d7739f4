"""Hardware parity sweep for the fused verifier (VERDICT r1 weak #9).

Runs ONLY on a real TPU (the CPU suite covers the same kernels in
interpret mode; on hardware the one extra hazard is Mosaic lowering /
MXU precision divergence). Sweeps the fused production pipeline across
edge shapes — S=1, K>1 aggregation with infinity padding lanes, shared
messages, tampered lanes — asserting the device verdict against the
pure-Python oracle.

Run it on the chip through the chip tool, in one process:
    chiprun -- env LIGHTHOUSE_TPU_TEST_PLATFORM=tpu \
        python -m pytest tests/test_tpu_parity.py -q -p no:xdist
(each new batch shape pays a kernel compile; the persistent cache in
.jax_cache, or $JAX_COMPILATION_CACHE_DIR, makes reruns cheap).
"""

import jax
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="hardware parity sweep; TPU only"
)

from lighthouse_tpu.crypto.bls.api import (  # noqa: E402
    AggregateSignature,
    SecretKey,
    SignatureSet,
    verify_signature_sets_python,
)
from lighthouse_tpu.jax_backend import JaxBackend  # noqa: E402


def _check(sets):
    want = verify_signature_sets_python(sets)
    got = JaxBackend().verify_signature_sets(sets)
    assert got == want, f"device={got} oracle={want}"
    return got


def test_single_set():
    sk = SecretKey.from_int(5)
    m = b"\x01" * 32
    assert _check([SignatureSet.single_pubkey(sk.sign(m), sk.public_key(), m)])


def test_aggregate_with_padding_lanes():
    sks = [SecretKey.from_int(i + 2) for i in range(5)]
    m1, m2 = b"\x02" * 32, b"\x03" * 32
    # K=3 and K=1 in one batch -> padding infinity lanes in the K grid
    s1 = SignatureSet.multiple_pubkeys(
        AggregateSignature.aggregate([sk.sign(m1) for sk in sks[:3]]),
        [sk.public_key() for sk in sks[:3]],
        m1,
    )
    s2 = SignatureSet.single_pubkey(sks[3].sign(m2), sks[3].public_key(), m2)
    assert _check([s1, s2])


def test_shared_message_and_tamper():
    sks = [SecretKey.from_int(i + 11) for i in range(3)]
    m = b"\x04" * 32
    sets = [
        SignatureSet.single_pubkey(sk.sign(m), sk.public_key(), m)
        for sk in sks
    ]
    assert _check(sets)
    # tamper one lane: wrong signer for the message
    bad = SignatureSet.single_pubkey(
        sks[0].sign(m), sks[1].public_key(), m
    )
    assert not _check([sets[0], bad, sets[2]])


def test_wrong_message_rejected():
    sk = SecretKey.from_int(21)
    assert not _check(
        [SignatureSet.single_pubkey(sk.sign(b"\x05" * 32), sk.public_key(), b"\x06" * 32)]
    )
