"""The work count behind verify_roofline, for a stated batch."""

from harness import work


def test_parts():
    assert work.FP12_MUL == 54 and work.FP12_SQR == 36
    assert work.MILLER_PER_PAIR == 63 * 64 + 5 * 80
    assert work.PER_SET == 9160
    assert work.PER_VERDICT == 15028
    assert work.HASH_TO_G2 == 9631


def test_one_gossip_call():
    # 1,024 single-pubkey sets, one verdict, 64 distinct messages
    muls = work.fp_muls(1024, 1, 64)
    assert muls == 1024 * 9160 + 15028 + 64 * 9631 == 10_011_252
    assert work.int8_ops(1024, 1, 64) == muls * 9216
    # the per-set share is the Miller loop and the scalar work
    assert 9_000 < muls / 1024 < 10_000


def test_more_verdicts_and_messages_cost_more():
    base = work.fp_muls(256, 1, 64)
    assert work.fp_muls(256, 4, 64) - base == 3 * work.PER_VERDICT
    assert work.fp_muls(256, 1, 256) - base == 192 * work.HASH_TO_G2
