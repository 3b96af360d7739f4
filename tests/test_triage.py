"""Grouped device verdicts + pack-once poison triage (ISSUE 5).

Compile-budget discipline: XLA:CPU takes ~2 minutes PER grouped-core
shape, so every device test in this module is engineered to touch only
two jit buckets — (S=4, G=2, K=2) for round 1 and (S=2, G=2, K=2) for
both refinement and pipelined chunks — and all tests share them through
the in-process jit cache. The full-scale acceptance run (1024 sets,
G=32) lives behind @pytest.mark.slow.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from lighthouse_tpu import jax_backend as jb
from lighthouse_tpu.common import resilience
from lighthouse_tpu.crypto.bls import api as bls_api
from lighthouse_tpu.crypto.bls.api import (
    AggregateSignature,
    SecretKey,
    SignatureSet,
    verify_signature_sets_python,
)
from lighthouse_tpu.ops.tower import FP12_ONE, fp12_mul

SKS = [SecretKey.from_int(i + 7) for i in range(8)]
M_BAD = b"\xee" * 32


def _mixed_sets(n=4, bad=()):
    """n sets alternating [single, 2-key agg, ...]; positions in ``bad``
    carry a signature over the wrong message. Same (S, K=2) compile
    bucket family as test_zz_pipeline."""
    sets = []
    for i in range(n):
        m = bytes([i + 1]) * 32
        signed = M_BAD if i in bad else m
        if i % 2 == 0:
            sk = SKS[i % len(SKS)]
            sets.append(
                SignatureSet.single_pubkey(sk.sign(signed), sk.public_key(), m)
            )
        else:
            a, b = SKS[i % len(SKS)], SKS[(i + 3) % len(SKS)]
            agg = AggregateSignature.aggregate([a.sign(signed), b.sign(m)])
            sets.append(
                SignatureSet.multiple_pubkeys(
                    agg, [a.public_key(), b.public_key()], m
                )
            )
    return sets


def _oracle(sets):
    return [verify_signature_sets_python([s]) for s in sets]


def _stage_count(stage):
    h = jb.DISPATCH_STAGE_SECONDS
    shard = h._shards.get(h._label_key({"stage": stage}))
    return shard.count if shard else 0


@pytest.fixture
def triage_env(monkeypatch):
    """VG=2 + pipeline off: the two cheap compile buckets, nothing else."""
    monkeypatch.setenv("LHTPU_VERDICT_GROUPS", "2")
    monkeypatch.setenv("LHTPU_PIPELINE", "0")
    monkeypatch.setenv("LHTPU_RETRY_BASE_MS", "0")
    resilience.reset()
    yield
    resilience.reset()


# ------------------------------------------------------------- ops unit


def test_fp12_tree_prod_groups_matches_pairwise_mul():
    """Per-group halving fold == the same fp12_mul applied by hand —
    exact array equality, since both sides run the identical op in the
    identical order (no canonical-form assumption needed)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(0, 256, FP12_ONE.shape, dtype=np.int32))
    y = jnp.asarray(rng.integers(0, 256, FP12_ONE.shape, dtype=np.int32))
    one = jnp.asarray(FP12_ONE)
    # two groups of 4: [x, y, 1, 1] and [1, 1, 1, 1]
    f = jnp.stack([jnp.stack([x, y, one, one]),
                   jnp.stack([one, one, one, one])])
    got = jb.fp12_tree_prod_groups(f, 4)
    want0 = fp12_mul(fp12_mul(x, one), fp12_mul(y, one))
    want1 = fp12_mul(fp12_mul(one, one), fp12_mul(one, one))
    assert np.array_equal(np.asarray(got[0]), np.asarray(want0))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want1))
    # group_size 1 is the identity
    g1 = jb.fp12_tree_prod_groups(f[:, :1].reshape(2, 1, *x.shape), 1)
    assert np.array_equal(np.asarray(g1), np.asarray(f[:, 0]))


def test_verdict_groups_knob(monkeypatch):
    monkeypatch.setenv("LHTPU_VERDICT_GROUPS", "0")
    assert jb._verdict_groups() == 0
    monkeypatch.setenv("LHTPU_VERDICT_GROUPS", "1")
    assert jb._verdict_groups() == 2        # floor: a group must split work
    monkeypatch.setenv("LHTPU_VERDICT_GROUPS", "3")
    assert jb._verdict_groups() == 4        # rounded up to a power of two
    monkeypatch.setenv("LHTPU_VERDICT_GROUPS", "garbage")
    assert jb._verdict_groups() == 32       # default
    monkeypatch.delenv("LHTPU_VERDICT_GROUPS")
    assert jb._verdict_groups() == 32


# ------------------------------------------- grouped core == scalar core


def _flat_batch(sets, S, K):
    from lighthouse_tpu.crypto.bls.curve import g1_infinity
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
    from lighthouse_tpu.ops.points import g1_to_dev, g2_to_dev

    inf1 = g1_infinity()
    rows = []
    for s in sets:
        row = [pk.point for pk in s.signing_keys]
        row += [inf1] * (K - len(row))
        rows.append(row)
    px, py, pinf = g1_to_dev([p for r in rows for p in r])
    sx, sy, sinf = g2_to_dev([s.signature.point for s in sets])
    mx, my, minf = g2_to_dev([hash_to_g2(s.message) for s in sets])
    return (
        px.reshape(S, K, 48), py.reshape(S, K, 48), pinf.reshape(S, K),
        sx, sy, sinf, mx, my, minf, jb._rand_bits_array(S),
    )


@pytest.mark.parametrize("bad", [(), (1,), (0, 3)])
def test_grouped_core_refines_scalar_core(bad):
    """bool[G] from the grouped core must AND down to the scalar core's
    verdict on identical inputs, and each group verdict must match the
    scalar core run on that group's slice alone (same r slice, so the
    relation is exact, not just probabilistic)."""
    sets = _mixed_sets(4, bad)
    px, py, pinf, sx, sy, sinf, mx, my, minf, r = _flat_batch(sets, 4, 2)
    whole = bool(jb._verify_jit(
        (px, py), pinf, (sx, sy), sinf, (mx, my), minf, r
    ))
    grouped = np.asarray(jb._verify_grouped_jit(
        (px, py), pinf, (sx, sy), sinf, (mx, my), minf, r, n_groups=2
    ))
    assert grouped.shape == (2,)
    assert bool(grouped.all()) == whole == (not bad)
    for g in range(2):
        lo, hi = 2 * g, 2 * g + 2
        assert bool(grouped[g]) == (not any(lo <= b < hi for b in bad))


# ------------------------------------------------- triage device path


@pytest.mark.parametrize(
    "n,bad,max_dispatches",
    [
        (4, (), 1),            # clean: one grouped dispatch, no refinement
        (4, (2,), 2),          # one poisoned group -> one gs=1 re-dispatch
        (4, (0, 1), 2),        # a whole group bad (50%)
        (2, (0, 1), 1),        # 100%: gs=1 in round 1, verdicts exact
        (3, (0,), 2),          # non-pow2 n: the padding group stays clean
    ],
)
def test_triage_matches_python_oracle(triage_env, n, bad, max_dispatches):
    sets = _mixed_sets(n, bad)
    be = jb.JaxBackend()
    before = jb.TRIAGE_DISPATCHES.value()
    got = be.verify_signature_sets_triaged(sets)
    assert got == _oracle(sets)
    tr = jb.dispatch_stage_report()["triage"]
    assert tr["enabled"] and tr["fallback"] is None
    assert tr["dispatches"] == jb.TRIAGE_DISPATCHES.value() - before
    assert tr["dispatches"] <= max_dispatches


def test_triage_zero_repack_on_refinement(triage_env):
    """The acceptance contract at module scale: refinement dispatches
    slice the retained limb grids — pack and hash_to_curve run ONCE for
    the whole triage even though two device dispatches happen."""
    sets = _mixed_sets(4, (2,))
    be = jb.JaxBackend()
    pack0 = _stage_count("pack")
    htc0 = _stage_count("hash_to_curve")
    d0 = jb.TRIAGE_DISPATCHES.value()
    assert be.verify_signature_sets_triaged(sets) == [
        True, True, False, True
    ]
    assert jb.TRIAGE_DISPATCHES.value() - d0 == 2
    assert _stage_count("pack") - pack0 == 1
    assert _stage_count("hash_to_curve") - htc0 == 1
    tr = jb.dispatch_stage_report()["triage"]
    assert tr["rounds"] == 2
    assert tr["clean_groups"] + tr["poisoned_groups"] >= 2


def test_triage_poisoned_duplicate_message(triage_env):
    """ISSUE 10 dedup: all four sets share ONE message and set 2's
    signature is tampered (signed over M_BAD). Dedup collapses the
    hash_to_curve batch to a single distinct row; the per-set verdicts
    must not alias — the tampered set alone fails. Same (S=4, K=2)
    bucket family as the other triage cases."""
    m = b"\x5a" * 32
    sets = []
    for i in range(4):
        signed = M_BAD if i == 2 else m
        if i % 2 == 0:
            sk = SKS[i]
            sets.append(SignatureSet.single_pubkey(
                sk.sign(signed), sk.public_key(), m
            ))
        else:
            a, b = SKS[i], SKS[i + 3]
            agg = AggregateSignature.aggregate([a.sign(signed), b.sign(m)])
            sets.append(SignatureSet.multiple_pubkeys(
                agg, [a.public_key(), b.public_key()], m
            ))
    be = jb.JaxBackend()
    got = be.verify_signature_sets_triaged(sets)
    assert got == [True, True, False, True]
    assert got == _oracle(sets)


def test_triage_pipelined_matches(triage_env, monkeypatch):
    """Chunked triage (2 chunks of 2, gs=1 per chunk) agrees with the
    oracle and stamps the pipeline suffix on the path."""
    monkeypatch.setenv("LHTPU_PIPELINE", "1")
    monkeypatch.setenv("LHTPU_PIPELINE_MIN_SETS", "2")
    monkeypatch.setenv("LHTPU_PIPELINE_CHUNK", "2")
    sets = _mixed_sets(4, (1, 2))  # a poisoned set in EACH chunk
    be = jb.JaxBackend()
    assert be.verify_signature_sets_triaged(sets) == _oracle(sets)
    assert be.last_path.endswith("+pipeline")
    assert jb.dispatch_stage_report()["triage"]["fallback"] is None


def test_triage_structural_rejects_skip_device(triage_env):
    """Infinity signatures are rejected host-side per set; an all-reject
    batch never dispatches."""
    good = _mixed_sets(2)
    inf = SignatureSet.multiple_pubkeys(
        AggregateSignature(), [SKS[0].public_key()], b"\x01" * 32
    )
    be = jb.JaxBackend()
    d0 = jb.TRIAGE_DISPATCHES.value()
    assert be.verify_signature_sets_triaged([inf, inf]) == [False, False]
    assert jb.TRIAGE_DISPATCHES.value() == d0  # no dispatch at all
    assert jb.dispatch_stage_report()["triage"]["structural_rejects"] == 2
    got = be.verify_signature_sets_triaged([good[0], inf, good[1]])
    assert got == [True, False, True]
    assert be.verify_signature_sets_triaged([]) == []


def test_triage_transient_fault_retried_in_stage(triage_env, monkeypatch):
    """A transient during the grouped dispatch is retried in place —
    verdicts unchanged, no fallback."""
    monkeypatch.setenv(
        "LHTPU_FAULT_INJECT", "hash_to_curve:aborted:1"
    )
    r0 = resilience.RETRIES_TOTAL.value(
        stage="hash_to_curve", kind="aborted"
    )
    be = jb.JaxBackend()
    got = be.verify_signature_sets_triaged(_mixed_sets(4, (2,)))
    assert got == [True, True, False, True]
    assert resilience.RETRIES_TOTAL.value(
        stage="hash_to_curve", kind="aborted"
    ) > r0
    assert jb.dispatch_stage_report()["triage"]["fallback"] is None


def test_triage_permanent_fault_degrades_to_host_bisect(
    triage_env, monkeypatch
):
    """A permanent fault inside triage degrades to the budgeted host
    bisection — per-set verdicts still correct, fallback recorded."""
    monkeypatch.setenv("LHTPU_FAULT_INJECT", "pack:mosaic:99")
    be = jb.JaxBackend()
    got = be.verify_signature_sets_triaged(_mixed_sets(4, (2,)))
    assert got == [True, True, False, True]
    tr = jb.dispatch_stage_report()["triage"]
    assert tr["fallback"] and tr["fallback"].startswith("degraded")


def test_triage_disabled_routes_to_host_bisect(triage_env, monkeypatch):
    monkeypatch.setenv("LHTPU_VERDICT_GROUPS", "0")
    be = jb.JaxBackend()
    got = be.verify_signature_sets_triaged(_mixed_sets(4, (2,)))
    assert got == [True, True, False, True]
    assert jb.dispatch_stage_report()["triage"]["fallback"] == "disabled"


# ------------------------------------------------------- api-level route


class _FakeSet:
    def __init__(self, ok):
        self.ok = ok


def _patch_counting_verify(monkeypatch):
    calls = []

    def fake(sets, backend=None):
        calls.append(len(sets))
        return all(s.ok for s in sets)

    monkeypatch.setattr(bls_api, "verify_signature_sets", fake)
    return calls


def test_bisect_all_good_is_one_call(monkeypatch):
    calls = _patch_counting_verify(monkeypatch)
    sets = [_FakeSet(True)] * 8
    assert bls_api.bisect_verify_sets(sets) == [True] * 8
    assert calls == [8]


def test_bisect_single_bad_is_logarithmic(monkeypatch):
    calls = _patch_counting_verify(monkeypatch)
    sets = [_FakeSet(i != 5) for i in range(8)]
    got = bls_api.bisect_verify_sets(sets)
    assert got == [i != 5 for i in range(8)]
    assert calls[0] == 8
    assert len(calls) <= 2 * (8).bit_length() + 3
    # a failing singleton is decided by its own failed batch call, not
    # re-verified linearly
    calls.clear()
    assert bls_api.bisect_verify_sets([_FakeSet(False)]) == [False]
    assert calls == [1]


def test_bisect_budget_exhaustion_goes_linear(monkeypatch):
    calls = _patch_counting_verify(monkeypatch)
    sets = [_FakeSet(False) for _ in range(8)]
    got = bls_api.bisect_verify_sets(sets, budget=[1])
    assert got == [False] * 8
    # budget spent on the first batch call -> per-set linear scan
    assert calls[0] == 8 and set(calls[1:]) == {1} and len(calls) == 9


def test_triaged_api_prefers_backend_method(monkeypatch):
    class _Triager:
        def verify_signature_sets_triaged(self, sets):
            return ["routed"] * len(sets)

    from lighthouse_tpu.crypto.bls import backends

    monkeypatch.setattr(
        backends, "get_backend", lambda name=None: _Triager()
    )
    assert bls_api.verify_signature_sets_triaged([1, 2]) == ["routed"] * 2


def test_triaged_api_python_backend_falls_back_to_bisect(triage_env):
    """The python oracle backend has no grouped dispatch: the api entry
    degrades to host bisection and still returns per-set verdicts."""
    sets = _mixed_sets(4, (2,))
    got = bls_api.verify_signature_sets_triaged(sets, backend="python")
    assert got == [True, True, False, True]


# ------------------------------------------------- full-scale acceptance


@pytest.mark.slow  # two fresh grouped-core compile buckets (~2 min each
# on XLA:CPU) + a 1024-lane Miller loop; the mechanics are pinned fast
# above at (S=4, G=2)
def test_acceptance_1024_sets_one_bad_three_dispatches(monkeypatch):
    """ISSUE 5 acceptance: 1024 sets with exactly one invalid resolve
    per-set in <=3 dispatches with zero pack/hash_to_curve work on the
    re-dispatches."""
    monkeypatch.setenv("LHTPU_VERDICT_GROUPS", "32")
    monkeypatch.setenv("LHTPU_PIPELINE", "0")
    resilience.reset()
    n, bad = 1024, 317
    sets = []
    for i in range(n):
        m = (i + 1).to_bytes(32, "big")
        sk = SKS[i % len(SKS)]
        signed = M_BAD if i == bad else m
        sets.append(
            SignatureSet.single_pubkey(sk.sign(signed), sk.public_key(), m)
        )
    be = jb.JaxBackend()
    d0 = jb.TRIAGE_DISPATCHES.value()
    pack0 = _stage_count("pack")
    htc0 = _stage_count("hash_to_curve")
    got = be.verify_signature_sets_triaged(sets)
    assert got == [i != bad for i in range(n)]
    assert jb.TRIAGE_DISPATCHES.value() - d0 <= 3
    assert _stage_count("pack") - pack0 == 1
    assert _stage_count("hash_to_curve") - htc0 == 1
    tr = jb.dispatch_stage_report()["triage"]
    assert tr["rounds"] == 2 and tr["poisoned_groups"] == 2
