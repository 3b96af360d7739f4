"""Multi-chip (virtual 8-CPU-device mesh) tests for the sharded verifier
and the driver entry points in __graft_entry__.py.

Shapes here deliberately match dryrun_multichip(4) so the in-memory jit
cache shares compiles between the two tests.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest


def big_stack_thread(fn):
    """Run the test body on a freshly-allocated 512 MB-stack thread.

    The shard_map pipeline's XLA compile recurses deeply. On the main
    thread the stack must GROW to absorb it, and late in a long pytest
    process an mmap can sit just below the stack ceiling — growth then
    SIGSEGVs (observed: full-suite-only crashes in
    backend_compile_and_load; isolation always passed). A pthread stack
    is preallocated up front, so no growth, no collision."""
    import functools
    import threading

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        result: list = []
        old = threading.stack_size(512 * 1024 * 1024)
        try:
            t = threading.Thread(
                target=lambda: result.append(_call(fn, args, kwargs))
            )
            t.start()
            t.join()
        finally:
            threading.stack_size(old)
        if result and isinstance(result[0], BaseException):
            raise result[0]

    def _call(f, a, k):
        try:
            f(*a, **k)
            return None
        except BaseException as e:  # noqa: BLE001 - re-raised on the main thread
            return e

    return wrapper

from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet, AggregateSignature
from lighthouse_tpu.crypto.bls.curve import g1_infinity
from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
from lighthouse_tpu.jax_backend import _rand_bits_array
from lighthouse_tpu.ops.points import g1_to_dev, g2_to_dev
from lighthouse_tpu.parallel import build_sharded_verifier, make_mesh


def _flat_batch(sets, S, K):
    """SignatureSets -> the flat array tuple the sharded verifier takes."""
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
        sx, sy, sinf, mx, my, minf, _rand_bits_array(S),
    )


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
@big_stack_thread
def test_sharded_verifier_matches_oracle():
    S, K = 4, 4
    sks = [SecretKey.from_int(i + 3) for i in range(5)]
    msgs = [bytes([i]) * 32 for i in range(4)]
    sets = [
        SignatureSet.single_pubkey(sks[0].sign(msgs[0]), sks[0].public_key(), msgs[0]),
        SignatureSet.multiple_pubkeys(
            AggregateSignature.aggregate([sks[1].sign(msgs[1]), sks[2].sign(msgs[1])]),
            [sks[1].public_key(), sks[2].public_key()],
            msgs[1],
        ),
        SignatureSet.single_pubkey(sks[3].sign(msgs[2]), sks[3].public_key(), msgs[2]),
        SignatureSet.single_pubkey(sks[4].sign(msgs[3]), sks[4].public_key(), msgs[3]),
    ]

    mesh = make_mesh(4, mp=2)  # dp=2, mp=2
    fn = jax.jit(build_sharded_verifier(mesh))

    good = _flat_batch(sets, S, K)
    assert bool(fn(*good)[0])

    # Tamper: swap two signatures -> the RLC product can no longer be one.
    bad = list(good)
    sx = np.array(good[3])
    sx[[0, 1]] = sx[[1, 0]]
    bad[3] = sx
    assert not bool(fn(*bad)[0])


@pytest.mark.slow  # the driver runs this exact gate itself every round;
# in-suite it is regression cover for gate EDITS, not routine CI (129 s)
@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
@big_stack_thread
def test_graft_dryrun_multichip():
    import __graft_entry__

    __graft_entry__.dryrun_multichip(4)


@pytest.mark.slow  # interpret-mode fused pipeline: the TRACE alone costs
# ~17 min cold on this host (kernel bodies inline; tracing is uncacheable)
@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
@big_stack_thread
def test_sharded_fused_matches_oracle():
    """VERDICT r1 item 7: the PRODUCTION (fused Pallas, interpret mode on
    CPU) verifier sharded over a 4-chip dp mesh, vs the oracle verdicts —
    one code path from verify_signature_sets to N chips."""
    from lighthouse_tpu.parallel import build_sharded_fused_verifier, make_mesh

    S, K = 4, 2
    sks = [SecretKey.from_int(i + 51) for i in range(5)]
    msgs = [bytes([i + 9]) * 32 for i in range(4)]
    sets = [
        SignatureSet.single_pubkey(sks[0].sign(msgs[0]), sks[0].public_key(), msgs[0]),
        SignatureSet.multiple_pubkeys(
            AggregateSignature.aggregate([sks[1].sign(msgs[1]), sks[2].sign(msgs[1])]),
            [sks[1].public_key(), sks[2].public_key()],
            msgs[1],
        ),
        SignatureSet.single_pubkey(sks[3].sign(msgs[2]), sks[3].public_key(), msgs[2]),
        SignatureSet.single_pubkey(sks[4].sign(msgs[3]), sks[4].public_key(), msgs[3]),
    ]
    mesh = make_mesh(4, mp=1)
    fn = jax.jit(build_sharded_fused_verifier(mesh))

    good = _flat_batch(sets, S, K)
    assert bool(fn(*good)[0])

    bad = list(good)
    sx = np.array(good[3])
    sx[[0, 1]] = sx[[1, 0]]
    bad[3] = sx
    assert not bool(fn(*bad)[0])


@pytest.mark.slow  # interpret-mode fused pipeline (see above)
@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
@big_stack_thread
def test_sharded_fused_indexed_matches_oracle():
    """VERDICT r2 item 4: indexed gather + shard_map + fused kernels as
    ONE composed path — the table is replicated per chip, the batch ships
    only validator indices, and the verdict still matches the oracle."""
    import jax.numpy as jnp

    from lighthouse_tpu import blsrt
    from lighthouse_tpu.parallel import (
        build_sharded_fused_indexed_verifier,
        make_mesh,
    )

    S, K = 4, 2
    sks = [SecretKey.from_int(i + 71) for i in range(5)]
    msgs = [bytes([i + 17]) * 32 for i in range(4)]
    sets = [
        SignatureSet.single_pubkey(
            sks[0].sign(msgs[0]), sks[0].public_key(), msgs[0], index=0
        ),
        SignatureSet.multiple_pubkeys(
            AggregateSignature.aggregate([sks[1].sign(msgs[1]), sks[2].sign(msgs[1])]),
            [sks[1].public_key(), sks[2].public_key()],
            msgs[1],
            indices=[1, 2],
        ),
        SignatureSet.single_pubkey(
            sks[3].sign(msgs[2]), sks[3].public_key(), msgs[2], index=3
        ),
        SignatureSet.single_pubkey(
            sks[4].sign(msgs[3]), sks[4].public_key(), msgs[3], index=4
        ),
    ]
    table = blsrt.DevicePubkeyTable()
    table.append_pubkeys([sk.public_key() for sk in sks])
    tx, ty = table.device_arrays()
    idx, lane_inf = table.gather_args(
        [s.signing_key_indices for s in sets], K
    )

    mesh = make_mesh(4, mp=1)
    fn = jax.jit(build_sharded_fused_indexed_verifier(mesh))

    base = _flat_batch(sets, S, K)
    sx, sy, sinf, mx, my, minf, r_bits = base[3:]
    good = (tx, ty, jnp.asarray(idx), jnp.asarray(lane_inf),
            sx, sy, sinf, mx, my, minf, r_bits)
    assert bool(fn(*good)[0])

    bad = list(good)
    sx_np = np.array(sx)
    sx_np[[0, 1]] = sx_np[[1, 0]]
    bad[4] = sx_np
    assert not bool(fn(*bad)[0])


@pytest.mark.slow  # interpret-mode fused pipeline (see above)
@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
@big_stack_thread
def test_backend_sharded_indexed_path_engages(monkeypatch):
    """The backend must NOT drop to one chip when the HBM table engages
    (VERDICT r2 weak #2): with sharding forced on, index-carrying sets
    must take the composed sharded-indexed program — including when the
    set count does not divide the device count (padding, not bail-out)."""
    from lighthouse_tpu import blsrt
    from lighthouse_tpu.jax_backend import JaxBackend

    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "1")
    monkeypatch.setenv("LHTPU_FUSED_VERIFY", "1")

    sks = [SecretKey.from_int(i + 91) for i in range(3)]
    msgs = [bytes([i + 31]) * 32 for i in range(3)]
    sets = [
        SignatureSet.single_pubkey(
            sk.sign(m), sk.public_key(), m, index=i
        )
        for i, (sk, m) in enumerate(zip(sks, msgs))
    ]
    table = blsrt.DevicePubkeyTable()
    table.append_pubkeys([sk.public_key() for sk in sks])
    blsrt.set_device_table(table)
    try:
        backend = JaxBackend()
        # 3 sets -> S pads to 4 then to 8 (the device count): the padded
        # lanes are infinity sets and must not disturb the verdict.
        assert backend.verify_signature_sets(sets)
        assert backend.last_path == "sharded-indexed"
        bad = [
            SignatureSet.single_pubkey(
                sets[0].signature, sks[1].public_key(), msgs[0], index=1
            ),
            sets[1],
        ]
        assert not backend.verify_signature_sets(bad)
    finally:
        blsrt.set_device_table(None)


@pytest.mark.slow  # the driver runs this exact gate itself every round (186 s)
@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
@big_stack_thread
def test_graft_dryrun_multichip_8():
    """The driver's exact 8-device gate (VERDICT r1: rc=124 timeout).

    dryrun_multichip asserts the sharded verdict is True, so this is a
    correctness check of the dp=4 x mp=2 collectives, under the dryrun's
    fast-compile config."""
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


def test_graft_entry_shapes():
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    # Don't compile here (test_jax_backend compiles the same program);
    # just validate structure.
    assert callable(fn)
    (pk, pk_inf, sig, sig_inf, msg, msg_inf, r_bits) = args
    assert pk[0].shape == (2, 2, 48) and r_bits.shape == (2, 64)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
@big_stack_thread
def test_fused_collectives_match_host():
    """FAST-tier certification of the fused path's mesh collectives
    WITHOUT the Pallas kernel bodies (whose interpret-mode trace costs
    ~17 min and lives in the slow tier): runs the exact helpers
    _verify_core_fused(axis=...) composes — mesh_all_ok (psum),
    mesh_fold_point (all_gather + group-law fold), mesh_fold_fp12
    (all_gather + Fp12 fold), mesh_rank0_lane (axis_index masking) —
    inside shard_map on the 8-device mesh, against the same math run
    single-device and against host group law."""
    from jax.sharding import PartitionSpec as P

    from lighthouse_tpu.crypto.bls.curve import g2_generator
    from lighthouse_tpu.jax_backend import (
        mesh_all_ok,
        mesh_fold_fp12,
        mesh_fold_point,
        mesh_rank0_lane,
    )
    from lighthouse_tpu.ops.pairing import fp12_fold_scan
    from lighthouse_tpu.ops.points import FP2_OPS, pt_from_affine, pt_to_affine
    from lighthouse_tpu.ops.tower import fp12_to_dev
    from lighthouse_tpu.parallel import make_mesh

    from jax import shard_map

    n = 8
    mesh = make_mesh(n, mp=1)

    # --- mesh_all_ok: one bad lane anywhere -> global False -------------
    def all_ok_prog(lanes):
        return mesh_all_ok(lanes, "dp")[None]

    f = jax.jit(shard_map(all_ok_prog, mesh=mesh, in_specs=P("dp"),
                          out_specs=P("dp"), check_vma=False))
    lanes = np.ones((n, 4), bool)
    assert bool(np.asarray(f(lanes)).all())
    lanes[5, 2] = False
    assert not bool(np.asarray(f(lanes)).any())

    # --- mesh_fold_point: fold of per-chip [k]G2 partials == [sum k]G2,
    # vs the HOST group law ---------------------------------------------
    ks = list(range(1, n + 1))
    pts = [g2_generator().mul(k) for k in ks]
    px, py, pinf = g2_to_dev(pts)

    def fold_prog(x, y, inf):
        j = pt_from_affine(FP2_OPS, x, y, inf)
        part = tuple(c[0] for c in j)  # this chip's single point
        acc = mesh_fold_point(FP2_OPS, part, "dp")
        return pt_to_affine(FP2_OPS, tuple(c[None] for c in acc))

    g = jax.jit(shard_map(fold_prog, mesh=mesh,
                          in_specs=(P("dp"), P("dp"), P("dp")),
                          out_specs=(P(), P(), P()), check_vma=False))
    ax, ay, ainf = g(px, py, pinf)
    ex, ey, einf = g2_to_dev([g2_generator().mul(sum(ks))])
    assert not bool(np.asarray(ainf)[0])
    assert np.array_equal(np.asarray(ax)[0], ex[0])
    assert np.array_equal(np.asarray(ay)[0], ey[0])

    # --- mesh_fold_fp12: mesh fold == the same fold single-device
    # (collective wiring under test; the field math itself is covered by
    # test_ops_tower/test_bls_pairing) ----------------------------------
    rng = np.random.RandomState(7)

    def rand_fp12():
        c = [(int(rng.randint(1, 2**30)), int(rng.randint(1, 2**30)))
             for _ in range(6)]
        return fp12_to_dev(c[:3], c[3:])

    vals = np.stack([rand_fp12() for _ in range(n)])  # [n, 2, 3, 2, 48]

    def fp12_prog(x):
        folded = mesh_fold_fp12(x[0][None], "dp")[0]
        fin = (~mesh_rank0_lane("dp")).astype(jnp.int32)
        n_fin = jax.lax.psum(fin.sum(), "dp")
        return folded[None], n_fin[None]

    h = jax.jit(shard_map(fp12_prog, mesh=mesh, in_specs=P("dp"),
                          out_specs=(P(), P("dp")), check_vma=False))
    folded, n_fin = h(vals)
    expect = jax.jit(fp12_fold_scan, static_argnums=1)(
        jnp.asarray(vals), n
    )
    assert np.array_equal(np.asarray(folded)[0], np.asarray(expect))
    # rank-0 masking: exactly one finite check-pair lane across the mesh
    assert int(np.asarray(n_fin)[0]) == 1


@pytest.mark.slow  # one fresh grouped-core compile inside shard_map
# (~2 min on XLA:CPU); the single-device grouped core is pinned fast in
# tests/test_triage.py
@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 virtual devices")
@big_stack_thread
def test_sharded_grouped_verifier_matches_oracle():
    """Grouped verdicts across chips (ISSUE 5): groups are chip-local,
    the only collective is the verdict-lane all_gather, so bool[G] must
    name exactly the poisoned group — in axis order — on a CPU mesh."""
    from lighthouse_tpu.parallel import build_sharded_grouped_verifier

    S, K, G = 4, 4, 2
    sks = [SecretKey.from_int(i + 3) for i in range(5)]
    msgs = [bytes([i]) * 32 for i in range(4)]
    sets = [
        SignatureSet.single_pubkey(sks[0].sign(msgs[0]), sks[0].public_key(), msgs[0]),
        SignatureSet.multiple_pubkeys(
            AggregateSignature.aggregate([sks[1].sign(msgs[1]), sks[2].sign(msgs[1])]),
            [sks[1].public_key(), sks[2].public_key()],
            msgs[1],
        ),
        SignatureSet.single_pubkey(sks[3].sign(msgs[2]), sks[3].public_key(), msgs[2]),
        SignatureSet.single_pubkey(sks[4].sign(msgs[3]), sks[4].public_key(), msgs[3]),
    ]

    mesh = make_mesh(2, mp=1)  # dp=2: one group of 2 sets per chip
    fn = jax.jit(build_sharded_grouped_verifier(mesh, G))

    good = _flat_batch(sets, S, K)
    ok = np.asarray(fn(*good))
    assert ok.shape == (G,) and ok.all()

    # Tamper set 2 (group 1): only that group's verdict flips.
    bad = list(good)
    sx = np.array(good[3])
    sx[[2, 3]] = sx[[3, 2]]
    bad[3] = sx
    ok = np.asarray(fn(*bad))
    assert ok.tolist() == [True, False]


# ------------------------------------------------ dispatch engine (ISSUE 8)
# Pure-host checks of the engine layer this module's sharded programs now
# serve through: builder wiring and the jitted-program cache. The routing/
# parity/fault contracts live in tests/test_parallel_dispatch.py.


def test_classic_sharded_indexed_builders_construct():
    """The classic (pure-XLA) indexed sharded builders exist and wrap
    without tracing — they are what serves indexed dispatch on CPU
    meshes, sharing the fused variants' flat argument convention."""
    from lighthouse_tpu.parallel import (
        build_sharded_grouped_indexed_verifier,
        build_sharded_indexed_verifier,
    )

    mesh = make_mesh(2, mp=1)
    assert callable(build_sharded_indexed_verifier(mesh))
    assert callable(build_sharded_grouped_indexed_verifier(mesh, 2))


def test_engine_program_cache_is_keyed_and_stable():
    """sharded_verify_fn/sharded_grouped_fn return the SAME jitted
    program for the same key (compiles are the expensive part — the
    cache must not rebuild per dispatch) and distinct programs for
    distinct keys."""
    from lighthouse_tpu.parallel import engine

    a = engine.sharded_verify_fn(2, fused=False)
    assert a is engine.sharded_verify_fn(2, fused=False)
    assert a is not engine.sharded_verify_fn(2, fused=False, indexed=True)
    g = engine.sharded_grouped_fn(2, 2, fused=False)
    assert g is engine.sharded_grouped_fn(2, 2, fused=False)
    assert g is not engine.sharded_grouped_fn(2, 4, fused=False)
    with pytest.raises(AssertionError):
        engine.sharded_verify_fn(2, fused=False, with_msm=True)


def test_engine_topology_sees_forced_host_mesh():
    """The conftest-forced 8-device host platform IS the discovered
    topology (power-of-two floor of the visible count)."""
    from lighthouse_tpu.parallel import engine

    top = engine.topology()
    assert top.visible == len(jax.devices())
    assert top.n_devices & (top.n_devices - 1) == 0
    assert top.n_devices <= top.visible
