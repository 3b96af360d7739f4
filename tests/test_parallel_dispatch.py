"""Sharded serving on CPU CI (ISSUE 8): the dispatch engine routes
production ``verify_signature_sets`` calls onto the 8 forced host
devices and the verdicts stay bit-identical to single-chip.

Compile budget: every backend test here shares exactly TWO device
programs — the classic sharded verifier at (S=8, K=1, dp=8) and the
single-chip classic verifier at (S=8, K=1); all poison rates, pad-waste
shapes, pipeline chunks and fault drills are sized to land in those
buckets. The persistent cache absorbs the *compile*, but the TRACE of
the pairing pipeline (and its shard_map wrapping) still costs minutes
per process on the 1-core CI host — so, like the sharded oracle-parity
tests in test_parallel.py, every test that actually dispatches is
@slow; the fast tier keeps the pure-host engine plan/breaker/floor/
classification units. `pytest -m slow tests/test_parallel_dispatch.py`
runs the dispatch set; bench.py --devices re-validates the same
contract end-to-end on every sweep.
"""

import os

import jax
import numpy as np
import pytest


def big_stack_thread(fn):
    """Run the test body on a freshly-allocated 512 MB-stack thread
    (same rationale as tests/test_parallel.py: the shard_map pipeline's
    XLA compile recurses deeply and late-process main-thread stack
    growth can SIGSEGV against an adjacent mmap)."""
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
        except BaseException as e:  # noqa: BLE001 - re-raised on main thread
            return e

    return wrapper


from lighthouse_tpu.common import pipeline, resilience  # noqa: E402
from lighthouse_tpu.crypto.bls.api import (  # noqa: E402
    SecretKey,
    SignatureSet,
)
from lighthouse_tpu.crypto.bls.backends import get_backend  # noqa: E402
from lighthouse_tpu.parallel import engine  # noqa: E402

SKS = [SecretKey.from_int(i + 201) for i in range(16)]
PKS = [sk.public_key() for sk in SKS]
MSGS = [bytes([i + 40]) * 32 for i in range(16)]


def _sets(n: int, poison=()):
    """n single-pubkey sets (K=1 — the cheapest compile bucket); a
    poisoned index signs against the WRONG pubkey, so its set must fail
    while every other verdict is unaffected."""
    out = []
    for i in range(n):
        pk = PKS[(i + 1) % n] if i in poison else PKS[i]
        out.append(
            SignatureSet.single_pubkey(SKS[i].sign(MSGS[i]), pk, MSGS[i])
        )
    return out


# ------------------------------------------------------------ engine (host)


def test_topology_pow2_floor(monkeypatch):
    """LHTPU_DEVICES caps the mesh and the result is floored to a power
    of two (padded S must keep power-of-two per-chip slices)."""
    monkeypatch.delenv("LHTPU_DEVICES", raising=False)
    visible = len(jax.devices())
    top = engine.topology()
    assert top.visible == visible
    assert top.n_devices == 1 << (visible.bit_length() - 1)
    monkeypatch.setenv("LHTPU_DEVICES", "6")
    assert engine.topology().n_devices == min(4, top.n_devices)
    monkeypatch.setenv("LHTPU_DEVICES", "1")
    assert engine.topology().n_devices == 1
    monkeypatch.setenv("LHTPU_DEVICES", "not-a-number")
    assert engine.topology().n_devices == top.n_devices


def test_plan_routing_and_padding(eight_host_devices, monkeypatch):
    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "1")
    # Forced: shards regardless of batch size, pads S up to the mesh.
    p = engine.plan(3, 4)
    assert (p.devices, p.S, p.pad_sets, p.reason) == (8, 8, 5, "forced")
    # S already divisible: unchanged.
    p = engine.plan(16, 16)
    assert (p.devices, p.S, p.reason) == (8, 16, "forced")
    # Rung overrides stay single-chip (deterministic degraded rungs).
    assert engine.plan(16, 16, path_override="classic").reason == \
        "rung-override"
    # Groups must divide the mesh.
    assert engine.plan(16, 16, n_groups=4).reason == "groups-indivisible"
    assert engine.plan(16, 16, n_groups=8).devices == 8
    # Kill switch.
    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "0")
    assert engine.plan(16, 16).reason == "disabled"
    # Default on a CPU host: single-chip (historical CI behavior).
    monkeypatch.delenv("LHTPU_SHARDED_VERIFY", raising=False)
    assert engine.plan(4096, 4096).reason == "cpu-default"
    # LHTPU_DEVICES=1 beats forcing.
    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "1")
    monkeypatch.setenv("LHTPU_DEVICES", "1")
    assert engine.plan(16, 16).reason == "one-device"


def test_plan_breaker_gating(eight_host_devices, monkeypatch):
    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "1")
    assert engine.plan(16, 16).devices == 8
    # A permanent sharded fault opens the breaker: plans degrade.
    resilience.breaker(engine.BREAKER).record_failure(permanent=True)
    assert engine.plan(16, 16).reason == "breaker-open"
    assert resilience.breaker_states()["sharded"] == "open"
    # Healing (a successful half-open probe) re-promotes.
    resilience.breaker(engine.BREAKER).record_success()
    assert engine.plan(16, 16).devices == 8


def test_pipeline_chunk_floor(eight_host_devices, monkeypatch):
    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "1")
    monkeypatch.delenv("LHTPU_PIPELINE_CHUNK", raising=False)
    monkeypatch.setenv("LHTPU_SHARD_MIN_SETS", "128")
    # floor = 8 chips * 128 sets -> chunks never shrink below 1024.
    assert engine.chunk_floor() == 1024
    assert pipeline.chunk_size(512) == 1024
    # An explicit chunk override always wins (tests pin geometries).
    monkeypatch.setenv("LHTPU_PIPELINE_CHUNK", "8")
    assert pipeline.chunk_size(512) == 8
    # Sharding off: the historical sizing is untouched.
    monkeypatch.delenv("LHTPU_PIPELINE_CHUNK", raising=False)
    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "0")
    assert engine.chunk_floor() == 1
    assert pipeline.chunk_size(4096) == 1024


# ----------------------------------------------------- backend, 8-way mesh


@pytest.mark.slow  # first trace of the sharded + single-chip pairing
# programs costs minutes on the 1-core host even with a warm disk cache
@big_stack_thread
def test_sharded_parity_across_poison_rates(eight_host_devices,
                                            monkeypatch):
    """Oracle parity vs single-chip at poison rates 0% / one set / 25% /
    100%: the sharded verdict must be bit-identical to the single-chip
    verdict AND to the pure-python oracle, on the same 8-set batch."""
    from lighthouse_tpu import jax_backend as jb

    be = get_backend("jax")
    for poison in ((), (3,), (0, 2), tuple(range(8))):
        sets = _sets(8, poison)
        # Ground truth by construction (sets are signed correctly and
        # poisoned by pubkey swap); the pure-python oracle agrees but
        # costs seconds of bigint pairing per set, so the fast tier
        # asserts against the construction directly.
        expect = len(poison) == 0

        monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "1")
        sharded = bool(be.verify_signature_sets(sets))
        assert be.last_path == "sharded-classic"
        par = jb.dispatch_stage_report()["parallel"]
        assert par["devices"] == 8 and par["sets_per_chip"] == 1
        assert par["pad_waste"] == 0.0 and par["mesh"] == [8, 1]

        monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "0")
        single = bool(be.verify_signature_sets(sets))
        assert be.last_path == "classic"
        assert jb.dispatch_stage_report()["parallel"]["devices"] == 1

        assert sharded == single == expect, (
            f"poison={poison}: sharded={sharded} single={single} "
            f"oracle={expect}"
        )


@pytest.mark.slow  # shares the parity test's traced programs (see above)
@big_stack_thread
def test_sharded_pad_waste_edges(eight_host_devices, monkeypatch):
    """n_sets < devices and non-multiple batches: pad to the mesh, keep
    the verdict, report the waste (same S=8 compile bucket)."""
    from lighthouse_tpu import jax_backend as jb

    be = get_backend("jax")
    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "1")

    assert be.verify_signature_sets(_sets(3))
    par = jb.dispatch_stage_report()["parallel"]
    assert par["devices"] == 8 and par["padded_sets"] == 8
    assert par["sets_per_chip"] == 1 and par["pad_waste"] == 0.625

    assert not be.verify_signature_sets(_sets(3, poison=(1,)))

    assert be.verify_signature_sets(_sets(5))
    par = jb.dispatch_stage_report()["parallel"]
    assert par["padded_sets"] == 8 and par["pad_waste"] == 0.375


@pytest.mark.slow  # shares the parity test's traced programs (see above)
@big_stack_thread
def test_pipelined_sharded_verdicts_under_fault(eight_host_devices,
                                                monkeypatch):
    """Pipelined x sharded composition under LHTPU_FAULT_INJECT: two
    8-set chunks through the sharded program, a transient fault on the
    first sharded dispatch retried in place, verdicts equal to ground
    truth (good batch True, poisoned chunk False)."""
    from lighthouse_tpu import jax_backend as jb

    be = get_backend("jax")
    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "1")
    monkeypatch.setenv("LHTPU_PIPELINE", "1")
    monkeypatch.setenv("LHTPU_PIPELINE_MIN_SETS", "4")
    monkeypatch.setenv("LHTPU_PIPELINE_CHUNK", "8")
    monkeypatch.setenv(
        "LHTPU_FAULT_INJECT", "sharded_dispatch:aborted:1"
    )

    sets = _sets(16)
    assert bool(be.verify_signature_sets(sets))
    assert be.last_path == "sharded-classic+pipeline"
    rep = jb.dispatch_stage_report()
    assert rep["retries"].get("dispatch:aborted", 0) >= 1
    assert rep["parallel"]["devices"] == 8
    assert rep["pipeline"]["chunks"] == 2

    monkeypatch.setenv("LHTPU_FAULT_INJECT", "")
    assert not bool(be.verify_signature_sets(_sets(16, poison=(11,))))


@pytest.mark.slow  # shares the parity test's traced programs (see above)
@big_stack_thread
def test_sharded_permanent_fault_degrades_to_single_chip(
        eight_host_devices, monkeypatch):
    """A permanent fault (and a simulated chip loss) inside the sharded
    dispatch stage circuit-breaks down to single-chip: no crash, the
    verdict is still correct, detail.path records the fallback rung,
    and the sharded breaker opens so later plans skip the mesh until
    re-promotion."""
    from lighthouse_tpu import jax_backend as jb

    be = get_backend("jax")
    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "1")
    # mosaic classifies to the "lowering" kind; chip_loss keeps its own.
    for kind, label in (("mosaic", "lowering"), ("chip_loss", "chip_loss")):
        resilience.reset()
        engine.reset()
        monkeypatch.setenv(
            "LHTPU_FAULT_INJECT", f"sharded_dispatch:{kind}:1"
        )
        assert bool(be.verify_signature_sets(_sets(8)))
        assert be.last_path == "classic+sharded-fallback"
        rep = jb.dispatch_stage_report()
        assert rep["parallel"]["devices"] == 1
        assert rep["parallel"]["reason"] == "degraded:" + label
        assert rep["breaker"]["sharded"] == "open"
        assert rep["degraded"].get("sharded", 0) >= 1

        # Breaker open: the next dispatch plans single-chip up front —
        # and still verifies correctly (including a poisoned set).
        monkeypatch.setenv("LHTPU_FAULT_INJECT", "")
        assert not bool(be.verify_signature_sets(_sets(8, poison=(2,))))
        assert be.last_path == "classic"
        assert jb.dispatch_stage_report()["parallel"]["reason"] == \
            "breaker-open"


def test_chip_loss_classifies_permanent():
    exc = resilience._FAULT_FACTORIES["chip_loss"]()
    assert resilience.classify(exc) == (resilience.PERMANENT, "chip_loss")


# ------------------------------------------------------------ triage (slow)


@pytest.mark.slow  # one fresh grouped-core compile inside shard_map at
# dp=8 plus a tiny single-chip refinement bucket (~minutes on XLA:CPU)
@big_stack_thread
def test_sharded_grouped_triage_refinement_contract(eight_host_devices,
                                                    monkeypatch):
    """Grouped-triage per-shard refinement dispatch-count contract:
    round 1 runs SHARDED grouped verdicts (groups divide the mesh), the
    refinement round slices the retained packs to the poisoned group —
    2 sets, 2 groups, indivisible by 8 chips — and re-dispatches
    single-chip WITHOUT re-packing: exactly 2 dispatches total, exact
    per-set verdicts."""
    from lighthouse_tpu import jax_backend as jb

    be = get_backend("jax")
    monkeypatch.setenv("LHTPU_SHARDED_VERIFY", "1")
    monkeypatch.setenv("LHTPU_VERDICT_GROUPS", "8")

    sets = _sets(16, poison=(5,))
    verdicts = be.verify_signature_sets_triaged(sets)
    assert [bool(v) for v in verdicts] == [i != 5 for i in range(16)]

    tri = jb.dispatch_stage_report()["triage"]
    assert tri["enabled"] and tri["dispatches"] == 2
    # Round 1 ran on the mesh; the report's parallel snapshot reflects
    # the LAST dispatch (the single-chip refinement).
    batches = {
        lbl["path"]: v for lbl, v in jb.DISPATCH_BATCHES.items()
    }
    assert batches.get("sharded-classic+triage", 0) >= 1
    assert jb.dispatch_stage_report()["parallel"]["reason"] in (
        "groups-indivisible", "pack-indivisible"
    )
