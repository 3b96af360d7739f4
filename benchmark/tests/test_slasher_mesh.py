"""The sharded slasher cell on the CPU: its numpy reference against the
plain one, the reference's speed at a window's size, the harness's run
with the engine broken underneath (each fault, and the control, turns
``correct`` false) and with the program's engine, and the cell's three
readers on hand-made traces and spans."""

import os
import sys
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import conftest  # noqa: E402,F401  (puts the benchmark on sys.path)
import slasher_fakes as fakes  # noqa: E402
from harness import context, engine_spans, plane_bytes, spec  # noqa: E402
from harness import slasher_lag_reference as lag  # noqa: E402
from harness import slasher_reference as sr  # noqa: E402
from harness import trace, votes  # noqa: E402

MESH_MIX = dict(fakes.TINY_MIX, kind="slasher_mesh")
SHAPE = votes.Shape.from_config(fakes.TINY_CONFIG, MESH_MIX)


def _reader(name):
    return spec.load_module(
        os.path.join(spec.BENCH_DIR, "metrics", name + ".py"),
        "metric_" + name)


def _plain(rows, history):
    return np.array(sr.Reference(history).process(rows.tolist()), np.uint8)


# ------------------------------------------------------------- reference
@pytest.mark.parametrize("seed", [5, 2**31 + 17, 4100000051])
def test_lag_reference_equals_the_plain_one_on_the_traffic(seed):
    s = votes.Votes(seed, SHAPE)
    rows = np.concatenate([s.old()] + [s.batch(k) for k in range(24)])
    want = _plain(rows, SHAPE.history_length)
    assert (lag.codes(rows, SHAPE.history_length) == want).all()
    for bit in (sr.SURROUNDS, sr.SURROUNDED, sr.DOUBLE):
        assert (want & bit).any()


def test_lag_reference_at_the_history_edge():
    """Old votes whose targets lie just out of the history, or just in
    it, as the newest target (a vote's own included) moves."""
    h = 64
    rows = np.array([
        [1, 10, 20], [2, 10, 20], [3, 10, 21], [4, 10, 21],  # old votes
        [5, 10, 21], [6, 10, 20], [7, 10, 21], [8, 10, 21],
        [9, 83, 84],   # now 84: a target of 20 is out, 21 in
        [1, 9, 21],    # would surround (10, 20): out
        [2, 11, 19],   # would be surrounded by (10, 20): out
        [3, 9, 22],    # surrounds (10, 21)
        [4, 11, 20],   # surrounded by (10, 21)
        [5, 10, 21],   # the same vote again: a double
        [6, 10, 20],   # the same again, out
        [8, 9, 86],    # its own target makes now 86: (10, 21) is out
        [9, 84, 85],
        [7, 9, 22],    # now 86: out
    ])
    want = _plain(rows, h)
    assert want.tolist()[9:] == [0, 0, sr.SURROUNDS, sr.SURROUNDED,
                                 sr.DOUBLE, 0, 0, 0, 0]
    assert (lag.codes(rows, h) == want).all()


def test_lag_reference_on_random_votes():
    """Many votes of a few validators, far more than the traffic gives
    one of them, sources and targets in any order, a short history."""
    r = np.random.default_rng(20260025)
    for n, validators, h in ((3000, 7, 32), (5000, 400, 64), (1, 1, 8)):
        s = r.integers(0, 200, n)
        rows = np.stack([r.integers(0, validators, n), s,
                         s + r.integers(0, 40, n)], axis=1)
        assert (lag.codes(rows, h) == _plain(rows, h)).all()
    assert lag.codes(np.zeros((0, 3), np.int64), 8).tolist() == []


def test_lag_reference_replays_20m_votes_in_under_30_s():
    shape = votes.Shape.from_config(
        {"validators": 2**20, "slots_per_epoch": 32, "history_length": 4096,
         "validator_chunk_size": 256},
        dict(MESH_MIX, old_per_chunk=3, doubles=32, surrounds=32))
    s = votes.Votes(2**31 + 25, shape)
    rows = np.concatenate([s.old()] + [s.batch(k) for k in range(610)])
    assert len(rows) > 20_000_000
    t = time.perf_counter()
    got = lag.codes(rows, shape.history_length)
    took = time.perf_counter() - t
    print(f"lag reference: {len(rows):,} votes in {took:.2f} s")
    assert took < 30
    assert np.count_nonzero(got) >= 610 * 64


# --------------------------------------------------------------- the run
@pytest.fixture
def cell(tmp_path):
    return fakes.tiny_cell(tmp_path, mix={"kind": "slasher_mesh"})


def test_sound_system_is_correct(cell):
    out = fakes.drive(cell, fakes.FakeSystem)
    assert cell.runner.__name__.endswith("slasher_mesh")
    assert out["correct"] is True
    assert out["checks"]["code_mismatches"] == {"value": 0, "limit": 0}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "votes_per_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", fakes.FAULTS)
def test_fault_is_caught(cell, fault):
    out = fakes.drive(cell, fakes.with_fault(fault))
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["code_mismatches"]["value"] > 0


def test_control_is_not_correct(cell):
    for seed in (101, 202, 303):
        out = fakes.drive(cell, fakes.control(), seed=seed)
        assert out["correct"] is False
        assert out["checks"]["code_mismatches"]["value"] >= 1


def test_the_program_engine_through_the_run(cell, monkeypatch):
    """The runner's own system, the program's engine on the CPU (its
    device path, XLA on the host) with the planes on the cell's chips,
    answers every vote as the reference does, with no compile in the
    window."""
    jax = pytest.importorskip("jax")

    def on_cpu(ctx):
        ctx.device = jax.devices()[0]
        ctx.dev_info = {"platform": "cpu", "kind": "cpu", "count": 1}

    monkeypatch.setattr(context.Context, "open_device", on_cpu)
    out = fakes.drive(cell, cell.runner.MeshSystem, seconds=0.5)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_a_window_compile_fails_the_run(cell, monkeypatch):
    monkeypatch.setattr(context.Context, "window_compiles", lambda ctx: 1)
    with pytest.raises(RuntimeError, match="compiles inside the window"):
        fakes.drive(cell, fakes.FakeSystem)


# --------------------------------------------------------------- readers
def _rows(busy_per_device, lo=1_000, hi=11_000):
    """A trace of ``len(busy_per_device)`` chips, each busy for the
    given nanoseconds inside a window of [lo, hi)."""
    planes = [{"name": f"/device:TPU:{k}", "lines": [
        {"name": "XLA Ops", "events": [["fusion.1", lo + 100, busy]]}]}
        for k, busy in enumerate(busy_per_device)]
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "t", "events": [["bench/traced", lo, hi - lo]]}]})
    return {"planes": planes}


def test_idle_share_is_averaged_over_the_chips():
    t = trace.reduce_rows(_rows([1_000, 2_000, 3_000, 4_000]))
    assert t["devices"] == 4
    got = _reader("device_idle_share.slasher_mesh").read({"trace": t})
    assert got == pytest.approx(100 * (1 - 2_500 / 10_000))
    assert _reader("device_idle_share.slasher_mesh").read({}) is None


def test_plane_roofline_from_the_rows_of_the_traced_batch():
    assert plane_bytes.plane_bytes(1, 4096) == 4096 * 9 * 2
    t = trace.reduce_rows(_rows([4_000] * 4))
    facts = {"trace": t, "traced_rows": 100, "history_length": 4096,
             "chips": 4, "peaks": {"hbm_bytes_per_s": 819e9}}
    want = 100 * 100 * 4096 * 18 / (4e-6 * 4 * 819e9)
    read = _reader("plane_hbm_roofline").read
    assert read(facts) == pytest.approx(want)
    assert read(dict(facts, traced_rows=0)) is None
    assert read(dict(facts, trace=None)) is None


def _batch(votes_, shard_max=None, shards=4, init=False):
    kids = [NS(name="slasher/group", duration=1, attrs={}, children=[
        NS(name="slasher/route", duration=0.5, attrs={}, children=[])])]
    if init:
        kids.append(NS(name="slasher/init_planes", duration=1, attrs={},
                       children=[]))
    attrs = {"votes": votes_}
    if shard_max is not None:
        attrs.update(shards=shards, shard_votes_max=shard_max)
    return NS(name="slasher/batch", duration=5, attrs=attrs, children=kids)


def test_shard_skew_reads_the_last_three_batches(monkeypatch):
    held = []
    monkeypatch.setattr(engine_spans, "_program_roots", lambda: held)
    read = _reader("shard_vote_skew").read
    traced = {"trace": {"busy_s": 0.1}}
    held += [_batch(400, 400, init=True), _batch(400, 120),
             _batch(400, 100)]
    assert read(traced) is None  # set-up's batch, with plane inits
    held += [_batch(800, 210), _batch(400, 105)]
    assert read(traced) == pytest.approx(
        100 * ((100 + 210 + 105) * 4 / 1600 - 1))
    assert read({}) is None


def test_shard_skew_reads_nothing_from_a_program_without_shards(
        monkeypatch):
    held = [_batch(400) for _ in range(4)]
    monkeypatch.setattr(engine_spans, "_program_roots", lambda: held)
    assert _reader("shard_vote_skew").read({"trace": {"busy_s": 1}}) is None


def test_the_cell_loads_with_its_readers():
    cell = spec.load_cell("slasher-votes-1m")
    assert cell.chips == 4 and cell.config["devices"] == 4
    assert cell.config["validators"] == 2**20
    assert {m.name for m in cell.per_layer} == {
        "device_idle_share.slasher_mesh", "plane_hbm_roofline",
        "shard_vote_skew"}
    assert {m.name for m in cell.end_to_end} == {"setup_s", "votes_per_s"}
    shape = votes.Shape.from_config(cell.config, cell.mix)
    assert shape.batch_votes == 32_768 + 64 and shape.n_old == 12_288
