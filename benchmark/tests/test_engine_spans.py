"""The slasher engine's per-layer readers: on hand-made span trees, with
no spans (the reading is None, as at a program without them), and on
the program's engine on the CPU, where each reading is checked against
what the traffic fixes."""

import os
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import conftest  # noqa: E402,F401  (puts the benchmark on sys.path)
import slasher_fakes as fakes  # noqa: E402
from harness import engine_spans, spec, votes  # noqa: E402

#: what a traced run's facts hold beside the program's spans
TRACED = {"trace": {"busy_s": 0.1, "window_s": 2.0}}
READERS = ("engine_host_us_per_vote", "engine_wait_share",
           "engine_launches_per_batch", "engine_pad_share")


def _reader(name):
    return spec.load_module(
        os.path.join(spec.BENCH_DIR, "metrics", name + ".py"),
        "metric_" + name)


def _span(name, start, end, children=(), **attrs):
    return NS(name=name, duration=end - start, attrs=attrs,
              children=list(children))


def _batch(t0, chunks, votes_, slots, init=False, launch=2):
    """A hand-made batch: 1 s of grouping, then per chunk 1 s to
    prepare, ``launch`` s to launch and 3 s waiting (with a 1 s plane
    init first where ``init``)."""
    kids = [_span("slasher/group", t0, t0 + 1)]
    t = t0 + 1
    for _ in range(chunks):
        kids.append(_span("slasher/prepare", t, t + 1))
        t += 1
        if init:
            kids.append(_span("slasher/init_planes", t, t + 1))
            t += 1
        kids += [_span("slasher/launch", t, t + launch),
                 _span("slasher/wait", t + launch, t + launch + 3)]
        t += launch + 3
    return _span("slasher/batch", t0, t + 1, kids, votes=votes_,
                 chunks=chunks, slots=slots)


@pytest.fixture
def held(monkeypatch):
    """What the program's tracer holds, as the readers see it."""
    roots = []
    monkeypatch.setattr(engine_spans, "_program_roots", lambda: roots)
    return roots


def test_readers_on_hand_made_spans(held):
    held += [_batch(0, 2, votes_=12, slots=16),   # 14 s
             _span("bls_dispatch/pack", 0, 5),     # not the engine's
             _batch(100, 3, votes_=20, slots=32),  # 20 s
             _batch(200, 1, votes_=4, slots=8)]    # 8 s
    got = {n: _reader(n).read(TRACED) for n in READERS}
    wait = 6 * 3
    assert got["engine_host_us_per_vote"] == pytest.approx(
        (14 + 20 + 8 - wait) * 1e6 / 36)
    assert got["engine_wait_share"] == pytest.approx(100 * wait / 42)
    assert got["engine_launches_per_batch"] == 2
    assert got["engine_pad_share"] == pytest.approx(100 * (1 - 36 / 56))


def test_readers_read_only_the_last_batches_without_plane_inits(held):
    """Set-up's batches (every chunk's planes made, pads compiled in the
    launch) may still be held when the run reports: they are not read,
    only the last three batches that made no planes."""
    held += [_batch(0, 4, votes_=4, slots=32, init=True, launch=900),
             _batch(1000, 2, votes_=9, slots=24, launch=400),
             _batch(2000, 2, votes_=9, slots=16, init=True)]
    window = [_batch(3000 + 100 * i, 2, votes_=12, slots=16)
              for i in range(3)]
    assert all(_reader(n).read(TRACED) is None for n in READERS)
    held += window
    got = {n: _reader(n).read(TRACED) for n in READERS}
    assert got["engine_host_us_per_vote"] == pytest.approx(
        3 * (14 - 6) * 1e6 / 36)
    assert got["engine_wait_share"] == pytest.approx(100 * 6 / 14)
    assert got["engine_launches_per_batch"] == 2
    assert got["engine_pad_share"] == 25.0
    held.append(_batch(4000, 3, votes_=20, slots=32))
    assert _reader("engine_launches_per_batch").read(TRACED) == 7 / 3


def test_readers_read_nothing_without_engine_spans(held):
    assert all(_reader(n).read(TRACED) is None for n in READERS)
    held.append(_span("bench/other", 0, 1))
    assert all(_reader(n).read(TRACED) is None for n in READERS)


def test_readers_read_nothing_outside_a_traced_run(held):
    held += [_batch(100 * i, 2, votes_=12, slots=16) for i in range(3)]
    assert all(_reader(n).read({}) is None for n in READERS)
    assert _reader("engine_pad_share").read(TRACED) == 25.0


def test_readers_on_the_engine_on_the_cpu():
    """Batches of the cell's traffic, at the tiny registry, through the
    program's engine with its spans on: one launch per validator chunk
    a batch touches, and the padding the engine's rule gives."""
    pytest.importorskip("jax")
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.slasher.arrays import SurroundEngine

    shape = votes.Shape.from_config(fakes.TINY_CONFIG, fakes.TINY_MIX)
    stream = votes.Votes(2**31 + 41, shape)
    eng = SurroundEngine(validator_chunk_size=shape.chunk,
                         history_length=shape.history_length)
    assert eng.device
    eng.process([tuple(r) for r in stream.old().tolist()])
    prev = tracing.set_enabled(True)
    tracing.clear()
    try:
        batches = [stream.batch(k) for k in range(engine_spans.SAMPLE)]
        for b in batches:
            eng.process([tuple(r) for r in b.tolist()])
        got = {n: _reader(n).read(TRACED) for n in READERS}
    finally:
        tracing.set_enabled(prev)
        tracing.clear()
    chunks = slots = n = 0
    for b in batches:
        _, per_chunk = np.unique(b[:, 0] // shape.chunk, return_counts=True)
        chunks += len(per_chunk)
        slots += sum(max(eng.pad_floor, 1 << int(c - 1).bit_length())
                     for c in per_chunk)
        n += len(b)
    assert got["engine_launches_per_batch"] == chunks / len(batches)
    assert got["engine_pad_share"] == pytest.approx(100 * (1 - n / slots))
    assert 0 < got["engine_wait_share"] < 100
    assert got["engine_host_us_per_vote"] > 0
