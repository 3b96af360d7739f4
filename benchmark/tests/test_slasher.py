"""The slasher cell on the CPU: its traffic, its plain reference against
the program's engine, and the harness's run with the engine broken
underneath (each fault, and the control, turns ``correct`` false).

The control is the reference put in the engine's place with the
configuration's history cut to a quarter. At the cell's own size it is
a script:

    python3 benchmark/tests/test_slasher.py --seconds 10 --seeds 11 22 33

which prints one line per seed with the number compared,
``code_mismatches``, as the control reads it.
"""

import argparse
import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import conftest  # noqa: E402,F401  (puts the benchmark on sys.path)
import slasher_fakes as fakes  # noqa: E402
from harness import slasher_reference as sr  # noqa: E402
from harness import spec, votes  # noqa: E402

SHAPE = votes.Shape.from_config(fakes.TINY_CONFIG, fakes.TINY_MIX)


def _stream_digest(seed, n=6):
    s = votes.Votes(seed, SHAPE)
    h = hashlib.sha256(s.old().tobytes())
    for k in range(n):
        h.update(s.batch(k).tobytes())
    return h.hexdigest()


def test_same_seed_same_votes_and_sizes_for_every_seed():
    assert _stream_digest(2**31 + 17) == _stream_digest(2**31 + 17)
    assert _stream_digest(2**31 + 17) != _stream_digest(5)
    for seed in (5, 2**31 + 17):
        s = votes.Votes(seed, SHAPE)
        assert len(s.old()) == SHAPE.n_old
        for k in range(2 * SHAPE.slots_per_epoch):
            assert len(s.batch(k)) == SHAPE.batch_votes


def test_an_epoch_holds_every_validator_once_with_its_slashable_votes():
    s = votes.Votes(3, SHAPE)
    epoch = np.concatenate([s.batch(k) for k in range(SHAPE.slots_per_epoch)])
    normal = epoch[(epoch[:, 2] == SHAPE.first_epoch)
                   & (epoch[:, 1] == SHAPE.first_epoch - 1)]
    extra = SHAPE.slots_per_epoch * SHAPE.doubles
    assert len(normal) == SHAPE.validators + extra
    assert len(np.unique(normal[:, 0])) == SHAPE.validators
    ref = sr.Reference(SHAPE.history_length)
    ref.process(s.old().tolist())
    codes = np.array(ref.process(epoch.tolist()))
    for bit in (sr.SURROUNDS, sr.SURROUNDED, sr.DOUBLE):
        assert (codes & bit).any()


def test_the_votes_stay_inside_the_history():
    s = votes.Votes(9, SHAPE)
    old = s.old()
    last = s.batch(4 * SHAPE.slots_per_epoch)
    assert last[:, 2].max() - (old[:, 1].min() - 1) < SHAPE.history_length


def test_pads_follow_the_engine_rule():
    batch = np.array([[0, 1, 2]] * 9 + [[300, 1, 2]] * 3)
    assert votes.pads(batch, 256, 8) == {16, 8}


def test_reference_agrees_with_the_engine_on_the_cpu():
    """The program's engine on the CPU (its device path, XLA on the
    host) and the reference give every vote the same code."""
    from lighthouse_tpu.slasher.arrays import SurroundEngine

    eng = SurroundEngine(validator_chunk_size=256, history_length=4096)
    assert eng.device
    ref = sr.Reference(4096)
    s = votes.Votes(20260002, SHAPE)
    flagged = 0
    for batch in [s.old()] + [s.batch(k) for k in range(3)]:
        rows = [tuple(r) for r in batch.tolist()]
        want = ref.process(rows)
        assert eng.process(rows) == want
        flagged += sum(1 for c in want if c)
    assert flagged > 0


@pytest.fixture
def cell(tmp_path):
    return fakes.tiny_cell(tmp_path)


def test_sound_system_is_correct(cell):
    out = fakes.drive(cell, fakes.FakeSystem)
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


def test_traced_run_reports_the_trace(cell):
    out = fakes.drive(cell, fakes.FakeSystem, seed=7, trace=1)
    assert out["correct"] is True
    assert out["device"]["window_s"] > 0 and out["device"]["busy_s"] == 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"


def main(argv) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="slasher-votes")
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in args.seeds:
        out = fakes.drive(cell, fakes.control(), seed=seed,
                          seconds=args.seconds)
        print(json.dumps({"control": "history/4", "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
