"""The harness's run with the timed path broken underneath: each fault
the gossip cell can have turns ``correct`` false; the sound stand-in
keeps it true."""

import pytest

import fakes


@pytest.fixture
def cell(tmp_path):
    return fakes.tiny_cell(tmp_path)


def test_sound_system_is_correct(cell):
    out = fakes.drive(cell, fakes.FakeSystem)
    assert out["correct"] is True
    assert out["checks"]["verdict_mismatches"] == {"value": 0, "limit": 0}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(fakes.FAULTS))
def test_fault_is_caught(cell, fault):
    out = fakes.drive(cell, fakes.with_fault(fault), seconds=1.5)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["verdict_mismatches"]["value"] > 0


def test_traced_run_reports_the_trace(cell):
    """--trace 1 on the stand-in: the profiler window is read (on the
    CPU no device plane, so no device metric), and the result keeps its
    shape with ``checks`` last."""
    import argparse
    import time

    from harness import context

    args = argparse.Namespace(workload=cell.name, seed=7, seconds=0.5,
                              trace=1)
    ctx = context.Context(args, cell, time.perf_counter())
    try:
        out = cell.runner.run(ctx, fakes.FakeSystem)
    finally:
        ctx.close()
    assert out["correct"] is True
    assert out["device"]["window_s"] > 0 and out["device"]["busy_s"] == 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"
