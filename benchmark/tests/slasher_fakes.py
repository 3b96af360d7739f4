"""Stand-ins for the slasher engine, built on the plain reference, with
the faults the slasher cell's check has to catch; and a tiny slasher
cell, so the harness's own run (traffic, window, reference check,
result) is driven on the CPU without a chip."""

from __future__ import annotations

import argparse
import json
import os
import time

from harness import context, slasher_reference, spec

BENCH = spec.BENCH_DIR

# 8 validator chunks, 256 votes a batch, at the configuration's history.
TINY_CONFIG = {"validators": 2048, "slots_per_epoch": 8,
               "history_length": 4096, "validator_chunk_size": 256}
TINY_MIX = {"kind": "slasher", "first_epoch": 8192, "old_per_chunk": 12,
            "old_min_width": 4, "old_max_width": 64,
            "old_max_age_epochs": 3840, "doubles": 4, "surrounds": 4}


class FakeSystem:
    """What EngineSystem offers the run, answered by the reference (or
    broken by ``fault``)."""

    fault = None
    history_div = 1

    def __init__(self, ctx, cfg):
        ctx.dev_info = {"platform": "cpu", "kind": "fake", "count": 1}
        self.history = int(cfg["history_length"]) // self.history_div
        self.ref = slasher_reference.Reference(self.history)
        self.pad_floor = 8

    def process(self, rows):
        if self.fault == "stale":
            # the state returned unchanged: nothing earlier is remembered
            return slasher_reference.Reference(self.history).process(rows), []
        if self.fault == "half_batch":
            half = len(rows) // 2
            return self.ref.process(rows[:half]) + [0] * (len(rows) - half), []
        if self.fault == "always_zero":
            return [0] * len(rows), []
        codes = self.ref.process(rows)
        if self.fault == "flipped":
            # one answer altered where it is produced
            codes[0] ^= slasher_reference.DOUBLE
        return codes, []

    def memory_peak(self):
        return 0


FAULTS = ("stale", "half_batch", "flipped", "always_zero")


def with_fault(fault):
    return type("Faulty", (FakeSystem,), {"fault": fault})


def control(div: int = 4):
    """The reference with the history the configuration states cut to
    a ``div``-th: votes older than that are forgotten."""
    return type("Control", (FakeSystem,), {"history_div": div})


def tiny_cell(tmp_path, config=None, mix=None):
    """A BENCHMARK.json in ``tmp_path`` with one slasher cell on the tiny
    registry, next to a copy-free view of the real benchmark dir."""
    cfg = dict(TINY_CONFIG, **(config or {}))
    mx = dict(TINY_MIX, **(mix or {}))
    os.makedirs(tmp_path / "benchmark" / "traffic", exist_ok=True)
    (tmp_path / "benchmark" / "kinds").symlink_to(
        os.path.join(BENCH, "kinds"))
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "benchmark" / "traffic" / "tiny-votes.json").write_text(
        json.dumps(mx))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "tiny.json"}],
        "workloads": [{"name": "tiny-votes", "config": "tiny",
                       "traffic": "tiny-votes", "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "source": "host_clock"},
            {"name": "votes_per_s", "unit": "votes/s", "better": "higher",
             "source": "host_clock"}],
        "per_layer": []}))
    return spec.load_cell("tiny-votes", str(tmp_path),
                          str(tmp_path / "benchmark"))


def drive(cell, system_cls, seed=20260001, seconds=0.1, trace=0):
    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=seconds,
                              trace=trace)
    ctx = context.Context(args, cell, time.perf_counter())
    try:
        return cell.runner.run(ctx, system_cls)
    finally:
        ctx.close()
