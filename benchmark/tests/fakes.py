"""A stand-in for the system under test, built on the plain reference,
with the faults the gossip cell's check has to catch. It lets the
harness's own run (traffic, window, reference check, result) be driven
on the CPU without a chip."""

from __future__ import annotations

import argparse
import json
import os
import time

from harness import context, reference, spec

BENCH = spec.BENCH_DIR

# A registry small enough for a test: 8 committees of 32 a slot.
TINY_CONFIG = {"validators": 2048, "slots_per_epoch": 8,
               "max_committees_per_slot": 64, "target_committee_size": 32,
               "attestation_batch_size": 64}
TINY_MIX = {"kind": "gossip", "tamper_every": 2,
            "tamper_kinds": ["wrong_signer", "wrong_message", "off_subgroup"],
            }

FAULTS = {
    # half of the batch left out: only the first half's sets are checked
    "half_batch": lambda items, prev: reference.batch_verdict(
        items[:len(items) // 2]),
    # the answer altered where it is produced
    "flipped": lambda items, prev: not reference.batch_verdict(items),
    # the state returned unchanged: the previous call's answer again
    "stale": lambda items, prev: prev,
    # no check at all
    "always_true": lambda items, prev: True,
}


class FakeSystem:
    """What ProgramSystem offers the run, answered by the reference
    (or by one of FAULTS)."""

    fault = None

    def __init__(self, ctx):
        ctx.dev_info = {"platform": "cpu", "kind": "fake", "count": 1}
        self.prev = True
        self.calls = 0

    def prepare(self, n_validators, shapes):
        pass

    def chunk(self, n):
        return max(1, n // 2)

    def shapes(self, call_sizes, keys):
        return {(self.chunk(n),) for n in call_sizes}

    def verify(self, call):
        items = call.items()
        if self.fault is None:
            verdict = reference.batch_verdict(items)
        else:
            verdict = FAULTS[self.fault](items, self.prev)
        self.prev = verdict
        self.calls += 1
        return verdict, []

    def stage_seconds(self):
        return {}

    def path_counts(self):
        return {}

    def memory_peak(self):
        return 0


def with_fault(fault):
    return type("Faulty", (FakeSystem,), {"fault": fault})


def tiny_cell(tmp_path, config=None, mix=None):
    """A BENCHMARK.json in ``tmp_path`` with one gossip cell on the
    tiny registry, next to a copy-free view of the real benchmark dir."""
    cfg = dict(TINY_CONFIG, **(config or {}))
    mx = dict(TINY_MIX, **(mix or {}))
    os.makedirs(tmp_path / "benchmark" / "traffic", exist_ok=True)
    (tmp_path / "benchmark" / "kinds").symlink_to(
        os.path.join(BENCH, "kinds"))
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "benchmark" / "traffic" / "tiny-gossip.json").write_text(
        json.dumps(mx))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "tiny.json"}],
        "workloads": [{"name": "tiny-gossip", "config": "tiny",
                       "traffic": "tiny-gossip", "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "source": "host_clock"},
            {"name": "sets_per_s", "unit": "sets/s", "better": "higher",
             "source": "host_clock"}],
        "per_layer": []}))
    return spec.load_cell("tiny-gossip", str(tmp_path),
                          str(tmp_path / "benchmark"))


def drive(cell, system_cls, seed=20260001, seconds=1.0):
    """One run of ``cell`` on ``system_cls``; the generator signs in this
    process, in parts of 96 sets (or at the cell's own sizes when the
    cell is a real one)."""
    if cell.config["validators"] == TINY_CONFIG["validators"]:
        cell.runner.WORKERS, cell.runner.PART_SETS = 0, 96
    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=seconds,
                              trace=0)
    ctx = context.Context(args, cell, time.perf_counter())
    try:
        return cell.runner.run(ctx, system_cls)
    finally:
        ctx.close()
