"""Attester votes through the slasher's device engine
(``SurroundEngine.process``), closed loop.

Traffic (``kind: slasher``, ``harness/votes.py``): a batch is one slot's
votes of the configuration's registry, with seeded double, surrounding
and surrounded votes among them. The engine keeps, for every validator
chunk, min- and max-target planes and a target-occupancy plane of the
configuration's ``history_length`` epochs in HBM, and answers each vote
with its code bits.

Set-up: the old votes (some in every validator chunk, so every chunk
gets its planes) and the first batches go through the entry, which
makes every per-chunk program shape the window will make, and the
window's batches are built. The window then calls the entry until
``--seconds`` have passed. Afterwards the plain reference replays every
vote the entry was given, in the same order, and ``correct`` is that no vote's code
differs. ``votes_per_s`` is the window's votes over its seconds.
"""

from __future__ import annotations

import hashlib
import time

import numpy as np

from harness import slasher_reference, votes

HISTORY_BATCHES = 4  # batches sent in set-up after the old votes
POOL_BATCHES = 32    # window batches built in set-up; later ones as needed
TRACE_AFTER = 1      # --trace 1: batches before the traced one


class EngineSystem:
    """The system under test as this cell drives it: the program's
    ``SurroundEngine`` on the chip, at the configuration's chunk and
    history sizes."""

    def __init__(self, ctx, cfg: dict):
        ctx.open_device()
        from lighthouse_tpu.slasher.arrays import SurroundEngine

        self.ctx = ctx
        self.engine = SurroundEngine(
            validator_chunk_size=int(cfg["validator_chunk_size"]),
            history_length=int(cfg["history_length"]))
        if not self.engine.device:
            raise RuntimeError("the slasher engine chose its host path")
        self.pad_floor = self.engine.pad_floor
        self.fallbacks = 0

    def process(self, rows: list) -> tuple:
        """(codes, why the device did not answer all of them)."""
        codes = self.engine.process(rows)
        rep = self.engine.report()
        why = []
        if rep["fallbacks"] != self.fallbacks or rep["degraded"]:
            why.append(f"host fallback: {rep['fault_kinds']}")
            self.fallbacks = rep["fallbacks"]
        return codes, why

    def memory_peak(self):
        return self.ctx.memory_peak()


class Sent:
    """One batch as sent: its votes and what came back."""

    __slots__ = ("rows", "codes", "failed", "seconds")

    def __init__(self, batch: np.ndarray):
        self.rows = [tuple(r) for r in batch.tolist()]
        self.codes = None
        self.failed = False
        self.seconds = 0.0


def run(ctx, system_cls=EngineSystem) -> dict:
    cfg = ctx.cell.config
    shape = votes.Shape.from_config(cfg, ctx.cell.mix)
    stream = votes.Votes(ctx.seed, shape)
    chunk = int(cfg["validator_chunk_size"])
    system = system_cls(ctx, cfg)

    # Set-up through the entry: the old votes, then the first batches.
    setup = [Sent(stream.old())]
    setup += [Sent(stream.batch(k)) for k in range(HISTORY_BATCHES)]
    warmed = set()
    for i, b in enumerate(setup):
        t = time.perf_counter()
        b.codes, why = system.process(b.rows)
        b.failed = bool(why)
        warmed |= votes.pads(np.asarray(b.rows), chunk, system.pad_floor)
        if i in (0, 1, len(setup) - 1):
            ctx.emit("setup_batch", i=i, votes=len(b.rows), not_device=why,
                     seconds=round(time.perf_counter() - t, 3))
    first = HISTORY_BATCHES
    pool = [stream.batch(k) for k in range(first, first + POOL_BATCHES)]
    need = set().union(*(votes.pads(b, chunk, system.pad_floor) for b in pool))
    if not need <= warmed:
        raise RuntimeError(f"the window's traffic makes per-chunk shapes "
                           f"{sorted(need - warmed)} that set-up did not")
    ctx.emit("setup_compiles", pads=sorted(warmed),
             **ctx.compile_summary("setup"))

    sent: list[Sent] = []
    trace_from = TRACE_AFTER if ctx.trace else -1
    traced = []
    with ctx.window():
        setup_s = ctx.elapsed()
        t0 = t_prev = time.perf_counter()
        deadline = t0 + ctx.seconds
        while time.perf_counter() < deadline or 0 <= trace_from:
            if len(sent) == trace_from:
                tracer = ctx.traced()
                tracer.__enter__()
            k = len(sent)
            with ctx.span("bench/prepare_batch"):
                b = Sent(pool[k] if k < len(pool) else stream.batch(first + k))
            with ctx.span("bench/process_batch"):
                try:
                    b.codes, why = system.process(b.rows)
                except Exception as exc:  # a batch that raises has failed
                    why = [repr(exc)[:300]]
            if why:
                b.failed = True
                ctx.emit("batch_failed", batch=k, why=why)
            t_end = time.perf_counter()
            b.seconds, t_prev = t_end - t_prev, t_end
            sent.append(b)
            if trace_from >= 0 and len(sent) == trace_from + 1:
                tracer.__exit__(None, None, None)
                traced = sent[trace_from:]
                trace_from = -1
        window_s = time.perf_counter() - t0
    peak = system.memory_peak()
    n_votes = sum(len(b.rows) for b in sent)
    failed = sum(len(b.rows) for b in sent if b.failed)
    digest = hashlib.sha256()
    for b in setup + sent:
        digest.update(np.asarray(b.rows, np.int64).tobytes())
    ctx.emit("window", seconds=window_s, batches=len(sent), votes=n_votes,
             failed_votes=failed, compiles_in_window=ctx.window_compiles(),
             batch_s=[round(b.seconds, 4) for b in sent],
             memory_peak_bytes=peak, traffic_digest=digest.hexdigest())
    if ctx.window_compiles():
        ctx.emit("window_compiles", **ctx.compile_summary("window"))

    checks = check(ctx, setup + sent, int(cfg["history_length"]))
    facts = {"votes": n_votes, "window_s": window_s,
             "memory_peak_bytes": peak,
             "traced_votes": sum(len(b.rows) for b in traced)}
    correct = all(row["value"] <= row["limit"] for row in checks.values())
    return ctx.result(
        correct=correct, attempted=n_votes, failed=failed,
        end_to_end={"setup_s": setup_s,
                    "votes_per_s": (n_votes - failed) / window_s},
        facts=facts, checks=checks)


def check(ctx, batches, history: int) -> dict:
    """The plain reference's code for every vote the entry was given,
    in the same order, against the engine's."""
    t = time.perf_counter()
    ref = slasher_reference.Reference(history)
    mismatches = flagged = unanswered = 0
    for b in batches:
        want = ref.process(b.rows)
        flagged += sum(1 for c in want if c)
        if b.codes is None or len(b.codes) != len(want):
            unanswered += len(want)
            continue
        mismatches += sum(1 for g, w in zip(b.codes, want) if g != w)
    ctx.emit("reference", batches=len(batches), flagged=flagged,
             unanswered=unanswered, seconds=round(time.perf_counter() - t, 3))
    if not flagged:
        raise RuntimeError("the traffic holds no slashable vote: the "
                           "harness is broken")
    return {"code_mismatches": {"value": mismatches + unanswered,
                                "limit": 0}}
