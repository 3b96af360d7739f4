"""Attester votes through the slasher's device engine with its planes
sharded over the cell's chips (``SurroundEngine(devices=chips)``),
closed loop.

Traffic (``kind: slasher_mesh``, the generator of ``harness/votes.py``
with the mix's sizes): a batch is one slot's votes of the
configuration's registry, with seeded double, surrounding and
surrounded votes among them. The engine keeps min- and max-target
planes and a target-occupancy plane of ``history_length`` epochs for
every validator chunk, each chip the chunks of every ``chips``-th slot,
and answers each batch with one program across the chips.

Set-up: the old votes (some in every validator chunk, so every chunk
gets its plane rows) and the first batches go through the entry, which
compiles every program shape the window uses; the window's batches are
built. The window then calls the entry until ``--seconds`` have passed;
a compile inside it fails the run. Afterwards the numpy reference
(``harness/slasher_lag_reference.py``) replays every vote the entry was
given, in the same order, and ``correct`` is that no vote's code
differs and none went unanswered. ``votes_per_s`` is the window's votes
over its seconds.

This differs from ``kinds/slasher.py`` in three ways: the system is
the sharded engine, the window's compiles are counted instead of
predicted from per-chunk pads, and the check is the numpy reference.
A batch's rows and codes are kept as arrays once it is answered, for
the check.
"""

from __future__ import annotations

import hashlib
import time

import numpy as np

from harness import engine_spans, slasher_lag_reference, votes
from kinds.slasher import (HISTORY_BATCHES, POOL_BATCHES, TRACE_AFTER,
                           EngineSystem, Sent)


class MeshSystem(EngineSystem):
    """The program's ``SurroundEngine`` with its planes on the cell's
    chips, at the configuration's chunk and history sizes."""

    def __init__(self, ctx, cfg: dict):
        ctx.open_device()
        from lighthouse_tpu.slasher.arrays import SurroundEngine

        self.ctx = ctx
        self.engine = SurroundEngine(
            validator_chunk_size=int(cfg["validator_chunk_size"]),
            history_length=int(cfg["history_length"]),
            devices=ctx.cell.chips)
        if not self.engine.device:
            raise RuntimeError("the slasher engine chose its host path")
        self.fallbacks = 0

    def memory_peaks(self) -> list:
        import jax

        return [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in jax.devices()[:self.ctx.cell.chips]]


def _answer(system, b: Sent, batch: np.ndarray) -> list:
    """Send ``b``; afterwards it keeps ``batch`` as its rows and its
    codes as an array. A window keeps ~20 M answers for the check: as
    tuples and lists, every full collection of the window would walk
    them all."""
    try:
        codes, why = system.process(b.rows)
        b.codes = np.asarray(codes, np.uint8)
    except Exception as exc:  # a batch that raises has failed
        why = [repr(exc)[:300]]
    b.rows = batch
    b.failed = bool(why)
    return why


def run(ctx, system_cls=MeshSystem) -> dict:
    cfg = ctx.cell.config
    shape = votes.Shape.from_config(cfg, ctx.cell.mix)
    stream = votes.Votes(ctx.seed, shape)
    system = system_cls(ctx, cfg)

    # Set-up through the entry: the old votes, then the first batches.
    arrays = [stream.old()] + [stream.batch(k)
                               for k in range(HISTORY_BATCHES)]
    setup = []
    for i, batch in enumerate(arrays):
        t = time.perf_counter()
        b = Sent(batch)
        why = _answer(system, b, batch)
        setup.append(b)
        if i in (0, 1, len(arrays) - 1):
            ctx.emit("setup_batch", i=i, votes=len(batch), not_device=why,
                     seconds=round(time.perf_counter() - t, 3))
    first = HISTORY_BATCHES
    pool = [stream.batch(k) for k in range(first, first + POOL_BATCHES)]
    ctx.emit("setup_compiles", **ctx.compile_summary("setup"))

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
                batch = pool[k] if k < len(pool) else stream.batch(first + k)
                b = Sent(batch)
            with ctx.span("bench/process_batch"):
                why = _answer(system, b, batch)
            if why:
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
    compiles = ctx.window_compiles()
    ctx.emit("window", seconds=window_s, batches=len(sent), votes=n_votes,
             failed_votes=failed, compiles_in_window=compiles,
             batch_s=[round(b.seconds, 4) for b in sent],
             memory_peak_bytes=peak,
             memory_peak_bytes_per_chip=getattr(system, "memory_peaks",
                                                lambda: None)(),
             traffic_digest=digest.hexdigest())
    if compiles:
        ctx.emit("window_compiles", **ctx.compile_summary("window"))
        raise RuntimeError(f"{compiles} compiles inside the window: set-up "
                           "did not make every program shape")

    if ctx.trace:
        # the engine's spans of the window's last batches, as the
        # program's tracer holds them: where a batch's host time goes
        ctx.emit("engine_spans", **(engine_spans.summary(
            {"trace": ctx.window_trace}) or {}))
    history = int(cfg["history_length"])
    checks = check(ctx, setup + sent, history)
    facts = {"votes": n_votes, "window_s": window_s,
             "memory_peak_bytes": peak, "chips": ctx.cell.chips,
             "history_length": history,
             "traced_votes": sum(len(b.rows) for b in traced),
             "traced_rows": sum(len(np.unique(b.rows[:, 0]))
                                for b in traced)}
    correct = all(row["value"] <= row["limit"] for row in checks.values())
    return ctx.result(
        correct=correct, attempted=n_votes, failed=failed,
        end_to_end={"setup_s": setup_s,
                    "votes_per_s": (n_votes - failed) / window_s},
        facts=facts, checks=checks)


def check(ctx, batches, history: int) -> dict:
    """The numpy reference's code for every vote the entry was given,
    in the same order, against the engine's."""
    t = time.perf_counter()
    want = slasher_lag_reference.codes(
        np.concatenate([b.rows for b in batches]), history)
    mismatches = unanswered = at = 0
    for b in batches:
        w, at = want[at:at + len(b.rows)], at + len(b.rows)
        if b.codes is None or len(b.codes) != len(w):
            unanswered += len(w)
            continue
        mismatches += int(np.count_nonzero(b.codes != w))
    flagged = int(np.count_nonzero(want))
    ctx.emit("reference", batches=len(batches), votes=len(want),
             flagged=flagged, unanswered=unanswered,
             seconds=round(time.perf_counter() - t, 3))
    if not flagged:
        raise RuntimeError("the traffic holds no slashable vote: the "
                           "harness is broken")
    return {"code_mismatches": {"value": mismatches + unanswered,
                                "limit": 0}}
