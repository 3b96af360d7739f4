"""Unaggregated gossip attestations through
``JaxBackend.verify_signature_sets``, closed loop.

Traffic (``kind: gossip``): the slots of the configuration's registry,
each committee's members signing its message, in a seeded arrival
order across subnets; calls of the configuration's
``attestation_batch_size`` sets, cut from that stream (a call may
straddle two slots). Every set carries its
validator index, so with the registry's pubkey table in HBM the device
gathers the pubkeys. Every ``tamper_every``-th call carries one invalid
set, at a seeded place in a quarter of the call that turns with each
such call (the second half first), of a kind drawn from
``tamper_kinds``.

Set-up: the generator's workers sign the warm-up traffic and the first
slots while this process lowers the programs the traffic's chunk shapes
need and builds the table; a warm-up call through the entry itself
then makes every chunk shape the window will make. The window then
calls the entry until ``--seconds`` have passed. Afterwards the plain
reference decides every tampered call and a seeded sample of the
others; ``correct`` is that no checked verdict differs. ``sets_per_s``
is the clean calls' sets over their seconds: a node rarely sees a
batch fail, so the tampered calls, there for ``correct``, are not
counted as served work.
"""

from __future__ import annotations

import hashlib
import time

import numpy as np

from harness import bls, reference, traffic

WARM_SLOT = 1 << 40  # warm-up traffic: slots the window never reaches
WORKERS = 3          # generator processes: each signs ~7,000 sets/s
PART_SETS = 8192     # sets the generator signs at a time
PARTS_AHEAD = 2      # parts signed ahead of the stream
CLEAN_CALLS = 2      # clean calls of the window the reference checks
QUARTERS = (2, 0, 3, 1)  # where successive tampered calls plant their set
TRACE_AFTER = 1      # --trace 1: calls before the traced one


class Stream:
    """The set stream of consecutive slots, cut into calls; the
    generator signs it in parts, ``ahead`` parts ahead of use."""

    def __init__(self, gen, first_slot: int, ahead: int, first=None):
        self.gen, self.ahead = gen, ahead
        self.key = (first_slot, 0)
        self.cur = None
        self.pos = 0
        self.late_s = 0.0
        self.slots = set()
        self._ready = first

    def _next_part(self):
        if self.cur is not None:
            self.key = self.gen.next_key(self.key)
        k = self.key
        for _ in range(self.ahead):
            k = self.gen.next_key(k)
            self.gen.request(k)
        t = time.perf_counter()
        self.cur = self._ready or self.gen.get(self.key)
        self._ready = None
        self.late_s += time.perf_counter() - t
        self.pos = 0
        self.slots.add(self.key[0])

    def take(self, n: int) -> list:
        """[(SlotSets, lo, hi)] covering the next ``n`` sets."""
        out = []
        while n:
            if self.cur is None or self.pos == len(self.cur.plan.validators):
                self._next_part()
            hi = min(self.pos + n, len(self.cur.plan.validators))
            out.append((self.cur, self.pos, hi))
            n -= hi - self.pos
            self.pos = hi
        return out


class Call:
    """One call's sets as plain data: what the reference checks."""

    __slots__ = ("validators", "messages", "sigs", "pks", "tamper",
                 "verdict", "failed", "seconds")

    def __init__(self, parts):
        self.validators = np.concatenate(
            [s.plan.validators[lo:hi] for s, lo, hi in parts])
        self.messages = [s.plan.messages[j] for s, lo, hi in parts
                         for j in s.plan.message_ids[lo:hi]]
        self.sigs = np.concatenate([s.sigs[lo:hi] for s, lo, hi in parts])
        self.pks = np.concatenate([s.pks[lo:hi] for s, lo, hi in parts])
        self.tamper = None  # (position, kind)
        self.verdict = None
        self.failed = False
        self.seconds = 0.0  # from the previous call's end to this one's

    def __len__(self):
        return len(self.validators)

    def items(self):
        return [(int(v), m, self.sigs[i].tobytes()) for i, (v, m) in
                enumerate(zip(self.validators.tolist(), self.messages))]

    def digest(self, h) -> None:
        h.update(self.validators.tobytes())
        h.update(b"".join(self.messages))
        h.update(self.sigs.tobytes())
        h.update(repr(self.tamper).encode())


class Tamperer:
    """Plants one invalid set in every ``every``-th call."""

    def __init__(self, seed: int, mix: dict, chunk: int):
        self.chunk = chunk
        self.every = int(mix.get("tamper_every", 0))
        self.kinds = list(mix.get("tamper_kinds", ["wrong_signer"]))
        self.rng = traffic.rng(seed, 7)
        self.count = 0
        # A point of E2 outside G2: a curve map's output before the
        # cofactor is cleared.
        u = (int(self.rng.integers(1 << 62)), int(self.rng.integers(1 << 62)))
        self.off_g2 = bls.map_to_curve_g2(u)

    def apply(self, k: int, call: Call) -> None:
        if not self.every or k % self.every != self.every - 1:
            return
        n = len(call)
        quarter = QUARTERS[self.count % 4]
        self.count += 1
        lo = quarter * n // 4
        pos = lo + int(self.rng.integers(max(1, (quarter + 1) * n // 4 - lo)))
        kind = self.kinds[int(self.rng.integers(len(self.kinds)))]
        # Another set of the same program chunk, so that the chunk's
        # distinct messages, and with them its shapes, stay as they were.
        c0 = pos - pos % self.chunk
        others = [j for j in range(c0, min(c0 + self.chunk, n))
                  if call.validators[j] != call.validators[pos]
                  and (kind != "wrong_message"
                       or call.messages[j] != call.messages[pos])]
        other = others[int(self.rng.integers(len(others)))]
        if kind == "wrong_signer":
            # this signature under another validator's index and key
            call.validators = call.validators.copy()
            call.pks = call.pks.copy()
            call.validators[pos] = call.validators[other]
            call.pks[pos] = call.pks[other]
        elif kind == "wrong_message":
            # this signature over another committee's message
            call.messages[pos] = call.messages[other]
        elif kind == "off_subgroup":
            sig = bls.jac(bls.g2_from_bytes(call.sigs[pos].tobytes()), bls.Fp2)
            bad = bls.to_affine(bls.add(sig, self.off_g2, bls.Fp2), bls.Fp2)
            call.sigs = call.sigs.copy()
            call.sigs[pos] = np.frombuffer(bls.g2_bytes(bad), np.uint8)
        else:
            raise ValueError(f"unknown tamper kind {kind!r}")
        call.tamper = (pos, kind)


def api_sets(call: Call) -> list:
    """The call as the program's API objects (what a node's gossip
    decoding hands to the verifier)."""
    from lighthouse_tpu.crypto.bls.api import PublicKey, Signature, SignatureSet
    from lighthouse_tpu.crypto.bls.curve import FQ2_B2, FQ_B1, AffinePoint
    from lighthouse_tpu.crypto.bls.fields import Fq, Fq2

    sig_raw = call.sigs.tobytes()
    pk_raw = call.pks.tobytes()
    out = []
    ib = int.from_bytes
    for i, (v, m) in enumerate(zip(call.validators.tolist(), call.messages)):
        s = sig_raw[192 * i:192 * i + 192]
        k = pk_raw[96 * i:96 * i + 96]
        sig = Signature(AffinePoint(Fq2(ib(s[:48]), ib(s[48:96])),
                                    Fq2(ib(s[96:144]), ib(s[144:])),
                                    False, FQ2_B2))
        pk = PublicKey(AffinePoint(Fq(ib(k[:48])), Fq(ib(k[48:])), False,
                                   FQ_B1))
        out.append(SignatureSet.single_pubkey(sig, pk, m, index=v))
    return out


# ------------------------------------------------------------ the program


def stream_calls(plans, per_call: int):
    """(call sizes, message keys per call) of the calls cut from the
    stream of these consecutive slots."""
    keys = [(p.slot, int(j)) for p in plans for j in p.message_ids]
    calls = [keys[i:i + per_call]
             for i in range(0, len(keys) - per_call + 1, per_call)]
    return [len(c) for c in calls], calls


class ProgramSystem:
    """The system under test as this cell drives it: the registry's
    pubkey table in HBM, ``JaxBackend.verify_signature_sets``, and the
    program's own counters (which dispatch paths answered, the stage
    seconds of its spans)."""

    FUSED = ("fused", "indexed", "sharded", "sharded-indexed")

    def __init__(self, ctx):
        ctx.open_device()
        from lighthouse_tpu import jax_backend as jb
        from lighthouse_tpu.common import pipeline, resilience
        from lighthouse_tpu.crypto.bls.backends import get_backend

        self.ctx, self.jb, self.res, self.pipeline = ctx, jb, resilience, pipeline
        self.backend = get_backend("jax")
        self.paths = self._paths()
        self.degraded = self._degraded()

    def prepare(self, n_validators: int, shapes) -> None:
        """Set-up of the program for this traffic. This thread lowers,
        once, each program the traffic's chunk shapes need, as the
        program itself specifies them (``jax_backend.fused_program`` for
        the indexed verify, ``tkernel_htc.map_programs`` for hash-to-G2),
        and builds the registry's table into HBM after the first; the
        compiles run in background threads meanwhile and, warm, load
        from the persistent cache. Lowering is Python and holds the interpreter:
        nothing else of this process runs beside it (a table build in a
        thread beside it slowed it by a third on the chip's host)."""
        import threading

        from lighthouse_tpu import blsrt
        from lighthouse_tpu import jax_backend as jb
        from lighthouse_tpu.ops import tkernel_htc as th

        errors = []

        def guarded(fn, *args):
            def go():
                try:
                    fn(*args)
                except BaseException as exc:  # handed to the main thread
                    errors.append(exc)
            t = threading.Thread(target=go, daemon=True)
            t.start()
            return t

        def build_table():
            t = time.perf_counter()
            table = blsrt.build_sequential_table(n_validators)
            table.device_arrays()
            blsrt.set_device_table(table)
            self.ctx.emit("table", validators=n_validators,
                          rows=table.capacity,
                          seconds=round(time.perf_counter() - t, 3))

        def compile_one(name, lowered):
            t = time.perf_counter()
            lowered.compile()
            self.ctx.emit("compiled", program=name,
                          seconds=round(time.perf_counter() - t, 3))

        rows = blsrt.DevicePubkeyTable.capacity_for(n_validators)
        specs = [(f"indexed_S{S}", jb.fused_program(S, 1, table_rows=rows))
                 for S in sorted({s for s, _ in shapes})]
        specs += [(f"hash_to_g2_D{D}", p) for D in sorted({d for _, d in shapes})
                  for p in th.map_programs(D)]
        threads = []
        for i, (name, (fn, args, static)) in enumerate(specs):
            t = time.perf_counter()
            lowered = fn.lower(*args, **static)
            self.ctx.emit("lowered", program=name,
                          seconds=round(time.perf_counter() - t, 3))
            threads.append(guarded(compile_one, name, lowered))
            if i == 0:
                build_table()  # while the first, largest program loads
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    def chunk(self, n: int) -> int:
        p = self.pipeline
        return p.chunk_size(n) if p.should_pipeline(n) else n

    def shapes(self, call_sizes, keys_per_call) -> set:
        """(S, D) of every chunk the program's pipeline cuts these calls
        into: padded sets, padded distinct messages."""
        from lighthouse_tpu.utils import next_pow2

        out = set()
        for n, keys in zip(call_sizes, keys_per_call):
            step = self.chunk(n)
            for lo in range(0, n, step):
                part = keys[lo:lo + step]
                out.add((next_pow2(len(part)), next_pow2(len(set(part)))))
        return out

    def verify(self, call) -> tuple:
        """(verdict, why the device did not answer all of it)."""
        verdict = self.backend.verify_signature_sets(api_sets(call))
        return verdict, self._step()

    def _paths(self) -> dict:
        return {lbl["path"]: v for lbl, v in self.jb.DISPATCH_BATCHES.items()}

    def path_counts(self) -> dict:
        """Dispatches by program path so far (bls_dispatch_batches_total)."""
        return self._paths()

    def _degraded(self) -> float:
        return sum(v for _, v in self.res.DEGRADED_TOTAL.items())

    def _step(self) -> list:
        now, deg = self._paths(), self._degraded()
        why = []
        for p, v in now.items():
            n = v - self.paths.get(p, 0)
            parts = p.split("+")
            if n and (parts[0] not in self.FUSED
                      or any("fallback" in q for q in parts)):
                why.append(f"{n:g} dispatch(es) on {p!r}")
        if deg - self.degraded:
            why.append(f"{deg - self.degraded:g} degraded")
        self.paths, self.degraded = now, deg
        return why

    def stage_seconds(self) -> dict:
        """bls_dispatch_stage_seconds sums by stage, from the program's
        Prometheus exposition (recorded while its tracing is on)."""
        h = self.jb.DISPATCH_STAGE_SECONDS
        out = {}
        for line in h.expose():
            if line.startswith(h.name + "_sum{"):
                lbl, value = line.rsplit(" ", 1)
                out[lbl.split('stage="', 1)[1].split('"', 1)[0]] = float(value)
        return out

    def memory_peak(self):
        return self.ctx.memory_peak()


# ------------------------------------------------------------------- run


def run(ctx, system_cls=ProgramSystem) -> dict:
    cell, mix = ctx.cell, ctx.cell.mix
    shape = traffic.Shape.from_config(cell.config)
    per_call = int(cell.config["attestation_batch_size"])
    workers = WORKERS
    gen = traffic.Generator(ctx.seed, shape, workers, PART_SETS)
    ctx.on_close(gen.close)

    # Signing starts first: the workers sign while this process meets
    # the chip and the program.
    t_gen = time.perf_counter()
    gen.request((WARM_SLOT, 0))
    ahead = PARTS_AHEAD
    key = (0, 0)
    for _ in range(ahead + 1):
        gen.request(key)
        key = gen.next_key(key)

    system = system_cls(ctx)
    tamperer = Tamperer(ctx.seed, mix, system.chunk(per_call))
    need = system.shapes(*stream_calls(
        [traffic.plan_slot(ctx.seed, k, shape) for k in (0, 1)],
        per_call))
    system.prepare(int(cell.config["validators"]), need)

    # Warm-up through the entry: a call of warm-up traffic, which makes
    # every chunk shape the window's traffic makes (checked with the
    # program's own chunking).
    warm = Stream(gen, WARM_SLOT, 0)
    warm_calls = [Call(warm.take(per_call))]
    have = system.shapes([len(c) for c in warm_calls],
                         [c.messages for c in warm_calls])
    if not need <= have:
        raise RuntimeError(f"warm-up makes chunk shapes {sorted(have)}, "
                           f"the traffic {sorted(need)}")
    for i, c in enumerate(warm_calls):
        t = time.perf_counter()
        c.verdict, why = system.verify(c)
        ctx.emit("warm_call", i=i, verdict=c.verdict, not_device=why,
                 seconds=round(time.perf_counter() - t, 3))
    first = gen.get((0, 0))
    ctx.emit("generator", workers=workers, sets_per_slot=shape.sets_per_slot,
             committees=shape.committees_per_slot, part_sets=gen.part_sets,
             capacity_sets_per_s=len(first.plan.validators) / first.sign_s,
             first_part_ready_s=round(time.perf_counter() - t_gen, 3))
    ctx.emit("setup_compiles", **ctx.compile_summary("setup"))

    # Slot 0's first part is signed; the stream keeps `ahead` parts
    # signing.
    stream = Stream(gen, 0, ahead, first)
    calls: list[Call] = []
    stages_before = system.stage_seconds()
    prep_s = 0.0
    trace_from = TRACE_AFTER if ctx.trace else -1
    traced = []
    with ctx.window():
        setup_s = ctx.elapsed()
        t0 = t_prev = time.perf_counter()
        deadline = t0 + ctx.seconds
        while time.perf_counter() < deadline or 0 <= trace_from:
            if len(calls) == trace_from:
                tracer = ctx.traced()
                tracer.__enter__()
            tp = time.perf_counter()
            with ctx.span("bench/prepare_call"):
                call = Call(stream.take(per_call))
                tamperer.apply(len(calls), call)
            prep_s += time.perf_counter() - tp
            with ctx.span("bench/verify_call"):
                try:
                    call.verdict, why = system.verify(call)
                except Exception as exc:  # a call that raises has failed
                    why = [repr(exc)[:300]]
            if why:
                call.failed = True
                ctx.emit("call_failed", call=len(calls), why=why)
            t_end = time.perf_counter()
            call.seconds, t_prev = t_end - t_prev, t_end
            calls.append(call)
            if trace_from >= 0 and len(calls) == trace_from + 1:
                tracer.__exit__(None, None, None)
                traced = calls[trace_from:]
                trace_from = -1
        window_s = time.perf_counter() - t0
    peak = system.memory_peak()
    stages = {s: v - stages_before.get(s, 0.0)
              for s, v in system.stage_seconds().items()}
    n_sets = sum(len(c) for c in calls)
    failed = sum(len(c) for c in calls if c.failed)
    clean = [c for c in calls if c.tamper is None]
    clean_s = sum(c.seconds for c in clean)
    clean_served = sum(len(c) for c in clean if not c.failed)
    digest = hashlib.sha256()
    for c in calls:
        c.digest(digest)
    ctx.emit("window", seconds=window_s, calls=len(calls), sets=n_sets,
             failed_sets=failed, slots=len(stream.slots),
             compiles_in_window=ctx.window_compiles(),
             clean_calls=len(clean), clean_s=clean_s,
             generator_late_s=stream.late_s, prepare_s=prep_s,
             paths=system.path_counts(),
             memory_peak_bytes=peak, traffic_digest=digest.hexdigest(),
             stage_seconds=stages)
    if ctx.window_compiles():
        ctx.emit("window_compiles", **ctx.compile_summary("window"))

    checks = check(ctx, gen, calls, warm_calls, workers)
    facts = {"sets": n_sets, "calls": len(calls), "window_s": window_s,
             "stage_seconds": stages, "memory_peak_bytes": peak,
             "traced_sets": sum(len(c) for c in traced),
             "traced_calls": len(traced),
             "traced_distinct_messages": sum(len(set(c.messages))
                                             for c in traced)}
    return ctx.result(
        correct=checks["verdict_mismatches"]["value"] == 0,
        attempted=n_sets, failed=failed,
        end_to_end={"setup_s": setup_s,
                    "sets_per_s": clean_served / clean_s},
        facts=facts, checks=checks)


def check(ctx, gen, calls, warm_calls, workers) -> dict:
    """The plain reference's verdict on the warm-up calls, on every
    tampered call of the window and on a seeded sample of its others,
    against the program's."""
    mix = ctx.cell.mix
    t = time.perf_counter()
    rng = traffic.rng(ctx.seed, 9)
    clean = [i for i, c in enumerate(calls) if c.tamper is None]
    n_clean = min(len(clean), CLEAN_CALLS)
    sample = rng.choice(clean, n_clean, replace=False).tolist() if n_clean else []
    checked = list(warm_calls) + [calls[i] for i in sorted(
        [i for i, c in enumerate(calls) if c.tamper] + sample)]
    mismatches = unintended = 0
    for c in checked:
        first = [c.tamper[0]] if c.tamper else []
        ref = reference.batch_verdict(c.items(), first, gen.submit, workers)
        unintended += ref != (c.tamper is None)
        mismatches += c.verdict is not ref
    ctx.emit("reference", checked_calls=len(checked),
             tampered=sum(1 for c in calls if c.tamper),
             clean_sampled=len(sample) + len(warm_calls),
             seconds=round(time.perf_counter() - t, 3))
    if unintended:
        raise RuntimeError(f"the reference disagrees with the generator on "
                           f"{unintended} call(s): the harness is broken")
    return {"verdict_mismatches": {"value": mismatches, "limit": 0}}
