"""Bring-up proof: the production verify path answers on the chip.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the sharded verifier on four chips

One process: JAX is imported once, no child touches it, and no failure is
caught to carry on. Each phase prints one JSON line (also appended to
chiprun_out/chip_smoke.jsonl); the last line is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

and is printed only when every phase passed. Without a TPU the script
exits non-zero at the device check: there is no CPU fallback.

Phases (one chip):
  1. device   — jax.devices()[0] must be a TPU. Then the three large
                programs are lowered and start compiling in background
                threads, so their minutes overlap the chain's set-up
                (Warmup).
  2. slots    — ScaleChain(1M validators, mainnet preset): the device
                pubkey table stays in HBM; two slots of gossip
                SignedAggregateAndProofs through BeaconProcessor → Router →
                JaxBackend; every aggregate must verify (BASELINE #5).
  3. gossip   — 4,096 single-pubkey sets from the sequential-key registry,
                each with its validator index, through
                JaxBackend.verify_signature_sets: True, and False with
                one lane tampered; the native C++ backend must agree on
                a subsample (BASELINE #4).
  4. device-answered — every dispatch took the fused Pallas path, the
                gossip chunks its HBM-table gather (``indexed``): no
                classic or native path, no degradation, every breaker
                closed, no interpret mode.
  5. compiles — the compile seconds of every program the run built.

With ``--chips 4`` only the sharded path and what it is compared with
run: the 4,096-set batch, good and tampered, at LHTPU_DEVICES=4 with
LHTPU_SHARDED_VERIFY=1, and at one chip; verdicts must match.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
LOG = os.path.join(REPO, "chiprun_out", "chip_smoke.jsonl")

N_VALIDATORS = 1_000_000
SLOTS = (1, 2)
GOSSIP_SETS = 4096
NATIVE_SAMPLE = 32
# Pipeline chunk for the gossip batch: 256 sets, the padded set count of
# a mainnet slot's 64 aggregates × 3 sets, so both phases hash through
# one compiled hash-to-G2 bucket (a chip compile costs minutes).
CHUNK = "256"
# Paths of the fused Pallas program (jax_backend._dispatch labels).
FUSED_PATHS = ("fused", "indexed", "sharded", "sharded-indexed")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_T0 = time.perf_counter()
_COMPILES: list[tuple[str, float]] = []


def emit(phase: str, **fields) -> None:
    row = {"phase": phase, "t_s": round(time.perf_counter() - _T0, 3),
           **fields}
    line = json.dumps(row, default=str)
    print(line, flush=True)
    os.makedirs(os.path.dirname(LOG), exist_ok=True)
    with open(LOG, "a") as f:
        f.write(line + "\n")


def fail(why: str) -> None:
    emit("failed", reason=why)
    sys.exit(f"chip_smoke: {why}")


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event == COMPILE_EVENT:
        name = str(kw.get("fun_name", "?"))
        _COMPILES.append((name, seconds))
        if seconds >= 1.0:  # a run cut by its time limit still shows these
            emit("compile", program=name, seconds=round(seconds, 3))


def compiles_since(mark: int) -> dict:
    rows = _COMPILES[mark:]
    return {"n": len(rows), "seconds": round(sum(s for _, s in rows), 3),
            "programs": [[name, round(s, 3)] for name, s in rows]}


# ------------------------------------------------------------ warm-up


def warm_programs(sets: int, table_rows: int) -> dict:
    """The three large programs of the one-chip phases, as JaxBackend
    calls them (tests/test_chip_smoke.py checks that it does): the
    indexed verify of the gossip chunks, whose pubkeys are gathered from
    the ``table_rows``-row HBM table; hash-to-G2 for a ``sets``-message
    bucket (the resident map, then the affine finish); the triage
    (grouped-verdict) verify of the slot's host-aggregated sets. Name ->
    [(jitted fn, argument avals, static kwargs)], in the order Warmup
    builds them: the longest compile first."""
    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.ops import tkernel_htc as th

    groups = min(jb._verdict_groups(), sets)
    return {
        "indexed": [jb.fused_program(sets, 1, table_rows=table_rows)],
        "hash_to_g2": th.map_programs(sets),
        "triage": [jb.fused_program(sets, 1, groups=groups)],
    }


class Warmup:
    """:func:`warm_programs`, built ahead of use. A cold build is minutes
    per program (PERF.md). Each is traced and lowered here, on the main
    thread (Python, which holds the GIL: tracing in a thread beside the
    chain's set-up slowed both several fold on the chip's host, PR 21),
    and compiled in a background thread (XLA, which releases it),
    overlapping the 1M-validator set-up. The triage verify compiles
    after hash-to-G2, so at most two compiles hold host memory at once
    (7.3 GB each, rehearsal, PR 21). The later call finds the executable
    in JAX's compilation cache and compiles nothing (the compile lines
    show it). A failed compile here fails the run."""

    AFTER = {"triage": "hash_to_g2"}

    def __init__(self, sets: int, table_rows: int):
        import threading

        self._errors: list[BaseException] = []
        self._threads = {}
        for name, cases in warm_programs(sets, table_rows).items():
            lowered = [self._lower(name, case) for case in cases]
            t = threading.Thread(
                target=self._compile_all, daemon=True,
                args=(lowered, self._threads.get(self.AFTER.get(name))))
            t.start()
            self._threads[name] = t

    @staticmethod
    def _lower(name, case):
        fn, args, static = case
        t0 = time.perf_counter()
        lowered = fn.lower(*args, **static)
        emit("lowered", program=name,
             seconds=round(time.perf_counter() - t0, 3))
        return name, lowered

    def _compile_all(self, lowered, after=None) -> None:
        if after is not None:
            after.join()
        try:
            for name, low in lowered:
                t0 = time.perf_counter()
                low.compile()
                emit("warm", program=name,
                     seconds=round(time.perf_counter() - t0, 3))
        except BaseException as exc:  # lhtpu: ignore[LH502] -- handed to the main thread, which fails the run
            self._errors.append(exc)

    def wait(self, *names: str) -> None:
        """The named programs are built (all of them by default)."""
        for name in names or self._threads:
            self._threads[name].join()
        if self._errors:
            raise self._errors[0]


# ------------------------------------------------------------ phase 4


def device_answered(report: dict, path_counts: dict, degraded: float,
                    interpret: bool, required: str) -> list[str]:
    """Why the run may NOT claim the chip answered (empty: it did).

    ``report`` is jax_backend.dispatch_stage_report(); ``path_counts``
    maps every dispatch path of the run to its count
    (bls_dispatch_batches_total); ``degraded`` is how far
    resilience.DEGRADED_TOTAL moved; ``interpret`` is whether the
    kernels would run in Pallas interpret mode; ``required`` is the
    program the run must have dispatched at least once (``indexed``:
    the HBM table gather; ``sharded``: the mesh)."""
    why = []
    if not any(n and p.split("+")[0] == required
               for p, n in path_counts.items()):
        why.append(f"no dispatch took the {required!r} path")

    def fused(path: str) -> bool:
        parts = path.split("+")
        return parts[0] in FUSED_PATHS and not any(
            "fallback" in p for p in parts)

    for path, n in sorted(path_counts.items()):
        if n and not fused(path):
            why.append(f"{n:g} dispatch(es) took the {path!r} path")
    last = report.get("path") or ""
    if not fused(last):
        why.append(f"last dispatch path {last!r} is not the fused program")
    if "native_fallback" in (report.get("stages_ms") or {}):
        why.append("the native_fallback stage ran")
    if degraded:
        why.append(f"resilience degraded {degraded:g} dispatch(es)")
    not_closed = {k: v for k, v in (report.get("breaker") or {}).items()
                  if v != "closed"}
    if not_closed:
        why.append(f"breakers not closed: {not_closed}")
    if interpret:
        why.append("Pallas kernels are in interpret mode")
    return why


def _path_counts() -> dict:
    from lighthouse_tpu import jax_backend as jb

    return {lbl["path"]: v for lbl, v in jb.DISPATCH_BATCHES.items()}


def _degraded_total() -> float:
    from lighthouse_tpu.common import resilience

    return sum(v for _, v in resilience.DEGRADED_TOTAL.items())


def check_device_answered(degraded_before: float, required: str) -> None:
    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.ops import tkernel_calls as tc

    report = jb.dispatch_stage_report()
    counts = _path_counts()
    why = device_answered(report, counts,
                          _degraded_total() - degraded_before,
                          tc._interpret(), required)
    emit("device_answered", ok=not why, paths=counts,
         breakers=report.get("breaker"), reasons=why)
    if why:
        fail("; ".join(why))


# ------------------------------------------------------------ phase 2


def phase_slots(spec, n_validators: int = N_VALIDATORS, warm=None):
    import jax

    from lighthouse_tpu.chain.scale import ScaleChain

    mark = len(_COMPILES)
    t0 = time.perf_counter()
    sc = ScaleChain(n_validators, spec)
    emit("chain", validators=n_validators,
         setup_s=round(time.perf_counter() - t0, 3),
         table_build_s=round(sc.table_build_s, 3),
         compress_s=round(sc.compress_s, 3),
         state_build_s=round(sc.state_build_s, 3),
         chain_init_s=round(sc.chain_init_s, 3),
         table_rows=len(sc.table), compiles=compiles_since(mark))
    for slot in SLOTS:
        mark = len(_COMPILES)
        sc.slot_clock.set_slot(slot)
        sc.chain.per_slot_task()
        t0 = time.perf_counter()
        aggs = sc.make_slot_aggregates(slot)
        prep_s = time.perf_counter() - t0
        if warm is not None:
            warm.wait("hash_to_g2", "triage")
        before = dict(sc.router.stats)
        res = sc.drive_slot(aggs)
        verified = res["aggregates_verified"] - before["aggregates_verified"]
        rejected = (res["attestations_rejected"]
                    - before["attestations_rejected"])
        emit("slot", slot=slot, aggregates=len(aggs),
             attesters=sum(len(sa.message.aggregate.aggregation_bits)
                           for sa in aggs),
             verified=verified, rejected=rejected,
             prep_s=round(prep_s, 3), slot_wall_s=round(res["slot_wall_s"], 3),
             path=_backend().last_path, compiles=compiles_since(mark))
        if not aggs or verified != len(aggs) or rejected:
            fail(f"slot {slot}: {verified}/{len(aggs)} aggregates "
                 f"verified, {rejected} rejected")
    stats = jax.devices()[0].memory_stats() or {}
    emit("slots_memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_in_use=stats.get("bytes_in_use"))
    return sc


# ------------------------------------------------------------ phase 3


def gossip_batch(n: int):
    """``n`` single-pubkey sets, one distinct message each, signed by
    the sequential-key registry (validator i holds sk = i + 1, as
    chain/scale.py builds it), plus a copy with lane 0 tampered (set 0
    carries set 1's signature). Each set carries its validator index, as
    a beacon node's gossip attestation does: with the registry's table in
    HBM the device gathers the pubkeys (the ``indexed`` path)."""
    from lighthouse_tpu.crypto.bls.api import PublicKey, Signature, SignatureSet
    from lighthouse_tpu.crypto.bls.curve import FQ2_B2, AffinePoint, g1_generator
    from lighthouse_tpu.crypto.bls.fields import Fq2
    from lighthouse_tpu.crypto.bls.native_backend import load_native_backend

    native = load_native_backend()
    g1 = g1_generator()
    msgs = [b"chip_smoke gossip" + i.to_bytes(15, "big") for i in range(n)]
    sigs, pks = [], []
    for i, m in enumerate(msgs):
        raw, _ = native.hash_to_g2_bytes(m)  # H(m), C++ SSWU + cofactor
        c = [int.from_bytes(raw[k:k + 48], "big") for k in range(0, 192, 48)]
        h = AffinePoint(Fq2(c[0], c[1]), Fq2(c[2], c[3]), False, FQ2_B2)
        sigs.append(Signature(h.mul(i + 1)))
        pks.append(PublicKey(g1.mul(i + 1)))

    def one(i, sig):
        return SignatureSet.single_pubkey(sig, pks[i], msgs[i], index=i)

    good = [one(i, sigs[i]) for i in range(n)]
    bad = [one(0, sigs[1])] + good[1:]
    return good, bad


def verify_pair(backend, good, bad) -> tuple[bool, bool, str, str]:
    ok = backend.verify_signature_sets(good)
    path_ok = backend.last_path
    tampered = backend.verify_signature_sets(bad)
    return ok, tampered, path_ok, backend.last_path


def phase_gossip(native, warm=None) -> None:
    mark = len(_COMPILES)
    t0 = time.perf_counter()
    good, bad = gossip_batch(GOSSIP_SETS)
    build_s = time.perf_counter() - t0
    if warm is not None:
        warm.wait()
    backend = _backend()
    t0 = time.perf_counter()
    ok, tampered, path_ok, path_bad = verify_pair(backend, good, bad)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ok2 = backend.verify_signature_sets(good)
    warm_s = time.perf_counter() - t0
    sample = slice(0, NATIVE_SAMPLE)
    n_ok = native.verify_signature_sets(good[sample])
    n_bad = native.verify_signature_sets(bad[sample])
    emit("gossip", sets=GOSSIP_SETS, good=ok,
         tampered=tampered, warm_good=ok2, native_sample=NATIVE_SAMPLE,
         native_good=n_ok, native_tampered=n_bad, path=path_ok,
         path_tampered=path_bad, build_s=round(build_s, 3),
         first_pair_s=round(first_s, 3), warm_s=round(warm_s, 3),
         compiles=compiles_since(mark))
    if not (ok is True and ok2 is True and tampered is False):
        fail(f"gossip batch verdicts good={ok}/{ok2} tampered={tampered}")
    if (n_ok, n_bad) != (ok, tampered):
        fail(f"native backend disagrees: native good={n_ok} "
             f"tampered={n_bad}")


# ------------------------------------------------------------ --chips 4


def phase_sharded(n_chips: int) -> None:
    """The 4,096-set batch at one chip and sharded over ``n_chips``."""
    from lighthouse_tpu.parallel import engine

    good, bad = gossip_batch(GOSSIP_SETS)
    backend = _backend()
    verdicts = {}
    for n, sharded in ((1, "0"), (n_chips, "1")):
        os.environ["LHTPU_DEVICES"] = str(n)
        os.environ["LHTPU_SHARDED_VERIFY"] = sharded
        mark = len(_COMPILES)
        t0 = time.perf_counter()
        ok, tampered, path_ok, path_bad = verify_pair(backend, good, bad)
        devices = engine.parallel_report().get("devices")
        verdicts[n] = (ok, tampered)
        emit("sharded" if n > 1 else "one_chip", devices_used=devices,
             good=ok, tampered=tampered, path=path_ok,
             path_tampered=path_bad,
             wall_s=round(time.perf_counter() - t0, 3),
             compiles=compiles_since(mark))
        if n > 1 and (devices != n_chips or "sharded" not in path_ok
                      or "sharded" not in path_bad):
            fail(f"sharded dispatch not engaged: devices={devices} "
                 f"paths={path_ok!r}/{path_bad!r}")
    if verdicts[1] != (True, False) or verdicts[n_chips] != verdicts[1]:
        fail(f"verdicts differ or are wrong: {verdicts}")


# ------------------------------------------------------------ driver


def _backend():
    from lighthouse_tpu.crypto.bls.backends import get_backend

    return get_backend("jax")


def main(argv: list[str]) -> int:
    chips = int(argv[argv.index("--chips") + 1]) if "--chips" in argv else 1
    os.environ["LHTPU_DEVICES"] = str(chips)
    os.environ["LHTPU_PIPELINE_CHUNK"] = CHUNK
    sys.path.insert(0, REPO)

    import jax

    from lighthouse_tpu.common.compile_cache import configure
    from lighthouse_tpu.consensus.config import mainnet_spec
    from lighthouse_tpu.crypto.bls.native_backend import load_native_backend

    cache = configure()
    native = load_native_backend()  # builds from the tracked .cpp files
    if native is None:
        fail("native C++ backend did not build")

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform!r}; there is no CPU fallback")
    if len(devs) < chips:
        fail(f"--chips {chips} but JAX sees {len(devs)} device(s)")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devs), chips_used=chips, compile_cache=cache)
    degraded_before = _degraded_total()

    if chips > 1:
        phase_sharded(chips)
    else:
        from lighthouse_tpu.blsrt import DevicePubkeyTable

        warm = Warmup(int(CHUNK), DevicePubkeyTable.capacity_for(N_VALIDATORS))
        phase_slots(mainnet_spec(), warm=warm)
        phase_gossip(native, warm=warm)
    check_device_answered(degraded_before,
                          "sharded" if chips > 1 else "indexed")
    emit("compiles", **compiles_since(0))
    emit("done", wall_s=round(time.perf_counter() - _T0, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": chips if chips > 1 else len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
