"""chip_smoke.py on the CPU: it must refuse to pass without the chip.

The phase-4 gate is fed stage reports of runs that did not stay on the
fused Pallas path — a classic or native dispatch, the native_fallback
stage, a degradation, a breaker not closed, interpret mode, no gather
from the HBM table — and must name each; and the script itself must
exit non-zero, with no ok line, when JAX finds no TPU. The programs it
builds ahead of use must be the ones JaxBackend then calls.
"""

import json

import jax
import jax.numpy as jnp
import pytest

import chip_smoke

FUSED_REPORT = {
    "path": "indexed+pipeline",
    "stages_ms": {"pack": 1.0, "hash_to_curve": 2.0, "dispatch": 3.0},
    "breaker": {"fused": "closed", "classic": "closed", "native": "closed"},
}
FUSED_COUNTS = {"indexed": 16.0, "fused+triage": 2.0}


def test_fused_run_passes():
    assert chip_smoke.device_answered(FUSED_REPORT, FUSED_COUNTS, 0.0,
                                      False, "indexed") == []


@pytest.mark.parametrize("report, counts, degraded, interpret, needle", [
    ({"path": "classic"}, {"classic": 1.0}, 0.0, False, "'classic'"),
    ({"path": "native-fallback"}, {"native-fallback": 1.0}, 0.0, False,
     "native-fallback"),
    ({}, {"classic-indexed+triage": 2.0}, 0.0, False, "classic-indexed"),
    ({"path": "python-fallback"}, {}, 0.0, False, "python-fallback"),
    ({"path": "indexed+sharded-fallback"}, {}, 0.0, False, "fallback"),
    ({"stages_ms": {"native_fallback": 4.0}}, {}, 0.0, False,
     "native_fallback stage"),
    ({}, {}, 1.0, False, "degraded"),
    ({"breaker": {"fused": "open", "classic": "closed"}}, {}, 0.0, False,
     "breakers"),
    ({}, {}, 0.0, True, "interpret"),
    ({}, {"indexed": 0.0, "fused": 16.0}, 0.0, False, "'indexed' path"),
])
def test_a_run_off_the_fused_path_fails(report, counts, degraded,
                                        interpret, needle):
    why = chip_smoke.device_answered(
        {**FUSED_REPORT, **report}, {**FUSED_COUNTS, **counts}, degraded,
        interpret, "indexed",
    )
    assert any(needle in w for w in why), why


def test_no_tpu_exits_nonzero_without_ok_line(monkeypatch, tmp_path,
                                              capsys):
    monkeypatch.setattr(chip_smoke, "LOG", str(tmp_path / "smoke.jsonl"))
    for knob in ("LHTPU_DEVICES", "LHTPU_PIPELINE_CHUNK"):
        monkeypatch.setenv(knob, "")  # restored after main() sets them
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines, "the failure is reported on stdout"
    assert json.loads(lines[-1])["phase"] == "failed"
    assert not any('"ok": true' in ln for ln in lines)


def _avals(tree):
    return jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(a.dtype)) if hasattr(a, "shape")
        else a, tree)


def test_warm_programs_are_what_dispatch_calls(monkeypatch):
    """Warmup compiles each program before the backend's first call; a
    program whose avals or static flags differ from that call would be
    compiled a second time on the chip, minutes each. Dispatch runs here
    with the chip's choices (fused program, device hash-to-G2, host
    pubkey aggregation, no interpret mode) and spies in place of the
    jitted programs, on one 256-set gossip chunk with validator indices
    and one slot-shaped triage batch (multi-key sets without indices)."""
    from lighthouse_tpu import blsrt
    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.crypto.bls.api import SignatureSet
    from lighthouse_tpu.ops import tkernel as tk
    from lighthouse_tpu.ops import tkernel_htc as th

    sets = int(chip_smoke.CHUNK)
    for knob, value in {"LHTPU_FUSED_VERIFY": "1", "LHTPU_DEVICE_HTC": "1",
                        "LHTPU_HOST_AGG": "1", "LHTPU_RESILIENCE": "0",
                        "LHTPU_HTC_BATCH_CACHE": "0", "LHTPU_DEVICES": "1",
                        "LHTPU_PIPELINE_CHUNK": chip_smoke.CHUNK}.items():
        monkeypatch.setenv(knob, value)
    monkeypatch.setattr(tk, "on_tpu", lambda: True)
    gossip, _ = chip_smoke.gossip_batch(sets)
    table = blsrt.DevicePubkeyTable()
    table.append_pubkeys([s.signing_keys[0] for s in gossip])
    monkeypatch.setattr(blsrt, "_TABLE", table)
    want = {name: [(fn, _avals(args), static)
                   for fn, args, static in cases]
            for name, cases in chip_smoke.warm_programs(
                sets, blsrt.DevicePubkeyTable.capacity_for(sets)).items()}

    got: list = []

    class Reached(Exception):
        pass

    def spy(module, name, result=None):
        program = getattr(module, name)

        def call(*args, **static):
            got.append((program, _avals(args), static))
            if result is None:
                raise Reached(name)
            return result(*args)
        monkeypatch.setattr(module, name, call)

    def jacobian(u, *_):
        return (jnp.zeros((2, 48, u.shape[0]), jnp.int32),) * 3

    def affine(q, *_):
        n = q[0].shape[-1]
        return (*jacobian(jnp.zeros((n,)))[:2], jnp.zeros((n,), bool))

    spy(th, "_map_to_g2_front_jit", jacobian)
    spy(th, "_map_to_g2_back_jit", affine)
    spy(jb, "_verify_fused_indexed_jit")
    spy(jb, "_verify_fused_grouped_jit")

    backend = jb.JaxBackend()
    with pytest.raises(Reached):
        backend.verify_signature_sets(gossip)
    assert got == want["hash_to_g2"] + want["indexed"]
    assert backend.last_stage_seconds  # the chunk went through dispatch

    half = sets // 4
    slot = [SignatureSet.multiple_pubkeys(
        s.signature, [s.signing_keys[0], gossip[i + half].signing_keys[0]],
        s.message) for i, s in enumerate(gossip[:half])]
    slot += gossip[half:3 * half]  # 192 sets, as a mainnet slot's 64 x 3
    got.clear()
    with pytest.raises(Reached):
        backend.verify_signature_sets_triaged(slot)
    assert got == want["hash_to_g2"] + want["triage"]


def test_warmup_compiles_every_program_and_hands_over_failures(
        monkeypatch, tmp_path, capsys):
    """Warmup builds each program once, triage only after hash-to-G2,
    and a compile that fails in its thread fails the waiting caller."""
    monkeypatch.setattr(chip_smoke, "LOG", str(tmp_path / "smoke.jsonl"))
    order = []

    class Program:
        __name__ = "program"

        def __init__(self, name, error=None):
            self.name, self.error = name, error

        def lower(self, *args, **static):
            return self

        def compile(self):
            if self.error:
                raise self.error
            order.append(self.name)

    def programs(error):
        return lambda sets, rows: {
            name: [(Program(name, error if name == "indexed" else None),
                    (), {})]
            for name in ("hash_to_g2", "triage", "indexed")}

    monkeypatch.setattr(chip_smoke, "warm_programs", programs(None))
    chip_smoke.Warmup(4, 8).wait()
    assert sorted(order) == ["hash_to_g2", "indexed", "triage"]
    assert order.index("hash_to_g2") < order.index("triage")

    monkeypatch.setattr(chip_smoke, "warm_programs",
                        programs(RuntimeError("refused")))
    with pytest.raises(RuntimeError, match="refused"):
        chip_smoke.Warmup(4, 8).wait()
    lines = capsys.readouterr().out.splitlines()
    assert sum('"phase": "warm"' in ln for ln in lines) == 3 + 2
