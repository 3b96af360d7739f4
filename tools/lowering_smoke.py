"""Mosaic compile rehearsal for a described v5e (no chip needed).

Compiles the main-path Pallas kernels at their production width, and the
whole fused verify program for a 4,096-set batch, with the TPU compiler
for a described ``v5e:2x2`` chip: ``interpret=False``, the MXU fold and
the 64 MB scoped-VMEM grant as on the chip. What Mosaic refuses here
(VMEM, tiling alignment, an unsupported primitive) it would refuse on
the chip, so this runs before any chip call that touches ops/:

  JAX_PLATFORMS=cpu python tools/lowering_smoke.py                # all
  JAX_PLATFORMS=cpu python tools/lowering_smoke.py --kernels-only
  JAX_PLATFORMS=cpu python tools/lowering_smoke.py --only miller,final_exp
  JAX_PLATFORMS=cpu python tools/lowering_smoke.py --knobs lazy,mxu_carry
  JAX_PLATFORMS=cpu python tools/lowering_smoke.py --smoke  # chip_smoke's programs
  python tools/lowering_smoke.py --run     # on the chip: one tiny verify

Every case prints one JSON line (lower/compile seconds, whether a
``tpu_custom_call`` is in the program, memory analysis); the exit code
is 1 if any case failed. A compile passing here is not a chip run.
``--run`` executes a 4-set fused verify on the attached chip, in this
one process and nothing else. The quick tier-1 subset is
tests/test_chip_compile.py.
"""

from __future__ import annotations

import json
import os
import resource
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# Knob sets the fused program can be compiled under (--knobs). "default"
# is what the chip runs. Mosaic refuses both PR-18 carry knobs as of
# PR 21 (CHANGES.md): lazy reduce needs 65.78 MB of scoped VMEM in the
# Miller kernel (grant 64 MB); the MXU carry emits an i1 -> i32 vector
# register bitcast Mosaic cannot lower. They stay off.
KNOB_SETS = {
    "default": {},
    "lazy": {"LHTPU_LAZY_REDUCE": "1"},
    "mxu_carry": {"LHTPU_MXU_CARRY": "1"},
}
# The kernels of the main path, cheapest first.
MAIN_KERNELS = ("scalar_mul_g1", "subgroup_g2_fast", "msm_accum",
                "scalar_mul_g2", "final_exp", "map_to_g2_resident",
                "miller")


def _arg(flag: str, default: str | None = None) -> str | None:
    if flag in sys.argv:
        return sys.argv[sys.argv.index(flag) + 1]
    return default


def _emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


def rehearse() -> int:
    from lighthouse_tpu.common.compile_cache import disable_persistent_cache

    disable_persistent_cache()  # a described-chip entry cannot be read back

    from lighthouse_tpu import jax_backend as jb
    from lighthouse_tpu.ops import chip_compile as cc

    _, one_chip = cc.describe_v5e()
    only = _arg("--only")
    names = only.split(",") if only else list(MAIN_KERNELS)
    knob_names = (_arg("--knobs", "default") or "").split(",")
    fails = 0

    def run(label: str, knob_name: str, case) -> None:
        nonlocal fails
        fn, args, static = case
        t0 = time.perf_counter()
        row = {"case": label, "knobs": knob_name}
        try:
            row.update(cc.compile_case(fn, args, static, one_chip))
            row["ok"] = bool(row["tpu_custom_call"])
        except Exception as exc:  # lhtpu: ignore[LH502] -- a refusal is this tool's result: reported per case, counted in the exit code
            row.update(ok=False, error=f"{type(exc).__name__}: {exc}"[:4000])
        row["wall_s"] = round(time.perf_counter() - t0, 1)
        row["max_rss_gb"] = round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20, 2)
        for k in ("lower_s", "compile_s"):
            if k in row:
                row[k] = round(row[k], 1)
        fails += not row["ok"]
        _emit(row)

    if "--smoke" in sys.argv:
        import chip_smoke
        from lighthouse_tpu.blsrt import DevicePubkeyTable

        rows = DevicePubkeyTable.capacity_for(chip_smoke.N_VALIDATORS)
        with cc.steer_tpu():
            programs = chip_smoke.warm_programs(int(chip_smoke.CHUNK), rows)
            for name, cases in programs.items():
                for case in cases:
                    run(f"{name}:{case[0].__name__}", "default", case)
        return 1 if fails else 0
    if names != ["none"]:
        with cc.steer_tpu():
            cases = cc.kernel_cases()
            for name in names:
                run(name, "default", cases[name])
    if "--kernels-only" not in sys.argv:
        for knob_name in knob_names:
            with cc.steer_tpu(KNOB_SETS[knob_name]):
                run(f"fused_verify_S{cc.S_REAL}", knob_name,
                    jb.fused_program(cc.S_REAL))
    return 1 if fails else 0


def run_on_chip() -> int:
    """One 4-set fused verify on the attached chip, good and tampered."""
    import jax

    from lighthouse_tpu.common.compile_cache import configure

    configure()
    if jax.devices()[0].platform != "tpu":
        print(f"--run needs a TPU (got {jax.devices()[0].platform})",
              file=sys.stderr)
        return 1

    from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet
    from lighthouse_tpu.jax_backend import JaxBackend

    # 4 sets is under the small-batch native route: pin the device.
    os.environ["LHTPU_HOST_FALLBACK"] = "0"

    S = 4
    sks = [SecretKey.from_int(i + 101) for i in range(S)]
    msgs = [i.to_bytes(32, "big") for i in range(S)]
    sets = [SignatureSet.single_pubkey(sk.sign(m), sk.public_key(), m)
            for sk, m in zip(sks, msgs)]
    bad = list(sets)
    bad[0] = SignatureSet.single_pubkey(sets[1].signature,
                                        sets[0].signing_keys[0], msgs[0])
    backend = JaxBackend()
    t0 = time.perf_counter()
    good_v = backend.verify_signature_sets(sets)
    bad_v = backend.verify_signature_sets(bad)
    row = {"case": f"fused_verify_S{S}_on_chip", "good": good_v,
           "tampered": bad_v, "path": backend.last_path,
           "wall_s": round(time.perf_counter() - t0, 1)}
    row["ok"] = good_v is True and bad_v is False
    _emit(row)
    return 0 if row["ok"] else 1


def main() -> int:
    if "--run" in sys.argv:
        return run_on_chip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    t0 = time.perf_counter()
    rc = rehearse()
    print(f"[lowering-smoke] {'PASS' if rc == 0 else 'FAILED'} in "
          f"{time.perf_counter() - t0:.0f}s", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
