"""Main-path kernels compiled by Mosaic for a described v5e (no chip).

The CPU suite runs every kernel in Pallas interpret mode, which never
lowers or compiles one. These tests run the chip's own compiler on the
production kernels at a 128-lane tile, with ``interpret=False`` and the
platform gate steered to the chip's choices (MXU fold, 64 MB scoped-VMEM
grant): what Mosaic would refuse on the chip — VMEM over budget, a slice
not aligned to the tiling, an unsupported primitive — fails here. The
Miller loop, final exponentiation, resident hash-to-G2 map and the whole
fused verify program take from half a minute to minutes each and stay in
``tools/lowering_smoke.py``.

The topology is described inside a fixture (never at import): only the
worker that runs this file loads libtpu, and every worker collects the
same tests.
"""

import jax
import pytest

from lighthouse_tpu.ops import chip_compile

LANES = 128  # one lane tile: each compile here takes seconds

CASES = ["scalar_mul_g1", "scalar_mul_g2", "subgroup_g2_fast", "msm_accum"]


@pytest.fixture(scope="module")
def one_chip():
    try:
        _, sharding = chip_compile.describe_v5e()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return sharding


@pytest.fixture
def as_chip(monkeypatch):
    """Trace as the chip would, with the persistent cache off (a
    described-chip entry is written but cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    from lighthouse_tpu.ops import tkernel as tk

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    monkeypatch.setattr(tk, "on_tpu", lambda: True)
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", CASES)
def test_kernel_compiles_for_v5e(one_chip, as_chip, name):
    fn, args, static = chip_compile.kernel_cases(LANES)[name]
    got = chip_compile.compile_case(fn, args, static, one_chip)
    print(f"{name}: lower {got['lower_s']:.1f}s compile "
          f"{got['compile_s']:.1f}s {got['memory']}")
    assert got["tpu_custom_call"], f"{name}: no Mosaic kernel in the program"


def test_vmem_grant_reaches_the_kernel(as_chip):
    """The steered gate is what a chip trace sees: the 64 MB grant."""
    from lighthouse_tpu.ops import tkernel as tk

    params = tk.vmem_params()
    assert params is not None
    assert params.vmem_limit_bytes == 64 * 1024 * 1024
