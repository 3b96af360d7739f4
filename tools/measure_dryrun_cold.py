"""Measure dryrun_multichip(8) cold wall time.

Points the persistent compile cache at an empty directory through
JAX_COMPILATION_CACHE_DIR (which lighthouse_tpu/common/compile_cache.py
honours) so every XLA compile is cold, then runs the gate:

  python tools/measure_dryrun_cold.py [cache_dir]
"""

import os
import sys
import tempfile
import time

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_COMPILATION_CACHE_DIR"] = (
    sys.argv[1] if len(sys.argv) > 1
    else tempfile.mkdtemp(prefix="cold_jax_cache_")
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import __graft_entry__  # noqa: E402

t0 = time.time()
__graft_entry__.dryrun_multichip(8)
print(f"TOTAL COLD WALL: {time.time() - t0:.1f}s", flush=True)
