"""Benchmark of lighthouse-tpu's signature verification on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` (a configuration under a traffic
mix) on the machine it is started on: set-up (device, program
warm-up, traffic), a measured window of ``--seconds``, then the check
of the window's answers against the plain reference. Prints
diagnostics as JSON lines on standard error, the numbers compared with
their limits as the last lines there, and one JSON result as the last
line of standard output. ``--trace 1`` records a profiler trace of the
window and reports the cell's per-layer metrics instead of its
end-to-end ones.

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when a file the cell names is missing.
There is no CPU fallback.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv) -> int:
    args = parse(argv)
    if ROOT not in sys.path:
        sys.path.insert(1, ROOT)
    from harness import context, spec

    try:
        cell = spec.load_cell(args.workload, ROOT, BENCH)
    except spec.SpecError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2
    ctx = context.Context(args, cell, T_START)
    try:
        result = cell.runner.run(ctx)
    except context.NoDevice as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 3
    finally:
        ctx.close()
    ctx.emit("done", wall_s=ctx.elapsed())
    ctx.print_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
