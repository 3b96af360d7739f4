"""The control of the gossip cell's comparison: the plain reference put
in the program's place with one guarantee of the configuration broken
(a batch verdict that covers only the first half of its sets). It has
to come out not correct.

As a test it runs on the tiny registry. At the cell's own size (the
1M-validator registry, 1,024-set calls) it is a script:

    python3 benchmark/tests/test_control.py --seconds 10 --seeds 11 22 33

which prints one line per seed with the number compared,
``verdict_mismatches``, as the control reads it.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import conftest  # noqa: E402,F401  (puts the benchmark on sys.path)
import fakes  # noqa: E402

CONTROL = "half_batch"


def test_control_is_not_correct(tmp_path):
    cell = fakes.tiny_cell(tmp_path)
    for seed in (101, 202, 303):
        out = fakes.drive(cell, fakes.with_fault(CONTROL), seed=seed,
                          seconds=1.5)
        assert out["correct"] is False
        assert out["checks"]["verdict_mismatches"]["value"] >= 1


def main(argv) -> int:
    from harness import spec

    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="allsubnets-gossip")
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in args.seeds:
        out = fakes.drive(cell, fakes.with_fault(CONTROL), seed=seed,
                          seconds=args.seconds)
        print(json.dumps({"control": CONTROL, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
