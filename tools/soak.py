#!/usr/bin/env python
"""Multi-epoch soak CLI over lighthouse_tpu.loadgen.soak.

Runs ServingLoop endurance epochs under a deterministic chaos schedule
and emits one ``soak_epoch`` JSON line per epoch plus a final
``soak_verdict`` line (exit 0 iff the verdict passes). CPU-runnable on
the virtual clock; ``--wall-clock`` serves in real time on hardware.

Examples:

    # the ISSUE 7 acceptance run: 8 epochs, transient chaos at epoch 2,
    # a permanent fault at epoch 4, chaos-free digest-parity replay
    python tools/soak.py --epochs 8 \\
        --chaos "2:dispatch:transient:3;4:device_sync:permanent:1"

    # leak hunting: long steady run, no chaos, bigger streams
    python tools/soak.py --epochs 32 --committees 8 --unagg 32

The chaos grammar is ``epoch:stage:kind:count`` items joined by ``;``
(also readable from LHTPU_CHAOS_SCHEDULE); ``kind`` takes the
LHTPU_FAULT_INJECT kinds plus the ``transient``/``permanent`` aliases.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lighthouse_tpu.common import knobs  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--chaos", default=knobs.knob("LHTPU_CHAOS_SCHEDULE"),
                    help="epoch:stage:kind:count[;...] chaos schedule")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--slots", type=int, default=2,
                    help="slots per epoch stream")
    ap.add_argument("--sps", type=float, default=2.0,
                    help="seconds per slot (pre-time_scale)")
    ap.add_argument("--time-scale", type=float, default=1.0)
    ap.add_argument("--committees", type=int, default=2)
    ap.add_argument("--committee-size", type=int, default=2)
    ap.add_argument("--unagg", type=int, default=4,
                    help="unaggregated attestations per slot")
    ap.add_argument("--sync", type=int, default=None,
                    help="sync signatures per slot (default: spec-shaped"
                         " derivation from the committee shape)")
    ap.add_argument("--weather",
                    default=knobs.knob("LHTPU_WEATHER_SCHEDULE"),
                    help="epoch:axis:value[;...] chain-weather plan "
                         "(axes: reorg_storm / non_finality / "
                         "slashing_flood / sync_boundary; epoch * = all)")
    ap.add_argument("--poison", type=float, default=0.25)
    ap.add_argument("--key-pool", type=int, default=8)
    ap.add_argument("--recovery-epochs", type=int, default=2,
                    help="re-promotion budget after the last chaos epoch")
    ap.add_argument("--wall-clock", action="store_true",
                    help="serve in real time instead of the virtual clock")
    ap.add_argument("--no-replay", action="store_true",
                    help="skip the chaos-free digest-parity replay")
    ap.add_argument("--backend", default="jax")
    args = ap.parse_args()

    # Small-bucket serving defaults (the fast-tier compile buckets):
    # explicit env always wins.
    os.environ.setdefault("LHTPU_VERDICT_GROUPS", "2")
    os.environ.setdefault("LHTPU_PIPELINE", "0")
    os.environ.setdefault("LHTPU_RETRY_BASE_MS", "0")
    # Breakers must be able to half-open within the run's wall time —
    # the stock 30 s cooldown would outlive a whole virtual soak.
    os.environ.setdefault("LHTPU_BREAKER_COOLDOWN_S", "0.05")

    # Persistent compile cache (same store as the test suite): a soak
    # measures lifetime behavior, not compile latency — epoch 0 should
    # reload the fast-tier buckets instead of paying minutes of XLA:CPU.
    from lighthouse_tpu.common.compile_cache import configure

    configure()

    from lighthouse_tpu.common import resilience
    from lighthouse_tpu.loadgen.serve import ServeConfig
    from lighthouse_tpu.loadgen.soak import (
        SoakConfig, SoakRunner, parse_chaos_schedule,
    )
    from lighthouse_tpu.loadgen.traffic import TrafficConfig

    resilience.reset()  # pick up the cooldown above
    cfg = SoakConfig(
        epochs=args.epochs,
        seed=args.seed,
        backend=args.backend,
        wall_clock=args.wall_clock,
        recovery_epochs=args.recovery_epochs,
        replay=not args.no_replay,
        weather=args.weather,
        traffic=TrafficConfig(
            slots=args.slots,
            seconds_per_slot=args.sps,
            committees_per_slot=args.committees,
            committee_size=args.committee_size,
            unaggregated_per_slot=args.unagg,
            sync_per_slot=args.sync,
            poison_rate=args.poison,
            key_pool=args.key_pool,
            seed=args.seed,
            time_scale=args.time_scale,
        ),
        serve=ServeConfig.from_env(
            batch_target=max(2, args.committees * args.committee_size),
            batch_deadline_ms=250.0,
        ),
    )
    runner = SoakRunner(cfg, chaos=parse_chaos_schedule(args.chaos))
    result = runner.run()
    return 0 if result["verdict"] == "pass" else 1


if __name__ == "__main__":
    sys.exit(main())
