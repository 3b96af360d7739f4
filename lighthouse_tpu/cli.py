"""The `lighthouse-tpu` CLI (reference: lighthouse/src/main.rs clap
tree + beacon_node/src/cli.rs + validator_client/src/cli.rs +
account_manager + lcli subcommands).

Subcommands:

* ``bn``        — run a beacon node (interop genesis or checkpoint sync,
  optional HTTP API / metrics / slasher).
* ``vc``        — run a validator client against one or more BNs.
* ``account``   — wallet/validator tooling: keystore create/import/list
  (account_manager).
* ``lcli``      — dev utilities: interop-genesis, skip-slots,
  transition-blocks, parse-ssz, insecure-validators (testing/lcli).
* ``db``        — database inspect/version/migrate/compact (database_manager).
* ``bench``     — the BLS device benchmark (bench.py's workload).
* ``boot-node`` — standalone discovery-only bootnode (boot_node).

Every subcommand melts flags into the component configs exactly as the
reference's get_config does; `--spec minimal|mainnet` picks the preset.
"""

from __future__ import annotations

import argparse
import json
import sys


def _spec_for(name: str):
    from .consensus.config import mainnet_spec, minimal_spec

    return minimal_spec() if name == "minimal" else mainnet_spec()


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--spec", choices=("minimal", "mainnet"), default="mainnet")
    p.add_argument("--debug-level", default="info",
                   choices=("debug", "info", "warn", "error", "crit"))


def build_parser() -> argparse.ArgumentParser:
    root = argparse.ArgumentParser(
        prog="lighthouse-tpu",
        description="TPU-native Ethereum consensus client framework",
    )
    sub = root.add_subparsers(dest="command", required=True)

    bn = sub.add_parser("bn", help="beacon node")
    _add_common(bn)
    bn.add_argument("--datadir", default=None)
    bn.add_argument("--http", action="store_true")
    bn.add_argument("--http-port", type=int, default=5052)
    bn.add_argument("--metrics", action="store_true")
    bn.add_argument("--metrics-port", type=int, default=0)
    bn.add_argument("--validator-monitor-auto", action="store_true")
    bn.add_argument("--slasher", action="store_true")
    bn.add_argument("--slasher-devices", type=int, default=0,
                    help="with --slasher: chips that hold the slasher's "
                         "min/max-target planes, sharded by validator "
                         "chunk (0 = on the host, in the database)")
    bn.add_argument("--interop-validators", type=int, default=64)
    bn.add_argument("--checkpoint-sync-url", default=None)
    bn.add_argument("--boot-nodes", default=None,
                    help="comma-separated bootnode URLs to register with")
    bn.add_argument("--backend", default=None,
                    choices=(None, "python", "jax", "fake"))
    bn.add_argument("--slots", type=int, default=0,
                    help="run N slots then exit (0 = forever)")

    vc = sub.add_parser("vc", help="validator client")
    _add_common(vc)
    vc.add_argument("--beacon-nodes", default="http://127.0.0.1:5052",
                    help="comma-separated BN URLs (fallback order)")
    vc.add_argument("--interop-validators", type=int, default=0,
                    help="use deterministic interop keys [0..N)")
    vc.add_argument("--keystores", nargs="*", default=[],
                    help="EIP-2335 keystore JSON paths")
    vc.add_argument("--password", default="")
    vc.add_argument("--slashing-protection-db", default=":memory:")
    vc.add_argument("--slots", type=int, default=0)

    account = sub.add_parser("account", help="key management")
    _add_common(account)
    asub = account.add_subparsers(dest="action", required=True)
    new = asub.add_parser("new", help="derive + encrypt a validator keystore")
    new.add_argument("--seed-hex", required=True)
    new.add_argument("--index", type=int, default=0)
    new.add_argument("--password", required=True)
    new.add_argument("--out", default="-")
    imp = asub.add_parser("inspect", help="inspect a keystore")
    imp.add_argument("path")
    imp.add_argument("--password", default=None)
    wallet = asub.add_parser(
        "wallet", help="EIP-2386 wallet create/recover/derive"
    )
    wallet.add_argument("action2", choices=("create", "recover", "validator"),
                        metavar="create|recover|validator")
    wallet.add_argument("--name", default="wallet")
    wallet.add_argument("--password", required=True)
    wallet.add_argument("--seed-hex", default=None,
                        help="recover: the seed backup; create: optional")
    wallet.add_argument("--wallet-file", default=None,
                        help="validator: wallet JSON path (updated in place)")
    wallet.add_argument("--keystore-password", default=None)
    wallet.add_argument("--count", type=int, default=1)
    wallet.add_argument("--out", default="-")

    ex = asub.add_parser(
        "exit", help="sign + publish a voluntary exit (validator exit flow)"
    )
    ex.add_argument("--keystore", required=True)
    ex.add_argument("--password", required=True)
    ex.add_argument("--validator-index", type=int, required=True)
    ex.add_argument("--epoch", type=int, required=True)
    ex.add_argument("--beacon-node", default=None,
                    help="BN URL to publish to; omit to just print the exit")
    ex.add_argument("--genesis-validators-root", required=True)
    ex.add_argument("--current-epoch", type=int, default=None,
                    help="the chain's current epoch (fetched from the BN "
                         "when --beacon-node is given; defaults to --epoch)")

    sp = asub.add_parser(
        "slashing-protection", help="EIP-3076 interchange import/export"
    )
    sp.add_argument("action2", choices=("export", "import"),
                    metavar="export|import")
    sp.add_argument("--db", required=True,
                    help="slashing protection SQLite path")
    sp.add_argument("--genesis-validators-root", required=True)
    sp.add_argument("--file", default="-")

    lcli = sub.add_parser("lcli", help="dev utilities")
    _add_common(lcli)
    lsub = lcli.add_subparsers(dest="action", required=True)
    ig = lsub.add_parser("interop-genesis")
    ig.add_argument("--validator-count", type=int, default=64)
    ig.add_argument("--genesis-time", type=int, default=1_600_000_000)
    nt = lsub.add_parser("new-testnet",
                         help="write a network config bundle + genesis state")
    nt.add_argument("--out", required=True, help="output directory")
    nt.add_argument("--validator-count", type=int, default=64)
    nt.add_argument("--genesis-time", type=int, default=1_600_000_000)
    nt.add_argument("--name", default="local-testnet")
    nt.add_argument("--altair-fork-epoch", type=int, default=None)
    nt.add_argument("--bellatrix-fork-epoch", type=int, default=None)
    eg = lsub.add_parser("eth1-genesis",
                         help="genesis state from signed deposits (the "
                              "deposit-contract path)")
    eg.add_argument("--validator-count", type=int, default=16)
    eg.add_argument("--eth1-block-hash", default="0x" + "42" * 32)
    eg.add_argument("--eth1-timestamp", type=int, default=1_606_824_000)
    dd = lsub.add_parser("deploy-deposit-contract",
                         help="deploy the deposit contract over eth1 "
                              "JSON-RPC and optionally submit "
                              "deterministic validator deposits")
    dd.add_argument("--eth1-http", required=True,
                    help="eth1 JSON-RPC endpoint")
    dd.add_argument("--confirmations", type=int, default=1)
    dd.add_argument("--validator-count", type=int, default=None,
                    help="submit deposits for this many insecure "
                         "(interop-key) validators after deploying")
    dd.add_argument("--bytecode-file", default=None,
                    help="compiled contract creation bytecode (hex); "
                         "default is the mock-EL marker payload")
    sk = lsub.add_parser("skip-slots")
    sk.add_argument("--slots", type=int, required=True)
    sk.add_argument("--validator-count", type=int, default=16)
    ps = lsub.add_parser("parse-ssz")
    ps.add_argument("--type", dest="ssz_type", required=True,
                    choices=("attestation", "signed_block", "state"))
    ps.add_argument("path")
    tb = lsub.add_parser("transition-blocks",
                         help="apply SSZ block(s) to an SSZ pre-state")
    tb.add_argument("--pre-state", required=True)
    tb.add_argument("--block", required=True, nargs="+")
    tb.add_argument("--post-state", default=None,
                    help="write the post state SSZ here")
    tb.add_argument("--no-signature-verification", action="store_true")
    iv = lsub.add_parser("insecure-validators",
                         help="write interop keystores + secrets dir")
    iv.add_argument("--count", type=int, required=True)
    iv.add_argument("--base-dir", required=True)

    db = sub.add_parser("db", help="database tooling")
    _add_common(db)
    db.add_argument("--datadir", required=True)
    db.add_argument("action", choices=("inspect", "version", "migrate", "compact"))
    db.add_argument("--target", type=int, default=None,
                    help="migrate: target schema version (default: current)")

    bench = sub.add_parser("bench", help="BLS device benchmark")
    bench.add_argument("--quick", action="store_true")

    boot = sub.add_parser(
        "boot-node", help="standalone discovery-only bootnode (boot_node binary)"
    )
    _add_common(boot)
    boot.add_argument("--port", type=int, default=0)
    boot.add_argument("--host", default="127.0.0.1")

    return root


# ------------------------------------------------------------------ commands
def run_bn(args) -> int:
    from .common.logging import StructuredLogger
    from .common.malloc_utils import configure_memory_allocator
    from .node import ClientBuilder, ClientConfig

    configure_memory_allocator()  # lighthouse/src/main.rs does this first
    log = StructuredLogger(level=args.debug_level)
    spec = _spec_for(args.spec)
    cfg = ClientConfig(
        datadir=args.datadir,
        validator_count=args.interop_validators,
        http_enabled=args.http,
        http_port=args.http_port,
        metrics_enabled=args.metrics,
        metrics_port=args.metrics_port,
        validator_monitor_auto=args.validator_monitor_auto,
        slasher_enabled=args.slasher,
        slasher_devices=args.slasher_devices,
        backend=args.backend,
        manual_clock=args.slots > 0,
    )
    builder = ClientBuilder(cfg, spec, log)
    if args.datadir:
        builder.disk_store(args.datadir)
    else:
        builder.memory_store()
    if args.checkpoint_sync_url:
        from .api import BeaconNodeClient

        builder.checkpoint_sync(BeaconNodeClient(url=args.checkpoint_sync_url))
    else:
        builder.interop_genesis()
    node = builder.build()
    if args.boot_nodes and node.network is not None:
        from .network.discovery import sync_with_boot_node

        for url in args.boot_nodes.split(","):
            try:
                learned = sync_with_boot_node(node.network.discovery, url.strip())
                log.info("bootnode sync", url=url.strip(), learned=learned)
            except (OSError, ValueError, KeyError) as e:
                log.warn("bootnode unusable", url=url.strip(), error=repr(e))
    log.info(
        "beacon node ready",
        spec=args.spec,
        http=node.http.url if node.http else "off",
        head=node.chain.head().root.hex()[:8],
    )
    if args.slots > 0:
        for _ in range(args.slots):
            node.chain.slot_clock.advance_slot()
            node.tick_slot()
        log.info("done", head_slot=int(node.chain.head().block.message.slot))
        node.stop()
        return 0
    node.start()
    node.executor.block_on_shutdown()
    return 0


def run_vc(args) -> int:
    from .api import BeaconNodeClient
    from .common.logging import StructuredLogger
    from .validator import BeaconNodeFallback, SlashingDatabase, ValidatorClient
    from .validator.keystore import Keystore

    log = StructuredLogger(level=args.debug_level)
    spec = _spec_for(args.spec)
    urls = [u.strip() for u in args.beacon_nodes.split(",") if u.strip()]
    clients = [BeaconNodeClient(url=u) for u in urls]
    client = clients[0] if len(clients) == 1 else BeaconNodeFallback(clients)

    genesis = (clients[0].get_genesis())["data"]
    gvr = bytes.fromhex(genesis["genesis_validators_root"].removeprefix("0x"))
    vc = ValidatorClient(
        client, spec, gvr, slashing_db=SlashingDatabase(args.slashing_protection_db)
    )
    if args.interop_validators:
        from .consensus.genesis import interop_keypairs

        vc.add_validators(interop_keypairs(args.interop_validators))
    for path in args.keystores:
        with open(path) as f:
            vc.add_validators([Keystore.from_json(f.read()).decrypt(args.password)])
    log.info("validator client ready", keys=len(vc.store.voting_pubkeys()))

    import time

    seconds = spec.SECONDS_PER_SLOT
    genesis_time = int(genesis["genesis_time"])
    count = 0
    while args.slots == 0 or count < args.slots:
        now = time.time()
        slot = max(0, int(now - genesis_time) // seconds)
        stats = vc.run_slot(slot)
        log.info("slot done", slot=slot, **stats)
        count += 1
        if args.slots == 0:
            time.sleep(max(0.0, (genesis_time + (slot + 1) * seconds) - time.time()))
    return 0


def run_account(args) -> int:
    from .validator.keystore import Keystore, derive_validator_keys

    if args.action == "new":
        seed = bytes.fromhex(args.seed_hex.removeprefix("0x"))
        signing, _ = derive_validator_keys(seed, args.index)
        ks = Keystore.encrypt(
            signing, args.password, path=f"m/12381/3600/{args.index}/0/0"
        )
        out = ks.to_json()
        if args.out == "-":
            print(out)
        else:
            with open(args.out, "w") as f:
                f.write(out)
        return 0
    if args.action == "inspect":
        with open(args.path) as f:
            ks = Keystore.from_json(f.read())
        info = {"pubkey": ks.pubkey, "path": ks.path, "uuid": ks.uuid}
        if args.password is not None:
            ks.decrypt(args.password)
            info["decrypts"] = True
        print(json.dumps(info, indent=2))
        return 0
    if args.action == "wallet":
        from .validator.wallet import Wallet

        if args.action2 in ("create", "recover"):
            seed = (
                bytes.fromhex(args.seed_hex.removeprefix("0x"))
                if args.seed_hex
                else None
            )
            if args.action2 == "recover" and seed is None:
                print(json.dumps({"error": "recover requires --seed-hex"}),
                      file=sys.stderr)
                return 1
            wallet = Wallet.create(args.name, args.password, seed=seed)
            # round-trip guard: the wallet must decrypt back to the seed
            recovered = wallet.decrypt_seed(args.password)
            if seed is not None and recovered != seed:
                print(json.dumps({"error": "seed round-trip failed"}),
                      file=sys.stderr)
                return 1
            out = wallet.to_json()
            if args.out == "-":
                print(out)
            else:
                with open(args.out, "w") as f:
                    f.write(out)
            if args.action2 == "create" and args.seed_hex is None:
                # the backup material (the reference prints a mnemonic)
                print(json.dumps({"seed_backup": "0x" + recovered.hex()}),
                      file=sys.stderr)
            return 0
        # action2 == "validator": derive the next N keystores
        if not args.wallet_file or not args.keystore_password:
            print(json.dumps({"error": "--wallet-file and "
                              "--keystore-password required"}),
                  file=sys.stderr)
            return 1
        with open(args.wallet_file) as f:
            wallet = Wallet.from_json(f.read())
        keystores = [
            json.loads(
                wallet.next_validator(
                    args.password, args.keystore_password
                ).to_json()
            )
            for _ in range(args.count)
        ]
        with open(args.wallet_file, "w") as f:
            f.write(wallet.to_json())  # persists nextaccount
        out = json.dumps(keystores, indent=2)
        if args.out == "-":
            print(out)
        else:
            with open(args.out, "w") as f:
                f.write(out)
        return 0
    if args.action == "exit":
        from .api import BeaconNodeClient
        from .consensus.config import compute_signing_root
        from .consensus.types import SignedVoluntaryExit, VoluntaryExit
        from .validator.keystore import Keystore

        with open(args.keystore) as f:
            sk = Keystore.from_json(f.read()).decrypt(args.password)
        spec = _spec_for(args.spec)
        gvr = bytes.fromhex(args.genesis_validators_root.removeprefix("0x"))
        msg = VoluntaryExit(
            epoch=args.epoch, validator_index=args.validator_index
        )
        # Sign under the Fork container the chain CURRENTLY carries (the
        # verifier's get_domain picks previous_version for pre-fork exit
        # epochs — two forks later that is NOT the exit epoch's own
        # version). Prefer the BN's live view of the current epoch.
        current_epoch = args.current_epoch
        if current_epoch is None and args.beacon_node:
            head = BeaconNodeClient(url=args.beacon_node).get_header()
            slot = int(head["data"]["header"]["message"]["slot"])
            current_epoch = slot // spec.preset.SLOTS_PER_EPOCH
        if current_epoch is None:
            current_epoch = args.epoch
        domain = spec.get_domain(
            spec.DOMAIN_VOLUNTARY_EXIT,
            args.epoch,
            spec.fork_at_epoch(current_epoch),
            gvr,
        )
        signed = SignedVoluntaryExit(
            message=msg,
            signature=sk.sign(compute_signing_root(msg, domain)).to_bytes(),
        )
        exit_json = {
            "message": {
                "epoch": str(args.epoch),
                "validator_index": str(args.validator_index),
            },
            "signature": "0x" + bytes(signed.signature).hex(),
        }
        if args.beacon_node:
            BeaconNodeClient(url=args.beacon_node).post_voluntary_exit(
                exit_json
            )
            print(json.dumps({"published": True, **exit_json}))
        else:
            print(json.dumps(exit_json, indent=2))
        return 0
    if args.action == "slashing-protection":
        from .validator.slashing_protection import SlashingDatabase

        db = SlashingDatabase(args.db)
        gvr = bytes.fromhex(
            args.genesis_validators_root.removeprefix("0x")
        )
        if args.action2 == "export":
            out = json.dumps(db.export_interchange(gvr), indent=2)
            if args.file == "-":
                print(out)
            else:
                with open(args.file, "w") as f:
                    f.write(out)
            return 0
        if args.file == "-":
            data = sys.stdin.read()
        else:
            with open(args.file) as f:
                data = f.read()
        from .validator.slashing_protection import SlashingError

        try:
            count = db.import_interchange(data, gvr)
        except SlashingError as e:
            print(json.dumps({"error": str(e)}), file=sys.stderr)
            return 1
        print(json.dumps({"imported_validators": count}))
        return 0
    return 1


def run_lcli(args) -> int:
    from .chain.harness import BeaconChainHarness

    spec = _spec_for(args.spec)
    if args.action == "interop-genesis":
        from .consensus.genesis import interop_genesis_state, interop_keypairs
        from .crypto.bls import backends as bls_backends

        prev = bls_backends._default
        bls_backends.set_default_backend("fake")
        try:
            state = interop_genesis_state(
                interop_keypairs(args.validator_count), args.genesis_time, spec,
                sign_deposits=False,
            )
        finally:
            bls_backends._default = prev
        print(json.dumps({
            "genesis_validators_root": "0x"
            + bytes(state.genesis_validators_root).hex(),
            "genesis_time": int(state.genesis_time),
            "validators": len(state.validators),
        }))
        return 0
    if args.action == "new-testnet":
        # lcli new_testnet: a network config bundle another node can boot
        # from (eth2_network_config layout: config.yaml + genesis.ssz +
        # boot_enr.yaml; network_config.load_testnet_dir reads it back).
        import os

        from .consensus.genesis import interop_genesis_state, interop_keypairs
        from .crypto.bls import backends as bls_backends

        prev = bls_backends._default
        bls_backends.set_default_backend("fake")
        try:
            state = interop_genesis_state(
                interop_keypairs(args.validator_count), args.genesis_time,
                spec, sign_deposits=False,
            )
        finally:
            bls_backends._default = prev
        os.makedirs(args.out, exist_ok=True)
        config = {
            "CONFIG_NAME": args.name,
            "PRESET_BASE": args.spec,
            "MIN_GENESIS_ACTIVE_VALIDATOR_COUNT": args.validator_count,
            "MIN_GENESIS_TIME": args.genesis_time,
            "GENESIS_FORK_VERSION": "0x" + spec.GENESIS_FORK_VERSION.hex(),
            "SECONDS_PER_SLOT": spec.SECONDS_PER_SLOT,
        }
        if args.altair_fork_epoch is not None:
            config["ALTAIR_FORK_EPOCH"] = args.altair_fork_epoch
            config["ALTAIR_FORK_VERSION"] = (
                "0x" + spec.ALTAIR_FORK_VERSION.hex()
            )
        if args.bellatrix_fork_epoch is not None:
            config["BELLATRIX_FORK_EPOCH"] = args.bellatrix_fork_epoch
            config["BELLATRIX_FORK_VERSION"] = (
                "0x" + spec.BELLATRIX_FORK_VERSION.hex()
            )
        with open(os.path.join(args.out, "config.yaml"), "w") as f:
            for k, v in config.items():
                f.write(f"{k}: {v}\n")
        with open(os.path.join(args.out, "genesis.ssz"), "wb") as f:
            f.write(state.encode())
        with open(os.path.join(args.out, "boot_enr.yaml"), "w") as f:
            f.write("[]\n")
        print(json.dumps({
            "out": args.out,
            "genesis_validators_root": "0x"
            + bytes(state.genesis_validators_root).hex(),
            "validators": len(state.validators),
        }))
        return 0
    if args.action == "deploy-deposit-contract":
        # lcli deploy_deposit_contract (reference: lcli/src/
        # deploy_deposit_contract.rs): deploy over eth1 JSON-RPC, wait
        # confirmations, print the address, then optionally submit
        # deterministic insecure-validator deposits.
        from .execution.deposit_contract import (
            MOCK_DEPOSIT_RUNTIME,
            DepositContractClient,
            DepositContractError,
        )

        client = DepositContractClient(args.eth1_http)
        try:
            bytecode = MOCK_DEPOSIT_RUNTIME
            if args.bytecode_file:
                try:
                    with open(args.bytecode_file) as f:
                        bytecode = bytes.fromhex(
                            f.read().strip().removeprefix("0x")
                        )
                except (OSError, ValueError) as e:
                    raise DepositContractError(
                        f"bytecode file {args.bytecode_file}: {e}"
                    ) from e
            address = client.deploy(bytecode, args.confirmations)
            print(f"Deposit contract address: {address}")
            if args.validator_count:
                amount = spec.preset.MAX_EFFECTIVE_BALANCE
                for i in range(args.validator_count):
                    print(f"Submitting deposit for validator {i}...")
                    client.deposit_deterministic(address, i, amount, spec)
        except DepositContractError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        return 0
    if args.action == "eth1-genesis":
        # lcli eth1_genesis: the deposit-contract path — REAL signed
        # deposits through initialize_beacon_state_from_eth1.
        from .consensus.genesis import (
            genesis_deposits,
            initialize_beacon_state_from_eth1,
            interop_keypairs,
        )

        keys = interop_keypairs(args.validator_count)
        deposits = genesis_deposits(
            keys, spec.preset.MAX_EFFECTIVE_BALANCE, spec, sign=True
        )
        state = initialize_beacon_state_from_eth1(
            bytes.fromhex(args.eth1_block_hash.removeprefix("0x")),
            args.eth1_timestamp,
            deposits,
            spec,
        )
        print(json.dumps({
            "genesis_validators_root": "0x"
            + bytes(state.genesis_validators_root).hex(),
            "validators": len(state.validators),
            "genesis_time": int(state.genesis_time),
        }))
        return 0
    if args.action == "skip-slots":
        h = BeaconChainHarness(validator_count=args.validator_count, spec=spec)
        from .consensus.transition.slot import process_slots

        state = process_slots(
            h.chain.head().state.copy(), args.slots, h.spec
        )
        print(json.dumps({
            "slot": int(state.slot),
            "state_root": "0x" + state.hash_tree_root().hex(),
        }))
        return 0
    if args.action == "parse-ssz":
        from .consensus.types import spec_types

        t = spec_types(spec.preset)
        with open(args.path, "rb") as f:
            raw = f.read()
        cls = {
            "attestation": t.Attestation,
            "signed_block": t.SIGNED_BLOCK_BY_FORK["phase0"],
            "state": t.BeaconStatePhase0,
        }[args.ssz_type]
        from .api.json_codec import container_to_json

        print(json.dumps(container_to_json(cls.decode(raw)), indent=2))
        return 0
    if args.action == "transition-blocks":
        # lcli/src/transition_blocks.rs: replay blocks onto a pre-state
        from .consensus.transition.block import (
            SignatureStrategy,
            per_block_processing,
        )
        from .consensus.transition.slot import process_slots
        from .consensus.types import spec_types, state_fork_name

        t = spec_types(spec.preset)
        with open(args.pre_state, "rb") as f:
            raw = f.read()
        # the SSZ state has no self-describing tag: pick the fork class
        # whose schema round-trips (newest first — later forks are
        # supersets and would mis-decode under older schemas)
        state = None
        for fork in ("bellatrix", "altair", "phase0"):
            try:
                candidate = t.STATE_BY_FORK[fork].decode(raw)
                if spec.fork_name_at_epoch(
                    int(candidate.slot) // spec.preset.SLOTS_PER_EPOCH
                ) == fork:
                    state = candidate
                    break
            except Exception:  # lhtpu: ignore[LH502] -- probing candidate pre-state decodings; failures mean try the next fork
                continue
        if state is None:
            print(json.dumps({"error": "undecodable pre-state"}),
                  file=sys.stderr)
            return 1
        strategy = (
            SignatureStrategy.NO_VERIFICATION
            if args.no_signature_verification
            else SignatureStrategy.VERIFY_BULK
        )
        for path in args.block:
            with open(path, "rb") as f:
                block_raw = f.read()
            # message.slot: first field of the message, which starts at
            # the 4-byte variable-offset recorded at the front
            msg_off = int.from_bytes(block_raw[:4], "little")
            slot = int.from_bytes(block_raw[msg_off:msg_off + 8], "little")
            if int(state.slot) < slot:
                state = process_slots(state, slot, spec)
            # block class chosen AFTER the advance (fork upgrades happen
            # at epoch boundaries inside process_slots)
            signed = t.SIGNED_BLOCK_BY_FORK[state_fork_name(state)].decode(
                block_raw
            )
            per_block_processing(state, signed, spec, strategy=strategy)
        out = state.encode()
        if args.post_state:
            with open(args.post_state, "wb") as f:
                f.write(out)
        print(json.dumps({
            "slot": int(state.slot),
            "state_root": "0x" + state.hash_tree_root().hex(),
        }))
        return 0
    if args.action == "insecure-validators":
        # lcli insecure_validators: deterministic interop keys, encrypted
        # under a per-key password file (validator_dir layout)
        import os

        from .consensus.genesis import interop_keypairs
        from .validator.keystore import Keystore

        os.makedirs(os.path.join(args.base_dir, "validators"), exist_ok=True)
        os.makedirs(os.path.join(args.base_dir, "secrets"), exist_ok=True)
        for i, sk in enumerate(interop_keypairs(args.count)):
            pubkey = sk.public_key().to_bytes().hex()
            password = f"insecure-password-{i}"
            ks = Keystore.encrypt(sk, password, kdf="pbkdf2",
                                  path=f"m/12381/3600/{i}/0/0")
            vdir = os.path.join(args.base_dir, "validators", f"0x{pubkey}")
            os.makedirs(vdir, exist_ok=True)
            with open(os.path.join(vdir, "voting-keystore.json"), "w") as f:
                f.write(ks.to_json())
            with open(
                os.path.join(args.base_dir, "secrets", f"0x{pubkey}"), "w"
            ) as f:
                f.write(password)
        print(json.dumps({"validators_written": args.count,
                          "base_dir": args.base_dir}))
        return 0
    return 1


def run_db(args) -> int:
    """database_manager equivalents: inspect / version / migrate /
    compact (database_manager/src/lib.rs subcommands)."""
    from .store.kv import KVStore
    from .store.schema_change import (
        CURRENT_SCHEMA_VERSION,
        migrate_schema,
        read_schema_version,
    )

    store = KVStore(args.datadir)
    try:
        if args.action == "version":
            print(json.dumps({
                "schema_version": read_schema_version(store),
                "current": CURRENT_SCHEMA_VERSION,
            }))
            return 0
        if args.action == "migrate":
            target = (args.target if args.target is not None
                      else CURRENT_SCHEMA_VERSION)
            version = migrate_schema(store, target)
            print(json.dumps({"schema_version": version}))
            return 0
        if args.action == "compact":
            store.compact()
            print(json.dumps({"compacted": True}))
            return 0
        counts: dict[str, int] = {}
        for column in (b"blk", b"ste", b"sum", b"met"):
            counts[column.decode()] = sum(
                1 for _ in store.iter_keys(column)
            )
        print(json.dumps(counts))
        return 0
    finally:
        store.close()


def run_bench(args) -> int:
    import subprocess

    cmd = [sys.executable, "bench.py"] + (["--quick"] if args.quick else [])
    return subprocess.call(cmd)


def run_boot_node(args) -> int:
    from .common.logging import StructuredLogger
    from .network.discovery import BootNodeServer

    log = StructuredLogger(level=args.debug_level)
    server = BootNodeServer(host=args.host, port=args.port)
    log.info("boot node listening", url=server.url)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return {
        "bn": run_bn,
        "vc": run_vc,
        "account": run_account,
        "lcli": run_lcli,
        "db": run_db,
        "bench": run_bench,
        "boot-node": run_boot_node,
    }[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
