"""Node composition + CLI tests: ClientBuilder wiring, slot ticking,
slasher integration, checkpoint sync boot, and CLI flag → config
behavior (reference test model: lighthouse/tests CLI tests +
client builder usage in node_test_rig)."""

import json

import pytest

from lighthouse_tpu.chain.harness import BeaconChainHarness
from lighthouse_tpu.cli import build_parser, main
from lighthouse_tpu.consensus.config import minimal_spec
from lighthouse_tpu.network import InMemoryHub
from lighthouse_tpu.node import ClientBuilder, ClientConfig


class TestClientBuilder:
    def test_memory_node_ticks(self):
        node = (
            ClientBuilder(ClientConfig(validator_count=8), minimal_spec())
            .memory_store()
            .interop_genesis()
            .build()
        )
        assert node.chain.current_slot() == 0
        node.chain.slot_clock.advance_slot()
        assert node.tick_slot() == 1
        node.stop()

    def test_http_node(self):
        node = (
            ClientBuilder(
                ClientConfig(validator_count=8, http_enabled=True),
                minimal_spec(),
            )
            .memory_store()
            .interop_genesis()
            .build()
        )
        client = node.client()
        assert client.url is not None  # real HTTP
        assert "lighthouse-tpu" in client.node_version()["data"]["version"]
        node.stop()

    def test_networked_nodes_share_hub(self):
        hub = InMemoryHub()
        spec = minimal_spec()
        n1 = (
            ClientBuilder(ClientConfig(validator_count=16), spec)
            .memory_store().interop_genesis().network(hub, "n1").build()
        )
        n2 = (
            ClientBuilder(ClientConfig(validator_count=16), spec)
            .memory_store().interop_genesis().network(hub, "n2").build()
        )
        # same interop genesis → same chain → gossip interop
        h1 = BeaconChainHarness(validator_count=16)
        assert n1.chain.genesis_block_root == h1.chain.genesis_block_root
        n1.chain.slot_clock.advance_slot()
        n2.chain.slot_clock.advance_slot()
        block = _block_on(n1)
        n1.chain.process_block(block)
        n1.network.publish_block(block)
        n2.tick_slot()
        assert n2.chain.head().root == n1.chain.head().root
        n1.stop(), n2.stop()

    def test_slasher_wired_to_gossip(self):
        hub = InMemoryHub()
        spec = minimal_spec()
        n1 = (
            ClientBuilder(ClientConfig(validator_count=16), spec)
            .memory_store().interop_genesis().network(hub, "n1").build()
        )
        n2 = (
            ClientBuilder(
                ClientConfig(validator_count=16, slasher_enabled=True), spec
            )
            .memory_store().interop_genesis().network(hub, "n2").build()
        )
        assert n2.slasher is not None
        n1.chain.slot_clock.advance_slot()
        n2.chain.slot_clock.advance_slot()
        block = _block_on(n1)
        n1.chain.process_block(block)
        n1.network.publish_block(block)
        n2.tick_slot()
        assert n2.slasher.stats["blocks"] >= 1  # block reached the slasher
        n1.stop(), n2.stop()

    def test_slasher_devices_build_the_device_slasher(self):
        from lighthouse_tpu.slasher import DeviceSlasher, Slasher

        spec = minimal_spec()
        args = build_parser().parse_args(
            ["bn", "--slasher", "--slasher-devices", "2"])
        assert args.slasher and args.slasher_devices == 2
        assert build_parser().parse_args(["bn"]).slasher_devices == 0
        node = (
            ClientBuilder(ClientConfig(validator_count=16,
                                       slasher_enabled=True,
                                       slasher_devices=2), spec)
            .memory_store().interop_genesis().build()
        )
        assert isinstance(node.slasher, DeviceSlasher)
        assert node.slasher.config.devices == 2
        assert node.slasher.engine.shards == 2
        node.stop()
        node = (
            ClientBuilder(ClientConfig(validator_count=16,
                                       slasher_enabled=True), spec)
            .memory_store().interop_genesis().build()
        )
        assert type(node.slasher) is Slasher
        node.stop()

    def test_checkpoint_sync_boot(self):
        """New node boots from a remote node's finalized/head state and
        continues from there (builder.rs:252-365)."""
        spec = minimal_spec()
        source = BeaconChainHarness(validator_count=16)
        source.extend_chain(5, attest=False)
        from lighthouse_tpu.api import BeaconApi, BeaconNodeClient

        remote = BeaconNodeClient(api=BeaconApi(source.chain))
        node = (
            ClientBuilder(ClientConfig(validator_count=16), spec)
            .memory_store()
            .checkpoint_sync(remote)
            .build()
        )
        # anchored at the source's finalized block (genesis here, since
        # nothing finalized) — head roots agree
        assert node.chain.head().root is not None
        assert int(node.chain.head().block.message.slot) >= 0
        node.stop()


def _block_on(node):
    """Produce a signed (infinity-sig, fake backend) block on a node."""
    h = BeaconChainHarness(validator_count=16)
    h.advance_slot()
    return h.make_block(1)


class TestCli:
    def test_parser_tree(self):
        p = build_parser()
        args = p.parse_args(["bn", "--spec", "minimal", "--http", "--slots", "2"])
        assert args.command == "bn" and args.http and args.slots == 2
        args = p.parse_args(["vc", "--interop-validators", "4"])
        assert args.interop_validators == 4
        args = p.parse_args(["account", "new", "--seed-hex", "ab" * 32,
                             "--password", "x"])
        assert args.action == "new"
        with pytest.raises(SystemExit):
            p.parse_args(["unknown"])

    def test_bn_runs_slots(self, capsys):
        rc = main(["bn", "--spec", "minimal", "--interop-validators", "8",
                   "--slots", "2", "--debug-level", "crit"])
        assert rc == 0

    def test_lcli_interop_genesis(self, capsys):
        rc = main(["lcli", "--spec", "minimal", "interop-genesis",
                   "--validator-count", "8"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["validators"] == 8
        assert out["genesis_validators_root"].startswith("0x")

    def test_account_new_and_inspect(self, tmp_path, capsys):
        out_path = tmp_path / "ks.json"
        rc = main(["account", "new", "--seed-hex", "cd" * 32,
                   "--password", "pw", "--index", "1", "--out", str(out_path)])
        assert rc == 0
        rc = main(["account", "inspect", str(out_path), "--password", "pw"])
        assert rc == 0
        info = json.loads(capsys.readouterr().out)
        assert info["decrypts"] is True
        assert info["path"] == "m/12381/3600/1/0/0"
