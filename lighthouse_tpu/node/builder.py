"""ClientBuilder → BeaconNode (reference: beacon_node/client/src/builder.rs:90-604).

Build order follows the reference: store (disk or memory), optional
slasher, beacon chain (genesis resolution: interop / provided state /
checkpoint sync from a remote BN — builder.rs:252-365), network
service on the hub, HTTP API server, then the timed services (slot
timer → per_slot_task + chain poll, state-advance at 3/4 slot,
notifier). ``tick_slot`` drives everything deterministically; ``start``
spawns the same loops on the TaskExecutor.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from ..api import BeaconApi, BeaconNodeClient, HttpServer
from ..chain.beacon_chain import BeaconChain
from ..common.logging import NullLogger, StructuredLogger
from ..common.metrics import REGISTRY
from ..common.slot_clock import ManualSlotClock, SystemSlotClock
from ..common.task_executor import TaskExecutor
from ..consensus.config import ChainSpec, minimal_spec
from ..consensus.genesis import interop_genesis_state, interop_keypairs
from ..network import NetworkService
from ..slasher import DeviceSlasher, Slasher, SlasherConfig
from ..store.hot_cold import HotColdDB, StoreConfig
from ..store.kv import MemoryStore


@dataclass
class ClientConfig:
    """The assembled flag surface (reference: beacon_node/src/config.rs
    get_config melting ~1,500 LoC of clap flags into ClientConfig)."""

    datadir: str | None = None          # None -> MemoryStore
    validator_count: int = 16           # interop genesis size
    genesis_time: int = 1_600_000_000
    backend: str | None = None          # BLS backend override
    http_enabled: bool = False
    http_port: int = 0
    metrics_enabled: bool = False
    metrics_port: int = 0  # 0 = ephemeral (tests); set for scrape targets
    slasher_enabled: bool = False
    # >0 runs the slasher's surround/double-vote planes on that many
    # chips (DeviceSlasher); 0 keeps them in the KV store (Slasher)
    slasher_devices: int = 0
    validator_monitor_auto: bool = False  # watch all validators
    validator_monitor_indices: tuple = ()  # or specific indices
    attestation_batch_size: int = 1024
    # >0 holds partial gossip batches until the oldest entry has waited
    # this long (processor.py batch-or-timeout accumulation); fires on the
    # node's periodic poll/tick, so keep it a multiple of the poll period.
    batch_deadline_ms: float = 0.0
    manual_clock: bool = True           # deterministic by default
    extra: dict = field(default_factory=dict)


class BeaconNode:
    def __init__(self, chain: BeaconChain, network: NetworkService | None,
                 api: BeaconApi, http: HttpServer | None,
                 slasher: Slasher | None, executor: TaskExecutor,
                 log: StructuredLogger, spec: ChainSpec,
                 metrics_server=None):
        from ..chain.state_advance import StateAdvanceTimer

        self.chain = chain
        self.network = network
        self.api = api
        self.http = http
        self.metrics_server = metrics_server
        self.slasher = slasher
        self.executor = executor
        self.log = log
        self.spec = spec
        self.state_advance = StateAdvanceTimer(chain)
        self._slot_metric = REGISTRY.gauge("beacon_head_slot", "Head slot")

    # ------------------------------------------------------------ lifecycle
    def client(self) -> BeaconNodeClient:
        if self.http is not None:
            return BeaconNodeClient(url=self.http.url)
        return BeaconNodeClient(api=self.api)

    def tick_slot(self) -> int:
        """One slot of node housekeeping (timer/src/lib.rs per_slot_task
        + network poll + slasher drain + notifier)."""
        chain = self.chain
        chain.per_slot_task()
        if self.network is not None:
            self.network.discover_and_connect()
            self.network.subnet_tick()
            self.network.poll()
        if self.slasher is not None:
            p = self.spec.preset
            current_epoch = chain.current_slot() // p.SLOTS_PER_EPOCH
            for found in self.slasher.process_queued(current_epoch):
                self._import_slashing(found)
        head = chain.head()
        self._slot_metric.set(int(head.block.message.slot))
        self.log.debug(
            "slot tick",
            slot=chain.current_slot(),
            head=head.root.hex()[:8],
            finalized=chain.finalized_checkpoint()[0],
        )
        return chain.current_slot()

    def _import_slashing(self, found) -> None:
        from ..consensus.verify_operation import (
            OperationError,
            verify_attester_slashing,
            verify_proposer_slashing,
        )
        from ..slasher.slasher import AttesterSlashingFound

        chain = self.chain
        state = chain.head().state
        try:
            if isinstance(found, AttesterSlashingFound):
                slashing = self.slasher.as_attester_slashing(found)
                op = verify_attester_slashing(
                    state, slashing, self.spec, backend=chain.backend
                )
                chain.op_pool.insert_attester_slashing(op)
            else:
                slashing = self.slasher.as_proposer_slashing(found)
                op = verify_proposer_slashing(
                    state, slashing, self.spec, backend=chain.backend
                )
                chain.op_pool.insert_proposer_slashing(op)
            self.log.warn(
                "slashing detected",
                kind=getattr(found, "kind", "proposal"),
                validator=getattr(
                    found, "validator_index", getattr(found, "proposer_index", -1)
                ),
            )
        except OperationError:
            pass  # e.g. already-slashed validator

    def start(self) -> "BeaconNode":
        """Spawn the timed loops for wall-clock operation."""
        seconds = self.spec.SECONDS_PER_SLOT

        def maybe_advance():
            if self.state_advance.due():
                self.state_advance.run()

        self.executor.spawn_periodic(self.tick_slot, seconds, "slot_timer")
        self.executor.spawn_periodic(
            maybe_advance, seconds / 8, "state_advance_timer"
        )
        if self.network is not None:
            self.executor.spawn_periodic(self.network.poll, 0.05, "network_poll")
        return self

    def stop(self) -> None:
        try:
            self.chain.persist()  # resume-safe shutdown
        except Exception:
            self.log.warn("chain persistence failed on shutdown")
        self.executor.shutdown.trigger("node stopped")
        if self.http is not None:
            self.http.stop()
        if self.metrics_server is not None:
            self.metrics_server.stop()


class ClientBuilder:
    def __init__(self, config: ClientConfig | None = None,
                 spec: ChainSpec | None = None, log=None):
        self.config = config or ClientConfig()
        self.spec = spec or minimal_spec()
        self.log = log or NullLogger()
        self._store = None
        self._genesis_state = None
        self._hub = None
        self._node_id = "node"
        self._checkpoint_client = None

    # -------------------------------------------------------------- sources
    def memory_store(self) -> "ClientBuilder":
        self._store = MemoryStore()
        return self

    def disk_store(self, path: str) -> "ClientBuilder":
        from ..store.kv import KVStore

        self._store = KVStore(path)
        return self

    def genesis_state(self, state) -> "ClientBuilder":
        self._genesis_state = state
        return self

    def interop_genesis(self, validator_count: int | None = None) -> "ClientBuilder":
        n = validator_count or self.config.validator_count
        keys = interop_keypairs(n)
        sign = self.config.backend not in (None, "fake")
        if not sign:
            # unsigned interop deposits are only valid under the fake
            # backend; pin the chain to it (the reference's fake_crypto
            # feature is likewise a whole-binary choice)
            self.config.backend = "fake"
            from ..crypto.bls import backends as bls_backends

            prev = bls_backends._default
            bls_backends.set_default_backend("fake")
            try:
                self._genesis_state = interop_genesis_state(
                    keys, self.config.genesis_time, self.spec,
                    sign_deposits=False,
                )
            finally:
                bls_backends._default = prev
        else:
            self._genesis_state = interop_genesis_state(
                keys, self.config.genesis_time, self.spec, sign_deposits=True
            )
        return self

    def checkpoint_sync(self, remote: BeaconNodeClient) -> "ClientBuilder":
        """Boot from a remote BN's finalized state
        (builder.rs:252-365 ClientGenesis::CheckpointSyncUrl)."""
        self._checkpoint_client = remote
        return self

    def network(self, hub, node_id: str) -> "ClientBuilder":
        self._hub = hub
        self._node_id = node_id
        return self

    # ---------------------------------------------------------------- build
    def build(self) -> BeaconNode:
        cfg = self.config
        store_backend = self._store if self._store is not None else MemoryStore()
        hot_cold = HotColdDB(
            store_backend,
            self.spec,
            StoreConfig(slots_per_restore_point=self.spec.preset.SLOTS_PER_EPOCH),
        )
        clock_cls = ManualSlotClock if cfg.manual_clock else SystemSlotClock

        from ..chain.persistence import KEY_PERSISTED_CHAIN, load_chain

        if self._checkpoint_client is not None:
            chain = self._build_from_checkpoint(hot_cold, clock_cls)
        elif (
            self._genesis_state is None
            and hot_cold.get_meta(KEY_PERSISTED_CHAIN) is not None
        ):
            # resume-from-store boot (ClientGenesis::FromStore). Load with
            # a frozen manual clock (no giant slot numbers during replay),
            # then install the real clock positioned at the head slot.
            probe_clock = ManualSlotClock(0, self.spec.SECONDS_PER_SLOT)
            chain = load_chain(hot_cold, self.spec, probe_clock, backend=cfg.backend)
            genesis_time = int(chain.head().state.genesis_time)
            clock = clock_cls(genesis_time, self.spec.SECONDS_PER_SLOT)
            if isinstance(clock, ManualSlotClock):
                clock.set_slot(int(chain.head().block.message.slot))
            chain.slot_clock = clock
        else:
            if self._genesis_state is None:
                self.interop_genesis()
            clock = clock_cls(
                int(self._genesis_state.genesis_time), self.spec.SECONDS_PER_SLOT
            )
            chain = BeaconChain.from_genesis(
                hot_cold, self._genesis_state, self.spec, clock,
                backend=cfg.backend,
            )

        # HBM-resident pubkey table (blsrt): with the device backend on
        # real hardware, mirror the pubkey cache into HBM so verify
        # batches gather by validator index instead of re-uploading
        # coordinates (SURVEY §7.1 layer 2; reference keeps this cache
        # host-side, validator_pubkey_cache.rs:20-24).
        if cfg.backend == "jax":
            import jax as _jax

            if _jax.default_backend() == "tpu":
                from ..blsrt import DevicePubkeyTable

                chain.pubkey_cache.attach_device_table(DevicePubkeyTable())

        network = None
        if self._hub is not None:
            network = NetworkService(
                chain, self._hub, self._node_id,
                attestation_batch_size=cfg.attestation_batch_size,
                batch_deadline_ms=cfg.batch_deadline_ms,
            )

        slasher = None
        if cfg.slasher_enabled and cfg.slasher_devices:
            slasher = DeviceSlasher(
                chain.types, SlasherConfig(devices=cfg.slasher_devices),
                db=store_backend)
        elif cfg.slasher_enabled:
            slasher = Slasher(chain.types, db=store_backend)

        api = BeaconApi(chain, network=network)
        http = None
        if cfg.http_enabled:
            http = HttpServer(api, port=cfg.http_port).start()
        metrics_server = None
        if cfg.metrics_enabled:
            from ..api.http_metrics import MetricsServer

            metrics_server = MetricsServer(port=cfg.metrics_port).start()
            self.log.info("metrics server listening", url=metrics_server.url)
        chain.validator_monitor.auto_register = cfg.validator_monitor_auto
        for index in cfg.validator_monitor_indices:
            chain.validator_monitor.register_validator(int(index))

        executor = TaskExecutor(self._node_id)
        node = BeaconNode(
            chain, network, api, http, slasher, executor, self.log, self.spec,
            metrics_server=metrics_server,
        )
        if slasher is not None and network is not None:
            # feed gossip attestations and blocks into the slasher
            # (slasher/service ingest path)
            from ..network.processor import WorkType

            router = network.router
            original_atts = router._work_attestation_batch
            original_block = router._work_gossip_block

            def atts_feeding(events):
                original_atts(events)
                for ev in events:
                    try:
                        indexed, _ = chain._gossip_attestation_checks(ev.payload)
                        slasher.accept_attestation(indexed)
                    except Exception:  # lhtpu: ignore[LH502] -- structurally invalid gossip has nothing to slash on; gossip path already rejected it
                        pass  # structurally invalid: nothing to slash on

            def block_feeding(ev):
                slasher.accept_block(ev.payload)
                original_block(ev)

            network.processor.register(WorkType.GOSSIP_ATTESTATION, atts_feeding)
            network.processor.register(WorkType.GOSSIP_BLOCK, block_feeding)
        return node

    def _build_from_checkpoint(self, hot_cold, clock_cls) -> BeaconChain:
        """Download finalized state+block from the remote BN and anchor
        the chain there (weak-subjectivity boot)."""
        from ..api.json_codec import container_from_json
        from ..consensus.types import spec_types, state_fork_name

        remote = self._checkpoint_client
        t = spec_types(self.spec.preset)
        finalized = remote.get_block("finalized")
        fork = finalized.get("version", "phase0")
        block = container_from_json(
            t.SIGNED_BLOCK_BY_FORK[fork], finalized["data"]
        )
        state_resp = remote.get_debug_state("finalized")
        state_cls = t.STATE_BY_FORK[state_resp.get("version", fork)]
        state = container_from_json(state_cls, state_resp["data"])
        clock = clock_cls(int(state.genesis_time), self.spec.SECONDS_PER_SLOT)
        block_root = block.message.hash_tree_root()
        hot_cold.put_state(bytes(block.message.state_root), state)
        hot_cold.put_block(block_root, block)
        hot_cold.set_genesis_block_root(block_root)  # anchor
        chain = BeaconChain(
            self.spec, hot_cold, clock, state, block, block_root,
            backend=self.config.backend,
        )
        chain.snapshot_cache.insert(block_root, state.copy())
        return chain
