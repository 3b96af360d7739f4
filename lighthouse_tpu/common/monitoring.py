"""Remote monitoring poster (reference: common/monitoring_api, 574 LoC
— periodically POSTs beaconnode/validator process metrics JSON to a
remote endpoint in the beaconcha.in client-stats format) plus the
psutil-free process self-observation the health governor feeds on:
an RSS reader (``/proc/self/status`` VmRSS with a
``resource.getrusage`` fallback) behind the
``process_resident_memory_bytes`` gauge, the one compile counter of
every jitted device program (``bls_jit_cache_events_total``, through
:func:`jit_cache_probe`), a jit-cache entry estimate behind
``bls_jit_cache_entries``, and the slasher engine's per-shard vote
counter ``slasher_shard_votes_total``."""

from __future__ import annotations

import json
import threading
import time
import urllib.request

from .metrics import REGISTRY

RSS_BYTES = REGISTRY.gauge(
    "process_resident_memory_bytes",
    "Resident set size of this process (VmRSS; getrusage fallback)",
)
JIT_CACHE_ENTRIES = REGISTRY.gauge(
    "bls_jit_cache_entries",
    "Estimated live jit-cache entries (compiles since last counted clear)",
)
JIT_CACHE_CLEARS = REGISTRY.counter(
    "bls_jit_cache_clears_total",
    "Counted jax.clear_caches() invocations, by cause",
    ("cause",),
)


def read_rss_bytes() -> int:
    """Current RSS in bytes without psutil: ``/proc/self/status``
    VmRSS (kB) where procfs exists, else ``resource.getrusage``
    ru_maxrss (kB on Linux — a high-water mark, still monotone enough
    for the leak sentinel). Returns 0 only if both fail."""
    try:
        with open("/proc/self/status", "rb") as fh:
            for line in fh:
                if line.startswith(b"VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:  # lhtpu: ignore[LH502] -- resource module absent off-unix; 0 means RSS unknown
        return 0


def sample_rss() -> int:
    """Read RSS and mirror it into ``process_resident_memory_bytes``."""
    rss = read_rss_bytes()
    RSS_BYTES.set(rss)
    return rss


JIT_CACHE_EVENTS = REGISTRY.counter(
    "bls_jit_cache_events_total",
    "Jitted device-program dispatches by compile-cache outcome",
    ("fn", "event"),
)


#: votes each shard of the slasher engine's device planes classified
#: (``slasher/arrays.py`` SurroundEngine), advanced once a launched batch
SLASHER_SHARD_VOTES = REGISTRY.counter(
    "slasher_shard_votes_total",
    "Votes classified by each shard of the surround engine's device planes",
    ("shard",),
)


def jit_cache_probe(fn, label: str):
    """Sample ``fn``'s jit cache size; returns a closure that, called
    after ``calls`` dispatches of ``fn`` (default 1), records each in
    ``bls_jit_cache_events_total{fn=label}`` as a hit or a miss (the
    cache's growth is the number of fresh traces/compiles). Counts
    nothing when the runtime doesn't expose _cache_size (non-jit
    callables, older jax)."""
    try:
        before = fn._cache_size()
    except Exception:  # lhtpu: ignore[LH502] -- _cache_size is a private jax API; absent means cache accounting is off, not an error
        return lambda calls=1: None

    def done(calls: int = 1) -> None:
        try:
            after = fn._cache_size()
        except Exception:  # lhtpu: ignore[LH502] -- same probe after dispatch; losing one sample is fine
            return
        misses = min(max(after - before, 0), calls)
        if misses:
            JIT_CACHE_EVENTS.inc(misses, fn=label, event="miss")
            note_jit_compile(after - before)
        if calls > misses:
            JIT_CACHE_EVENTS.inc(calls - misses, fn=label, event="hit")

    return done


# Jit-cache entry accounting: JAX exposes no stable global cache-size
# API, so we count compiles (jit_cache_probe calls note_jit_compile on
# every miss) and re-baseline on a counted clear.
_JIT_LOCK = threading.Lock()
_JIT_COMPILES = 0
_JIT_BASELINE = 0


def note_jit_compile(n: int = 1) -> None:
    """A jit-cache miss (a compile) happened; bump the entry estimate."""
    global _JIT_COMPILES
    with _JIT_LOCK:
        _JIT_COMPILES += n
        JIT_CACHE_ENTRIES.set(_JIT_COMPILES - _JIT_BASELINE)


def note_jit_cache_cleared(cause: str = "manual") -> None:
    """The caches were dropped (jax.clear_caches / arena prune):
    re-baseline the entry estimate and count the clear."""
    global _JIT_BASELINE
    with _JIT_LOCK:
        _JIT_BASELINE = _JIT_COMPILES
        JIT_CACHE_ENTRIES.set(0)
    JIT_CACHE_CLEARS.inc(cause=cause)


def jit_cache_entry_count() -> int:
    """Estimated live jit-cache entries since the last counted clear."""
    with _JIT_LOCK:
        return _JIT_COMPILES - _JIT_BASELINE


class MonitoringService:
    def __init__(self, endpoint: str, node=None, vc=None, timeout: float = 5.0):
        self.endpoint = endpoint
        self.node = node
        self.vc = vc
        self.timeout = timeout
        self.posts = 0

    def collect(self) -> list[dict]:
        """client-stats JSON bodies (monitoring_api/src/types.rs)."""
        now = int(time.time() * 1000)
        out = []
        if self.node is not None:
            chain = self.node.chain
            head = chain.head()
            out.append(
                {
                    "version": 1,
                    "timestamp": now,
                    "process": "beaconnode",
                    "sync_beacon_head_slot": int(head.block.message.slot),
                    "sync_eth2_synced": (
                        chain.current_slot()
                        - int(head.block.message.slot)
                    ) <= 1,
                    "slasher_active": self.node.slasher is not None,
                    "network_peers_connected": (
                        len(self.node.network.peer_manager.connected_peers())
                        if self.node.network
                        else 0
                    ),
                }
            )
        if self.vc is not None:
            out.append(
                {
                    "version": 1,
                    "timestamp": now,
                    "process": "validator",
                    "validator_total": len(self.vc.store.voting_pubkeys()),
                    "validator_active": len(self.vc.store.voting_pubkeys()),
                }
            )
        return out

    def post(self) -> bool:
        body = json.dumps(self.collect()).encode()
        req = urllib.request.Request(
            self.endpoint,
            data=body,
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout):
                self.posts += 1
                return True
        except OSError:
            return False
