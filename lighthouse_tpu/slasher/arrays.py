"""Min/max-target chunked arrays (reference: slasher/src/array.rs).

The surround-vote check needs, for every validator and every source
epoch e, the minimum and maximum attestation target the validator has
ever attested with source >= e (min-targets) / source <= e
(max-targets). The reference's "flat layout": the (validator, epoch)
plane is tiled into chunks of ``validator_chunk_size`` validators ×
``chunk_size`` epochs; each chunk is a little-endian u16-distance array,
zlib-compressed in the DB, updated in place as attestations arrive.

Distances are stored relative to the epoch (`target - epoch` for max,
and saturating for min) so u16 suffices (the reference stores u16
distances the same way).
"""

from __future__ import annotations

import zlib

from ..common import knobs, monitoring, resilience, tracing
from ..common.metrics import REGISTRY

MAX_DISTANCE = 0xFFFF

# SurroundEngine per-vote verdict bits. DOUBLE is a *candidate* (ring
# occupancy hit) — the caller confirms against the exact-target root
# map, which keeps ring collisions from ever surfacing as findings.
CODE_SURROUNDS = 1
CODE_SURROUNDED = 2
CODE_DOUBLE = 4


# SurroundEngine's counters, each advanced once per batch (reference:
# the slasher's batch metrics in slasher/src/metrics.rs).
VOTES_TOTAL = REGISTRY.counter(
    "slasher_votes_total", "Votes classified by the surround engine")
LAUNCHES_TOTAL = REGISTRY.counter(
    "slasher_launches_total",
    "Per-chunk device plane-scan programs launched by the surround engine")
PLANE_INITS_TOTAL = REGISTRY.counter(
    "slasher_plane_inits_total",
    "Validator chunks whose device planes were made fresh")
FALLBACKS_TOTAL = REGISTRY.counter(
    "slasher_fallbacks_total",
    "Chunks the surround engine replayed on the host after a fault",
    ("kind",))


def _col(name: str) -> bytes:
    return name.encode()


class ChunkedArray:
    """One plane (min or max) of the epoch×validator distance grid."""

    #: min plane: default distance is "infinite" (no attestation yet)
    #: max plane: default 0 (never attested beyond its own epoch)
    def __init__(self, db, column: bytes, chunk_size: int,
                 validator_chunk_size: int, default: int):
        self.db = db
        self.column = column
        self.chunk_size = chunk_size
        self.validator_chunk_size = validator_chunk_size
        self.default = default
        self._cache: dict[tuple[int, int], list[int]] = {}
        self._dirty: set[tuple[int, int]] = set()

    # ------------------------------------------------------------- chunk io
    def _key(self, validator_chunk: int, epoch_chunk: int) -> bytes:
        return validator_chunk.to_bytes(4, "big") + epoch_chunk.to_bytes(4, "big")

    def _load(self, validator_chunk: int, epoch_chunk: int) -> list[int]:
        key = (validator_chunk, epoch_chunk)
        chunk = self._cache.get(key)
        if chunk is not None:
            return chunk
        raw = self.db.get(self.column, self._key(*key))
        n = self.chunk_size * self.validator_chunk_size
        if raw is None:
            chunk = [self.default] * n
        else:
            data = zlib.decompress(raw)
            chunk = [
                int.from_bytes(data[i * 2 : i * 2 + 2], "little")
                for i in range(n)
            ]
        self._cache[key] = chunk
        return chunk

    def flush(self) -> None:
        for key in self._dirty:
            chunk = self._cache[key]
            data = b"".join(v.to_bytes(2, "little") for v in chunk)
            self.db.put(self.column, self._key(*key), zlib.compress(data, 1))
        self._dirty.clear()

    # ------------------------------------------------------------ accessors
    def _index(self, validator: int, epoch: int) -> tuple[tuple[int, int], int]:
        vc, vi = divmod(validator, self.validator_chunk_size)
        ec, ei = divmod(epoch, self.chunk_size)
        return (vc, ec), vi * self.chunk_size + ei

    def get(self, validator: int, epoch: int) -> int:
        key, idx = self._index(validator, epoch)
        return self._load(*key)[idx]

    def set(self, validator: int, epoch: int, value: int) -> None:
        key, idx = self._index(validator, epoch)
        chunk = self._load(*key)
        if chunk[idx] != value:
            chunk[idx] = value
            self._dirty.add(key)


class TargetArrays:
    """The pair of planes + the surround logic (array.rs apply_attestation).

    For an attestation (source s, target t) by validator v:

    * it SURROUNDS an earlier vote iff some prior (s', t') has s' > s and
      t' < t  →  check ``max_target(v, s+1) < t`` is violated, i.e. an
      existing max-target entry at epoch s+1 lies strictly inside (s, t);
    * it IS SURROUNDED by an earlier vote iff some prior (s', t') has
      s' < s and t' > t  →  check via min-targets at epoch s-1… stored as
      distances.

    Updates then extend both planes over the affected epoch ranges.
    ``history_length`` bounds how far back epochs are tracked (reference
    default 4096).
    """

    def __init__(self, db, chunk_size: int, validator_chunk_size: int,
                 history_length: int):
        self.history_length = history_length
        self.chunk_size = chunk_size
        self.min_targets = ChunkedArray(
            db, _col("slasher/min_targets"), chunk_size, validator_chunk_size,
            default=MAX_DISTANCE,
        )
        self.max_targets = ChunkedArray(
            db, _col("slasher/max_targets"), chunk_size, validator_chunk_size,
            default=0,
        )

    # ------------------------------------------------------------- distances
    # min plane at epoch e: min over recorded votes with source' >= e of
    #   (target' - e)   (MAX_DISTANCE = no such vote)
    # max plane at epoch e: max over recorded votes with source' <= e of
    #   (target' - e)   (0 = no such vote reaching past e)
    # Epochs index a ring of size history_length — valid while the live
    # attestation window (weak-subjectivity period) stays well inside it,
    # the same bound the reference enforces by pruning.

    def check_surround(self, validator: int, source: int, target: int):
        """Does (source, target) create a surround pair with any
        recorded vote? Returns "surrounds" / "surrounded" / None."""
        # surrounded: a prior vote (s' < source, t' > target).
        # Read the max plane at e = source-1: covers s' <= source-1 (strict).
        if source >= 1:
            e = source - 1
            d = self.max_targets.get(validator, e % self.history_length)
            if d != 0 and e + d > target:
                return "surrounded"
        # surrounds: a prior vote (s' > source, t' < target).
        # Read the min plane at e = source+1: covers s' >= source+1 (strict).
        e = source + 1
        d = self.min_targets.get(validator, e % self.history_length)
        if d != MAX_DISTANCE and e + d < target:
            return "surrounds"
        return None

    def apply(self, validator: int, source: int, target: int) -> None:
        """Record the vote in both planes (bounded by history_length)."""
        # max plane: our vote has s' = source <= e for all e >= source;
        # distance t - e is meaningful while e <= target.
        hi = min(target, source + self.history_length - 1)
        for e in range(source, hi + 1):
            idx = e % self.history_length
            d = min(target - e, MAX_DISTANCE - 1)
            if d > self.max_targets.get(validator, idx):
                self.max_targets.set(validator, idx, d)
        # min plane: our vote has s' = source >= e for all e <= source.
        lo = max(0, source - self.history_length + 1)
        for e in range(lo, source + 1):
            idx = e % self.history_length
            d = min(target - e, MAX_DISTANCE - 1)
            if d < self.min_targets.get(validator, idx):
                self.min_targets.set(validator, idx, d)

    def flush(self) -> None:
        self.min_targets.flush()
        self.max_targets.flush()


class SurroundEngine:
    """Batched surround/double-vote detection on device (ISSUE 17).

    The per-vote state TargetArrays keeps in compressed KV chunks lives
    here as resident ``[validator_chunk, history]`` int32 planes — min
    distances (default MAX_DISTANCE), max distances (default 0), plus a
    ring-occupancy plane for double-vote candidates. A ``jax.lax.scan``
    walks the batch sequentially (votes for one validator must observe
    each other, exactly as the host path does) while each vote's plane
    update is vectorized across the full epoch ring — banded min/max
    array work, the MXU/VPU fit the issue names.

    Verdict codes are bits (CODE_SURROUNDS / CODE_SURROUNDED /
    CODE_DOUBLE); the double bit is only a candidate — the caller
    (DeviceSlasher) confirms it against the exact-target root map, so a
    ring collision can never produce a false finding and device output
    stays bit-exact with the host ``Slasher`` oracle.

    Degradation: any fault inside ``process`` (including an injected
    ``slasher``-stage fault) trips a sticky host fallback. The engine
    keeps a per-chunk vote log, so the fallback replays the chunk's
    history into host mirror planes and continues — no findings lost,
    same codes, no crash.
    """

    def __init__(self, validator_chunk_size: int | None = None,
                 history_length: int | None = None, pad_floor: int = 8):
        self.validator_chunk_size = (
            validator_chunk_size if validator_chunk_size is not None
            else int(knobs.knob("LHTPU_SLASHER_CHUNK"))
        )
        self.history_length = (
            history_length if history_length is not None
            else int(knobs.knob("LHTPU_SLASHER_HISTORY"))
        )
        self.pad_floor = max(1, pad_floor)
        forced = knobs.knob("LHTPU_SLASHER_DEVICE")
        self._jax = None
        self._jnp = None
        self._scan = None
        if forced == "0":
            self.device = False
        else:
            try:
                import jax
                import jax.numpy as jnp

                self._jax, self._jnp = jax, jnp
                self._scan = self._build_scan()
                self.device = True
            except Exception:
                if forced == "1":
                    raise
                self.device = False
        self.degraded = False       # sticky host fallback after a fault
        self.fallbacks = 0
        self.fault_kinds: dict[str, int] = {}
        self.processed = 0
        # per validator-chunk state
        self._dev: dict[int, tuple] = {}          # chunk -> jnp planes
        self._host: dict[int, tuple] = {}         # chunk -> host mirror
        self._log: dict[int, list] = {}           # chunk -> [(vi,s,t)]

    # ----------------------------------------------------------- public api
    def process(self, votes: list[tuple[int, int, int]]) -> list[int]:
        """Classify ``(validator, source, target)`` votes in order;
        returns one code-bit int per vote, aligned with the input."""
        with tracing.span("slasher/batch", votes=len(votes)) as batch:
            self.processed += len(votes)
            with tracing.span("slasher/group"):
                groups: dict[int, list[tuple[int, int, int, int]]] = {}
                for pos, (v, s, t) in enumerate(votes):
                    chunk, vi = divmod(int(v), self.validator_chunk_size)
                    groups.setdefault(chunk, []).append(
                        (pos, vi, int(s), int(t)))
            batch.set(chunks=len(groups))
            codes = [0] * len(votes)
            launches = slots = inits = 0
            faults: dict[str, int] = {}
            probe = monitoring.jit_cache_probe(self._scan, "slasher_scan")
            for chunk in sorted(groups):
                items = groups[chunk]
                try:
                    resilience.maybe_inject("slasher")
                    if self.device and not self.degraded:
                        out, pad, fresh = self._process_device(chunk, items)
                        launches += 1
                        slots += pad
                        inits += fresh
                    else:
                        with tracing.span("slasher/host_path", mirror=False):
                            out = self._process_host(chunk, items)
                except Exception as exc:
                    _, kind = resilience.classify(exc)
                    self.fault_kinds[kind] = self.fault_kinds.get(kind, 0) + 1
                    faults[kind] = faults.get(kind, 0) + 1
                    self.fallbacks += 1
                    self.degraded = True
                    with tracing.span("slasher/host_path", mirror=False):
                        out = self._process_host(chunk, items, rebuild=True)
                for (pos, _, _, _), code in zip(items, out):
                    codes[pos] = code
                self._log.setdefault(chunk, []).extend(
                    (vi, s, t) for _, vi, s, t in items
                )
            batch.set(slots=slots)
            VOTES_TOTAL.inc(len(votes))
            if launches:
                probe(launches)
                LAUNCHES_TOTAL.inc(launches)
            if inits:
                PLANE_INITS_TOTAL.inc(inits)
            for kind, n in faults.items():
                FALLBACKS_TOTAL.inc(n, kind=kind)
        return codes

    def report(self) -> dict:
        return {
            "device": bool(self.device and not self.degraded),
            "degraded": self.degraded,
            "fallbacks": self.fallbacks,
            "fault_kinds": dict(self.fault_kinds),
            "votes": self.processed,
            "chunks": len(self._log),
        }

    # ---------------------------------------------------------- device path
    def _fresh_device(self):
        jnp = self._jnp
        shape = (self.validator_chunk_size, self.history_length)
        return (
            jnp.full(shape, MAX_DISTANCE, dtype=jnp.int32),
            jnp.zeros(shape, dtype=jnp.int32),
            jnp.zeros(shape, dtype=jnp.bool_),
        )

    def _build_scan(self):
        jax, jnp = self._jax, self._jnp
        H = self.history_length

        def step(carry, vote):
            minp, maxp, occ = carry
            vi, s, t, valid = vote[0], vote[1], vote[2], vote[3]
            ok = valid != 0
            # surround checks — same plane reads as
            # TargetArrays.check_surround, surrounded takes priority
            e1 = s - 1
            d1 = maxp[vi, e1 % H]
            surrounded = (s >= 1) & (d1 != 0) & (e1 + d1 > t)
            e2 = s + 1
            d2 = minp[vi, e2 % H]
            surrounds = (d2 != MAX_DISTANCE) & (e2 + d2 < t)
            dbl = occ[vi, t % H]
            code = (
                surrounds.astype(jnp.int32) * CODE_SURROUNDS
                + surrounded.astype(jnp.int32) * CODE_SURROUNDED
                + dbl.astype(jnp.int32) * CODE_DOUBLE
            )
            code = jnp.where(ok, code, 0)
            # vectorized apply over every ring position p: recover the
            # epoch e covering p inside the vote's affected band
            p = jnp.arange(H, dtype=jnp.int32)
            hi = jnp.minimum(t, s + H - 1)
            off_max = (p - s) % H
            e_max = s + off_max
            in_max = off_max <= (hi - s)
            d_max = jnp.minimum(t - e_max, MAX_DISTANCE - 1)
            row_max = maxp[vi]
            maxp = maxp.at[vi].set(
                jnp.where(ok & in_max & (d_max > row_max), d_max, row_max)
            )
            lo = jnp.maximum(0, s - H + 1)
            off_min = (s - p) % H
            e_min = s - off_min
            in_min = off_min <= (s - lo)
            d_min = jnp.minimum(t - e_min, MAX_DISTANCE - 1)
            row_min = minp[vi]
            minp = minp.at[vi].set(
                jnp.where(ok & in_min & (d_min < row_min), d_min, row_min)
            )
            occ = occ.at[vi, t % H].set(occ[vi, t % H] | ok)
            return (minp, maxp, occ), code

        def run(planes, votes):
            return jax.lax.scan(step, planes, votes)

        return jax.jit(run)

    def _process_device(self, chunk: int,
                        items) -> tuple[list[int], int, bool]:
        """One chunk's votes through one plane-scan program: (codes,
        vote slots scanned, whether the chunk's planes were made here)."""
        jnp = self._jnp
        n = len(items)
        pad = max(self.pad_floor, 1 << max(0, (n - 1).bit_length()))
        with tracing.span("slasher/prepare", mirror=False):
            rows = [(vi, s, t, 1) for _, vi, s, t in items]
            rows += [(0, 0, 0, 0)] * (pad - n)
            votes = jnp.asarray(rows, dtype=jnp.int32)
        planes = self._dev.get(chunk)
        fresh = planes is None
        if fresh:
            with tracing.span("slasher/init_planes", mirror=False):
                planes = self._fresh_device()
        with tracing.span("slasher/launch", mirror=False):
            new_planes, codes = self._scan(planes, votes)
        with tracing.span("slasher/wait", mirror=False):
            codes = self._jax.device_get(codes)
        out = [int(c) for c in codes[:n]]
        self._dev[chunk] = new_planes
        return out, pad, fresh

    # ------------------------------------------------------------ host path
    def _fresh_host(self):
        n = self.validator_chunk_size * self.history_length
        return ([MAX_DISTANCE] * n, [0] * n, set())

    def _process_host(self, chunk: int, items,
                      rebuild: bool = False) -> list[int]:
        planes = self._host.get(chunk)
        if planes is None or rebuild:
            planes = self._fresh_host()
            self._host[chunk] = planes
            for vi, s, t in self._log.get(chunk, ()):
                self._host_vote(planes, vi, s, t)
        return [self._host_vote(planes, vi, s, t) for _, vi, s, t in items]

    def _host_vote(self, planes, vi: int, s: int, t: int) -> int:
        minp, maxp, occ = planes
        H = self.history_length
        base = vi * H
        code = 0
        if s >= 1:
            e = s - 1
            d = maxp[base + e % H]
            if d != 0 and e + d > t:
                code |= CODE_SURROUNDED
        e = s + 1
        d = minp[base + e % H]
        if d != MAX_DISTANCE and e + d < t:
            code |= CODE_SURROUNDS
        if (vi, t % H) in occ:
            code |= CODE_DOUBLE
        hi = min(t, s + H - 1)
        for ep in range(s, hi + 1):
            idx = base + ep % H
            dd = min(t - ep, MAX_DISTANCE - 1)
            if dd > maxp[idx]:
                maxp[idx] = dd
        lo = max(0, s - H + 1)
        for ep in range(lo, s + 1):
            idx = base + ep % H
            dd = min(t - ep, MAX_DISTANCE - 1)
            if dd < minp[idx]:
                minp[idx] = dd
        occ.add((vi, t % H))
        return code
