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

import functools
import itertools
import zlib

import numpy as np

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
    "Device plane programs launched by the surround engine, one a batch")
TILES_TOTAL = REGISTRY.counter(
    "slasher_tiles_total",
    "Vote tiles the surround engine's plane programs stepped through")
PLANE_INITS_TOTAL = REGISTRY.counter(
    "slasher_plane_inits_total",
    "Validator chunks whose device planes were made fresh")
FALLBACKS_TOTAL = REGISTRY.counter(
    "slasher_fallbacks_total",
    "Batches the surround engine answered on the host after a fault",
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
    here as three resident device planes — min distances (int32,
    default MAX_DISTANCE), max distances (int32, default 0) and ring
    occupancy for double-vote candidates (bool) — each
    ``[devices, rows, history / 128, 128]``, sharded on the leading axis
    over a one-axis mesh of the first ``devices`` chips, so a plane row
    is whole (8, 128) tiles. A validator chunk gets a slot the first
    time it receives a vote (``_slot``, a host table, slots handed out
    in first-seen order), so the planes grow with the chunks touched.
    Slot ``k`` lives on shard ``k % devices`` at local slot
    ``k // devices``, and validator ``v`` owns row ``local × chunk + v %
    chunk`` of its shard; set-up's votes, which touch every chunk in
    chunk order, spread the chunks evenly. A chip holds its own rows
    only, and a plane grows along its local row axis, on every shard at
    once, so no row ever moves between chips.

    One ``process`` call is one device program across the chips: one
    upload, one launch, one read-back. Only the order of one
    validator's votes matters (a vote reads and writes its own row
    alone), so a vote's *round* is the number of earlier votes of its
    validator in the batch, and each shard's votes go round after round
    in tiles of ``T_eff`` rows, no row twice in a tile. Under
    ``shard_map`` every chip walks its own tiles with a ``fori_loop``:
    each gathers its rows of the local planes, computes the codes and
    the banded updates vectorized across the epoch ring, and scatters
    the rows back; no collective, since no vote reads another shard's
    rows. The planes are donated, so the update is in place. ``T_eff``
    is ``tile`` or, for smaller batches, the busiest shard's largest
    round rounded up to a power of two (at least ``pad_floor``); the
    tiles uploaded per shard are the busiest shard's count rounded up
    to a power of two, so a batch compiles nothing new unless its
    (tiles, ``T_eff``) bucket or the plane size is new. One chip is the
    case ``devices=1`` of the same program.

    Verdict codes are bits (CODE_SURROUNDS / CODE_SURROUNDED /
    CODE_DOUBLE); the double bit is only a candidate — the caller
    (DeviceSlasher) confirms it against the exact-target root map, so a
    ring collision can never produce a false finding and device output
    stays bit-exact with the host ``Slasher`` oracle.

    Degradation: any fault inside ``process`` (including an injected
    ``slasher``-stage fault) trips a sticky host fallback. The engine
    keeps a log of every batch's votes, so the fallback replays each
    touched chunk's history into host mirror planes and answers the
    whole batch there — no findings lost, same codes, no crash. The
    donated device planes are never read again.
    """

    def __init__(self, validator_chunk_size: int | None = None,
                 history_length: int | None = None, pad_floor: int = 8,
                 tile: int = 512, devices: int = 1):
        if devices < 1:
            raise ValueError(f"devices must be at least 1, not {devices}")
        self.shards = int(devices)
        self.validator_chunk_size = (
            validator_chunk_size if validator_chunk_size is not None
            else int(knobs.knob("LHTPU_SLASHER_CHUNK"))
        )
        self.history_length = (
            history_length if history_length is not None
            else int(knobs.knob("LHTPU_SLASHER_HISTORY"))
        )
        self.pad_floor = max(1, pad_floor)
        self.tile = max(self.pad_floor, tile)
        forced = knobs.knob("LHTPU_SLASHER_DEVICE")
        self._jax = None
        self._program = None
        self._grow = None
        self._sharding = None
        if forced == "0":
            self.device = False
        else:
            try:
                import jax

                self._jax = jax
                chips = jax.devices()
                self.device = True
            except Exception:
                if forced == "1":
                    raise
                self.device = False
        if self.device:
            if len(chips) < self.shards:
                raise ValueError(f"the slasher's planes need {self.shards} "
                                 f"devices, JAX sees {len(chips)}")
            from jax.sharding import Mesh, NamedSharding, PartitionSpec

            mesh = Mesh(np.asarray(chips[:self.shards]), (SHARD_AXIS,))
            self._sharding = NamedSharding(mesh, PartitionSpec(SHARD_AXIS))
            self._program = _plane_program(mesh)
            self._grow = _grow_program(mesh)
        self.degraded = False       # sticky host fallback after a fault
        self.fallbacks = 0
        self.fault_kinds: dict[str, int] = {}
        self.processed = 0
        self._slot = np.full(0, -1, np.int64)     # chunk -> slot, -1 none
        self._slots_used = 0                      # slots handed out
        self._slots_made = 0                      # slots with plane rows
        self._planes = None                       # (min, max, occ) planes
        self._host: dict[int, tuple] = {}         # chunk -> host mirror
        self._log: list[np.ndarray] = []          # each batch's votes

    # ----------------------------------------------------------- public api
    def process(self, votes: list[tuple[int, int, int]]) -> list[int]:
        """Classify ``(validator, source, target)`` votes in order;
        returns one code-bit int per vote, aligned with the input."""
        with tracing.span("slasher/batch", votes=len(votes)) as batch:
            if not votes:
                return []
            self.processed += len(votes)
            C = self.validator_chunk_size
            with tracing.span("slasher/group"):
                arr = np.fromiter(itertools.chain.from_iterable(votes),
                                  np.int64, 3 * len(votes)).reshape(-1, 3)
                chunk, vi = np.divmod(arr[:, 0], C)
                slot = self._assign_slots(chunk)
                with tracing.span("slasher/route", mirror=False):
                    local, shard = np.divmod(slot, self.shards)
                    per_shard = np.bincount(shard, minlength=self.shards)
                    dest, t_eff, tiles, rounds = _tiles(
                        slot * C + vi, self.tile, self.pad_floor, shard,
                        self.shards)
            batch.set(chunks=int(np.count_nonzero(np.bincount(slot))),
                      rounds=rounds, shards=self.shards,
                      shard_votes_max=int(per_shard.max()))
            launched = False
            fault = None
            try:
                resilience.maybe_inject("slasher")
                if self.device and not self.degraded:
                    codes = self._process_device(
                        arr, shard, local * C + vi, dest, t_eff, tiles)
                    launched = True
                    batch.set(tiles=tiles,
                              slots=self.shards * tiles * t_eff)
                else:
                    with tracing.span("slasher/host_path", mirror=False):
                        codes = self._process_host(arr)
            except Exception as exc:
                _, fault = resilience.classify(exc)
                self.fault_kinds[fault] = self.fault_kinds.get(fault, 0) + 1
                self.fallbacks += 1
                self.degraded = True
                self._planes = None
                self._host.clear()
                with tracing.span("slasher/host_path", mirror=False):
                    codes = self._process_host(arr)
            self._log.append(arr)
            VOTES_TOTAL.inc(len(votes))
            if launched:
                LAUNCHES_TOTAL.inc()
                TILES_TOTAL.inc(self.shards * tiles)
                for k, n in enumerate(per_shard.tolist()):
                    monitoring.SLASHER_SHARD_VOTES.inc(n, shard=str(k))
            if fault is not None:
                FALLBACKS_TOTAL.inc(kind=fault)
        return codes

    def report(self) -> dict:
        return {
            "device": bool(self.device and not self.degraded),
            "degraded": self.degraded,
            "fallbacks": self.fallbacks,
            "fault_kinds": dict(self.fault_kinds),
            "votes": self.processed,
            "chunks": self._slots_used,
            "shards": self.shards,
        }

    def _assign_slots(self, chunk: np.ndarray) -> np.ndarray:
        """Each vote's chunk slot; chunks seen for the first time get
        the next slots, in chunk order."""
        if chunk.min() < 0:
            raise ValueError("negative validator index")
        top = int(chunk.max()) + 1
        if top > len(self._slot):
            self._slot = np.concatenate(
                (self._slot, np.full(top - len(self._slot), -1, np.int64)))
        slot = self._slot[chunk]
        if (slot < 0).any():
            new = np.unique(chunk[slot < 0])
            first, self._slots_used = (self._slots_used,
                                       self._slots_used + len(new))
            self._slot[new] = np.arange(first, self._slots_used)
            slot = self._slot[chunk]
        return slot

    # ---------------------------------------------------------- device path
    def _grow_planes(self) -> int:
        """Give every slot its plane rows; returns the chunks given rows.
        Each shard grows along its local row axis to ``ceil(slots /
        devices)`` chunks, one plane at a time, each old buffer released
        before the next plane grows, so the peak is one plane's old and
        new copy."""
        fresh = self._slots_used - self._slots_made
        if not fresh:
            return 0
        C, N, H = self.validator_chunk_size, self.shards, self.history_length
        need = -(-self._slots_used // N) * C
        have = 0 if self._planes is None else self._planes[0].shape[1]
        self._slots_made = self._slots_used
        if need == have:
            return fresh
        with tracing.span("slasher/init_planes", mirror=False):
            planes, self._planes = list(self._planes or ()), None
            lanes = 128 if H % 128 == 0 else H
            for k, (fill, dtype) in enumerate(
                    ((MAX_DISTANCE, np.int32), (0, np.int32),
                     (False, np.bool_))):
                if k < len(planes):
                    old = planes[k]
                else:
                    old = self._jax.device_put(
                        np.zeros((N, 0, H // lanes, lanes), dtype),
                        self._sharding)
                    planes.append(old)
                planes[k] = self._grow(old, need - have, fill)
                old.delete()
            self._planes = tuple(planes)
        return fresh

    def _process_device(self, arr, shard, rows, dest, t_eff: int,
                        tiles: int) -> list[int]:
        """The batch through one plane program; codes in input order."""
        with tracing.span("slasher/prepare", mirror=False):
            run = _pow2(tiles) * t_eff
            at = shard * run + dest
            buf = np.zeros((self.shards * run, 3), np.int32)
            buf[:, 0] = -1
            buf[at, 0] = rows
            buf[at, 1:] = arr[:, 1:]
            votes = self._jax.device_put(
                buf.reshape(self.shards, -1, t_eff, 3), self._sharding)
        fresh = self._grow_planes()
        probe = monitoring.jit_cache_probe(self._program, "slasher_scan")
        with tracing.span("slasher/launch", mirror=False):
            *planes, codes = self._program(*self._planes, votes,
                                           np.int32(tiles))
        self._planes = tuple(planes)
        probe()
        if fresh:
            PLANE_INITS_TOTAL.inc(fresh)
        with tracing.span("slasher/wait", mirror=False):
            codes = self._jax.device_get(codes)
        return codes.reshape(-1)[at].tolist()

    # ------------------------------------------------------------ host path
    def _fresh_host(self):
        n = self.validator_chunk_size * self.history_length
        return ([MAX_DISTANCE] * n, [0] * n, set())

    def _process_host(self, arr: np.ndarray) -> list[int]:
        """The batch on host mirror planes; a chunk's mirror is rebuilt
        from the vote log the first time the host path meets it."""
        C = self.validator_chunk_size
        chunks = np.unique(arr[:, 0] // C)
        need = chunks[[int(c) not in self._host for c in chunks]]
        if len(need):
            for c in need.tolist():
                self._host[c] = self._fresh_host()
            if self._log:
                past = np.concatenate(self._log)
                past = past[np.isin(past[:, 0] // C, need)]
                for v, s, t in past.tolist():
                    self._host_vote(self._host[v // C], v % C, s, t)
        return [self._host_vote(self._host[v // C], v % C, s, t)
                for v, s, t in arr.tolist()]

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


#: the one mesh axis the engine's planes are sharded on
SHARD_AXIS = "shard"


@functools.cache
def _plane_program(mesh):
    """The jitted program that steps one batch's tiles over the donated
    planes on every chip of ``mesh``, shared by the engines on those
    chips (the history is the planes' width). Each chip runs the tile
    loop on its own shard; nothing crosses chips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    def run(minp, maxp, occ, votes, tiles):
        # a plane row is [groups, lanes]: on the TPU a row is then whole
        # (8, 128) tiles, one contiguous run of HBM, not one sublane of
        # every tile of a [rows, history] plane
        rows_total, G, L = minp.shape
        H = G * L
        _, T, _ = votes.shape
        lane = jnp.arange(T, dtype=jnp.int32)
        p = jnp.arange(H, dtype=jnp.int32).reshape(1, G, L)

        def at(rows, pos):
            """Each gathered row's value at ring position ``pos``."""
            return rows[lane, pos // L, pos % L]

        def tile(i, carry):
            minp, maxp, occ, codes = carry
            row, s, t = votes[i, :, 0], votes[i, :, 1], votes[i, :, 2]
            ok = row >= 0
            # pad rows point past the planes, each at its own row, so
            # their gathers fill and their scatters drop
            row = jnp.where(ok, row, rows_total + lane)
            mx = maxp.at[row].get(mode="fill", fill_value=0)
            mn = minp.at[row].get(mode="fill", fill_value=MAX_DISTANCE)
            oc = occ.at[row].get(mode="fill", fill_value=False)
            # surround checks — same plane reads as
            # TargetArrays.check_surround, surrounded takes priority
            e1 = s - 1
            d1 = at(mx, e1 % H)
            surrounded = (s >= 1) & (d1 != 0) & (e1 + d1 > t)
            e2 = s + 1
            d2 = at(mn, e2 % H)
            surrounds = (d2 != MAX_DISTANCE) & (e2 + d2 < t)
            dbl = at(oc, t % H)
            code = (
                surrounds.astype(jnp.int32) * CODE_SURROUNDS
                + surrounded.astype(jnp.int32) * CODE_SURROUNDED
                + dbl.astype(jnp.int32) * CODE_DOUBLE
            )
            codes = codes.at[i].set(jnp.where(ok, code, 0))
            # vectorized apply over every ring position p: recover the
            # epoch e covering p inside the vote's affected band
            s, t = s[:, None, None], t[:, None, None]
            hi = jnp.minimum(t, s + H - 1)
            off_max = (p - s) % H
            e_max = s + off_max
            in_max = off_max <= (hi - s)
            d_max = jnp.minimum(t - e_max, MAX_DISTANCE - 1)
            mx = jnp.where(in_max & (d_max > mx), d_max, mx)
            lo = jnp.maximum(0, s - H + 1)
            off_min = (s - p) % H
            e_min = s - off_min
            in_min = off_min <= (s - lo)
            d_min = jnp.minimum(t - e_min, MAX_DISTANCE - 1)
            mn = jnp.where(in_min & (d_min < mn), d_min, mn)
            oc = oc | (p == t % H)
            # whole rows, as gathered: a scatter of single elements makes
            # XLA relayout the whole occupancy plane
            put = dict(mode="drop", unique_indices=True)
            maxp = maxp.at[row].set(mx, **put)
            minp = minp.at[row].set(mn, **put)
            occ = occ.at[row].set(oc, **put)
            return minp, maxp, occ, codes

        codes = jnp.zeros(votes.shape[:2], jnp.int32)
        return jax.lax.fori_loop(0, tiles, tile, (minp, maxp, occ, codes))

    def shard(minp, maxp, occ, votes, tiles):
        """One chip's part: its planes and tiles, without the shard
        axis (a view, so the donated planes stay in place)."""
        out = run(minp[0], maxp[0], occ[0], votes[0], tiles)
        return tuple(x[None] for x in out)

    spec = PartitionSpec(SHARD_AXIS)
    return jax.jit(jax.shard_map(shard, mesh=mesh,
                                 in_specs=(spec,) * 4 + (PartitionSpec(),),
                                 out_specs=(spec,) * 4, check_vma=False),
                   donate_argnums=(0, 1, 2))


@functools.cache
def _grow_program(mesh):
    """``grow(plane, rows, fill)``: the plane with ``rows`` more rows
    of ``fill`` on every shard, appended along the local row axis, so
    no row moves between chips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    def grow(plane, rows, fill):
        def shard(old):
            new = jnp.full((1, rows) + old.shape[2:], fill, old.dtype)
            return jnp.concatenate((old, new), axis=1)

        spec = PartitionSpec(SHARD_AXIS)
        return jax.shard_map(shard, mesh=mesh, in_specs=spec,
                             out_specs=spec)(plane)

    return jax.jit(grow, static_argnums=(1, 2))


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _tiles(rows: np.ndarray, tile: int, floor: int, shard: np.ndarray,
           shards: int):
    """Place each vote of a batch (its plane row, unique across shards,
    in input order) in a tile of its shard in which no row repeats:
    ``(dest, t_eff, tiles, rounds)``.

    A vote's round is the number of earlier votes of its row in the
    batch; on each shard the rounds go in order, each padded to whole
    tiles of ``t_eff`` rows, and vote ``i`` goes to slot ``dest[i]`` of
    its shard's tiles laid end to end. Round 0 is the largest, so
    ``t_eff`` is ``tile``, or the busiest shard's round 0 rounded up to
    a power of two (at least ``floor``) where that is smaller; ``tiles``
    is the busiest shard's count; ``shard`` gives each vote's shard, of
    ``shards``."""
    n = len(rows)
    pos = np.arange(n)
    order = np.argsort(rows, kind="stable")
    ranked = rows[order]
    first = np.ones(n, bool)
    first[1:] = ranked[1:] != ranked[:-1]
    rnd = np.empty(n, np.int64)
    rnd[order] = pos - np.maximum.accumulate(np.where(first, pos, 0))
    rounds = int(rnd.max()) + 1
    # per (shard, round): its votes, its tiles, its first slot
    group = shard * rounds + rnd
    per_group = np.bincount(group, minlength=shards * rounds)
    t_eff = min(tile, max(floor, _pow2(int(per_group[::rounds].max()))))
    per_tiles = (-(-per_group // t_eff)).reshape(shards, rounds)
    start = (np.cumsum(per_tiles, axis=1) - per_tiles).ravel() * t_eff
    by_group = np.argsort(group, kind="stable")
    rank = pos - np.repeat(np.cumsum(per_group) - per_group, per_group)
    dest = np.empty(n, np.int64)
    dest[by_group] = start[group[by_group]] + rank
    return dest, t_eff, int(per_tiles.sum(axis=1).max()), rounds
