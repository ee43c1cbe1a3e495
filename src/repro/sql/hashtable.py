"""Physical dimension hash tables: host-side build + cross-query cache.

The build is the numpy parallel linear-probe placement (emulates the
paper's CAS build; any placement satisfying the gapless-chain invariant is
a valid linear-probing table).  Dimension tables are small relative to the
fact table, so the build runs on the host and only the probe side is a
device kernel — the paper makes the same split (§4.3: build time is noise
at SSB dimension cardinalities).

``HashTableCache`` keys built tables by the *logical* identity of the
build side — (dim table, key column, filter fingerprint, payload
fingerprint) — so a query server can skip the build phase whenever two
queries share a join build side (e.g. every SSB flight joins ``date`` on
``d_datekey`` with the same payload).
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import EMPTY   # probe kernels compare against this
from repro.sql import plan as P
from repro.sql import spans as SP
from repro.sql import ssb
from repro.sql import storage as ST
from repro.sql.storage import PackedTable


def np_hash(keys: np.ndarray, n_slots: int) -> np.ndarray:
    return ((keys.astype(np.uint32) * np.uint32(2654435761))
            & np.uint32(n_slots - 1)).astype(np.int64)


def np_build(keys: np.ndarray, vals: np.ndarray, n_slots: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    htk = np.full(n_slots, EMPTY, np.int32)
    htv = np.zeros(n_slots, np.int32)
    slot = np_hash(keys, n_slots)
    pending = np.arange(len(keys))
    while len(pending):
        s = slot[pending]
        order = np.argsort(s, kind="stable")
        s_sorted = s[order]
        first = np.ones(len(s_sorted), bool)
        first[1:] = s_sorted[1:] != s_sorted[:-1]
        winner_rows = pending[order[first]]
        winner_slots = s_sorted[first]
        empty = htk[winner_slots] == EMPTY
        placed = winner_rows[empty]
        htk[winner_slots[empty]] = keys[placed]
        htv[winner_slots[empty]] = vals[placed]
        placed_mask = np.zeros(len(keys), bool)
        placed_mask[placed] = True
        rest = pending[~placed_mask[pending]]
        slot[rest] = (slot[rest] + 1) & (n_slots - 1)
        pending = rest
    return htk, htv


def next_pow2(n: int) -> int:
    return 1 << max(4, int(np.ceil(np.log2(max(n * 2, 2)))))


def filtered_build_side(db: ssb.Database, join: P.HashJoin
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """(keys, payload vals) of one join's dim side after the dim filter —
    the logical build side shared by the monolithic and the partitioned
    physical builds.  May be empty (filter drops every row): the builds
    below must then yield valid all-EMPTY tables, and every probe misses
    (the query's result is zero, not a crash)."""
    dim: ssb.Table = getattr(db, join.dim)
    mask = P.pred_mask(join.filter, dim)
    keys = np.asarray(dim[join.key_col])[mask].astype(np.int32)
    vals = P.expr_values(join.payload, dim)[mask]
    if len(vals) and vals.min() < 0:
        # non-negative payloads are the engine's contract: the numpy
        # oracle marks probe misses with a negative sentinel, and negative
        # group-id contributions would wrap in the scatter-add — a
        # negative payload would silently diverge the three paths
        raise ValueError(
            f"join on {join.dim}.{join.key_col}: payload {join.payload!r} "
            f"yields negative values (min {int(vals.min())}) on filtered "
            "rows; payloads must be >= 0 after the dim filter")
    return keys, vals


# Key spans up to this many slots get a table as wide as the span.
DIRECT_SLOTS = 1 << 22


def table_slots(keys: np.ndarray, dim_keys: np.ndarray) -> int:
    """Slot count of a dim hash table holding ``keys``: at most half
    full, and — when the dimension's keys ``dim_keys`` (what fact foreign
    keys reference) span at most ``DIRECT_SLOTS`` values — at least that
    span.  The multiplicative hash maps any run of S consecutive
    integers to distinct slots of a power-of-two S, so then every build
    key sits in its home slot and a probe of any key in the span ends at
    its first slot.  On the XLA path each probe round gathers over every
    fact row in lock-step until the longest chain ends (up to ~20 rounds
    at half fill on SSB's filtered build sides); a span-wide table makes
    that one round.  Dense surrogate keys, as SSB's, always qualify."""
    n = next_pow2(max(len(keys), 1))
    if len(dim_keys):
        span = int(dim_keys.max()) - int(dim_keys.min()) + 1
        if n < span <= DIRECT_SLOTS:
            n = 1 << (span - 1).bit_length()
    return n


def build_dim_table(db: ssb.Database, join: P.HashJoin
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Build the (filtered) hash table for one join's dim side.
    Probe miss == row filtered (selective-join pipelining)."""
    from repro.sql import faults
    faults.maybe_fault("build")
    keys, vals = filtered_build_side(db, join)
    n_slots = table_slots(keys, np.asarray(
        getattr(db, join.dim)[join.key_col]))
    htk, htv = np_build(keys, vals, n_slots)
    return ST.upload(htk), ST.upload(htv)


@dataclass(frozen=True)
class PackedParts:
    """Dense packed layout of 2^bits per-partition hash tables: one
    ``(P, S)`` key array + one ``(P, S)`` value array, ``S`` a single
    power-of-two slot count shared by every partition (sized off the
    fullest partition, >=50% empty like the monolithic build).  Row ``p``
    IS partition p's table, so a Pallas grid over partitions can window
    it with a plain BlockSpec index map — the layout the fused
    single-launch probe kernel (``kernels/part_probe.py``) consumes."""
    htk: jnp.ndarray                    # (P, S) int32, EMPTY-filled slots
    htv: jnp.ndarray                    # (P, S) int32

    @property
    def n_parts(self) -> int:
        return self.htk.shape[0]

    @property
    def n_slots(self) -> int:
        return self.htk.shape[1]

    @property
    def nbytes(self) -> int:
        return int(self.htk.size + self.htv.size) * 4


def _bucket_runs(keys: np.ndarray, vals: np.ndarray, bits: int):
    """Sort the build side into contiguous low-bit bucket runs; yields
    (keys_run, vals_run) per partition."""
    bucket = keys & ((1 << bits) - 1)
    order = np.argsort(bucket, kind="stable")   # one pass, then slice
    keys, vals = keys[order], vals[order]       # contiguous bucket runs
    ends = np.cumsum(np.bincount(bucket, minlength=1 << bits))
    start = 0
    for p in range(1 << bits):
        yield keys[start:ends[p]], vals[start:ends[p]]
        start = int(ends[p])


def build_dim_partitions(db: ssb.Database, join: P.HashJoin, bits: int,
                         side: Optional[Tuple[np.ndarray, np.ndarray]]
                         = None, packed: bool = False):
    """Radix-partitioned build: 2^bits per-partition hash tables, bucketed
    by the key's low ``bits`` bits (the probe side partitions by the same
    rule).  With bits chosen from the cost model every table is
    cache/VMEM-resident during its partition's probe pass (paper §4.4,
    Fig. 8).  ``side`` lets a caller that already filtered the build side
    pass it in instead of filtering the dim table a second time.

    ``packed=False`` returns the loop layout — a list of per-partition
    (htk, htv) pairs, each sized to its own partition — consumed by the
    host-orchestrated ``part_loop`` strategy.  ``packed=True`` returns
    :class:`PackedParts`, the dense uniform-slot layout the fused
    single-launch kernel windows with its grid."""
    keys, vals = side if side is not None else filtered_build_side(db, join)
    if not packed:
        parts: List[Tuple[jnp.ndarray, jnp.ndarray]] = []
        for kp, vp in _bucket_runs(keys, vals, bits):
            htk, htv = np_build(kp, vp, next_pow2(max(len(kp), 1)))
            parts.append((ST.upload(htk), ST.upload(htv)))
        return parts
    counts = np.bincount(keys & ((1 << bits) - 1), minlength=1 << bits)
    n_slots = next_pow2(max(int(counts.max()) if len(keys) else 0, 1))
    htk = np.full((1 << bits, n_slots), EMPTY, np.int32)
    htv = np.zeros((1 << bits, n_slots), np.int32)
    for p, (kp, vp) in enumerate(_bucket_runs(keys, vals, bits)):
        htk[p], htv[p] = np_build(kp, vp, n_slots)
    return PackedParts(ST.upload(htk), ST.upload(htv))


def join_cache_key(join: P.HashJoin) -> Tuple:
    """Logical identity of a join's build side (mult is a probe-side
    concern and deliberately excluded — same table, different group
    multiplier still hits)."""
    return (join.dim, join.key_col,
            P.fingerprint(join.filter), P.fingerprint(join.payload))


def _has_callable(part) -> bool:
    if isinstance(part, tuple):
        return (bool(part) and part[0] == "callable") or \
            any(_has_callable(p) for p in part)
    return False


def _cacheable(key: Tuple) -> bool:
    """Identity-fingerprinted (callable) build sides — at any nesting
    depth, e.g. inside a FlagExpr — never re-hit across independently
    built plans, so storing them only pins memory."""
    return not _has_callable(key)


def db_fingerprint(db, tables: Optional[Iterable[str]] = None) -> Tuple:
    """Cheap data identity of a Database: per table, (attr, name, n_rows,
    crc32 of every column's data).  Build sides depend on *non*-key
    columns too (dim filters and payloads read attributes like
    ``s_region``), so all columns participate — two databases with equal
    fingerprints produce identical build sides and an equal-but-reloaded
    database may keep serving a warmed cache.

    ``tables`` restricts the fingerprint to the named database
    *attributes*: the cache only ever builds from dimension tables, so
    scoping the comparison to the dims its entries actually reference
    skips streaming the (orders-of-magnitude larger) fact table on every
    reload.  ``None`` fingerprints everything.

    A ``repro.sql.shard.ShardedDatabase`` fingerprints as its base
    Database (duck-typed via the ``base`` attribute): the shards differ
    only in the fact table, which build sides never read."""
    db = getattr(db, "base", db)
    names = None if tables is None else set(tables)
    items = []
    for attr, t in vars(db).items():
        # PackedTable decodes on access, so a packed database
        # fingerprints identically to its plain original — a cache
        # warmed on one serves the other (same logical data)
        if not isinstance(t, (ssb.Table, PackedTable)):
            continue
        if names is not None and attr not in names:
            continue
        crc = 0
        for c in sorted(t.columns):
            crc = zlib.crc32(np.ascontiguousarray(t[c]).tobytes(), crc)
        items.append((attr, t.name, t.n_rows, crc))
    return tuple(sorted(items))


@dataclass
class HashTableCache:
    """Keyed cache of built dimension hash tables with hit/miss stats.

    Scoped to a single *logical* database: the cache key is the logical
    build side, so entries built from one database must never answer for
    another.  The first ``get_or_build`` binds the cache to its database;
    later calls with a different object first compare ``db_fingerprint``
    — an equal-but-reloaded database (same tables, rows and key columns)
    rebinds and keeps the warmed entries, a genuinely different one
    raises rather than serving wrong tables.  The comparison is scoped to
    the dim tables the cached entries actually reference (``_dims``):
    only those tables can serve stale data, and fingerprinting just them
    avoids streaming the fact table's crc on every reload.  ``reset()``
    drops the entries and the binding for an explicit data reload.
    """
    tables: Dict[Tuple, object] = field(default_factory=dict)
    hits: int = 0
    misses: int = 0
    # recency bookkeeping for ResourceGovernor.evict_cold(): every cache
    # access stamps its key with a monotonically increasing tick
    _tick: int = 0
    _last_used: Dict[Tuple, int] = field(default_factory=dict, repr=False)
    _db: object = None
    _dims: Set[str] = field(default_factory=set)
    _db_fp: Optional[Tuple] = None      # (dims scope, fingerprint) memo
    # databases already proven equal to the binding: the base database
    # plus every shard replica (repro.sql.shard slices the fact table
    # but shares the dim objects) and every reloaded copy that passed
    # the fingerprint check — re-fingerprinting per shard switch would
    # put a crc pass on the sharded host loop's inner path
    _accepted: List[object] = field(default_factory=list, repr=False)

    def _bind(self, db) -> None:
        if self._db is db or any(db is a for a in self._accepted):
            return
        if self._db is None:
            self._db = db           # fingerprint deferred: the common
            self._accepted.append(db)   # never-reloaded case pays nothing
            return
        dims = frozenset(self._dims)
        if self._db_fp is None or self._db_fp[0] != dims:
            self._db_fp = (dims, db_fingerprint(self._db, dims))
        if db_fingerprint(db, dims) == self._db_fp[1]:
            self._db = db           # reloaded copy / shard replica of
            self._accepted.append(db)   # the same data
            return
        raise ValueError(
            "HashTableCache is scoped to one Database; call reset() (or "
            "use a fresh cache) before serving a different database")

    def reset(self) -> None:
        """Drop all entries and the database binding (data reload)."""
        self.tables.clear()
        self._dims.clear()
        self._last_used.clear()
        self._db = None
        self._db_fp = None
        self._accepted.clear()

    def _touch(self, key: Tuple) -> None:
        self._tick += 1
        self._last_used[key] = self._tick

    def evict_cold(self, keep: int = 2) -> int:
        """Drop every entry except the ``keep`` most recently used —
        the ResourceGovernor's memory-pressure reaction.  Entries keep
        their logical identity, so a later request simply rebuilds
        (a miss, not an error).  Returns the eviction count."""
        if len(self.tables) <= keep:
            return 0
        by_recency = sorted(self.tables,
                            key=lambda k: self._last_used.get(k, 0))
        victims = by_recency[:len(by_recency) - keep]
        for k in victims:
            self.tables.pop(k, None)
            self._last_used.pop(k, None)
        return len(victims)

    def get_or_build(self, db: ssb.Database, join: P.HashJoin
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        with SP.span(SP.HASHTABLE):
            self._bind(db)
            key = join_cache_key(join)
            hit = self.tables.get(key)
            if hit is not None:
                self.hits += 1
                self._touch(key)
                return hit
            self.misses += 1
            built = build_dim_table(db, join)
            if _cacheable(key):
                self.tables[key] = built
                self._dims.add(join.dim)
                self._touch(key)
            return built

    def get_build_count(self, db: ssb.Database, join: P.HashJoin) -> int:
        """Filtered build-side row count, memoized under the join's
        logical key (the partitioned lowering needs it on every execute
        to size ``part_bits``; re-filtering the dim per request would
        waste the warm-cache path).  Not a build, so it does not touch
        the hit/miss stats."""
        self._bind(db)
        key = ("n_build", join_cache_key(join))
        hit = self.tables.get(key)
        if hit is not None:
            self._touch(key)
            return hit
        n = len(filtered_build_side(db, join)[0])
        if _cacheable(key):
            self.tables[key] = n
            self._dims.add(join.dim)
            self._touch(key)
        return n

    def get_or_build_parts(self, db: ssb.Database, join: P.HashJoin,
                           bits: int, packed: bool = False):
        """Partitioned analogue of ``get_or_build``: 2^bits per-partition
        tables, cached under the build side's logical key + bits +
        physical layout (the loop's per-partition list and the fused
        kernel's :class:`PackedParts` are distinct entries)."""
        self._bind(db)
        key = (join_cache_key(join), "part", bits,
               "packed" if packed else "list")
        hit = self.tables.get(key)
        if hit is not None:
            self.hits += 1
            self._touch(key)
            return hit
        self.misses += 1
        built = build_dim_partitions(db, join, bits, packed=packed)
        if _cacheable(key):
            self.tables[key] = built
            self._dims.add(join.dim)
            self._touch(key)
        return built

    def get_or_build_replicated(self, db, join: P.HashJoin, mesh
                                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Per-device binding of one join's table: ``get_or_build``, then
        ``device_put`` fully replicated over ``mesh`` — cached under the
        logical key + the mesh's device set, so the transfer happens once
        per build, not once per sharded launch.  The logical entry is
        shared with the solo path (a replicated fetch after a solo build
        is one hit + one transfer, no rebuild)."""
        from jax.sharding import NamedSharding, PartitionSpec
        self._bind(db)
        key = (join_cache_key(join), "replicated",
               tuple(d.id for d in mesh.devices.flat))
        hit = self.tables.get(key)
        if hit is not None:
            self.hits += 1
            self._touch(key)
            return hit
        htk, htv = self.get_or_build(db, join)
        sh = NamedSharding(mesh, PartitionSpec())
        built = (jax.device_put(htk, sh), jax.device_put(htv, sh))
        if _cacheable(key):
            self.tables[key] = built
            self._dims.add(join.dim)
            self._touch(key)
        return built

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0
