"""Sharded fact-table execution: row partitions over a device mesh.

The paper's bandwidth argument (§4: analytic scans saturate the memory
system, so speedup tracks the bandwidth ratio) extends directly to
*aggregate multi-chip bandwidth*: N devices scanning disjoint fact
shards deliver ~N x scan GB/s, provided the per-shard work stays the
same single-pass kernel and the only cross-device traffic is the final
(n_groups,) aggregate grid.  This module owns that decomposition:

  shard     — ``shard_database(db, mesh_or_count)`` cuts the fact table
              into contiguous row ranges, one per device
              (``storage.slice_rows``: plain columns slice as views,
              packed columns re-pack under the parent encoding).  The
              dimension tables are shared BY OBJECT with the base
              database — replication, not copies — so the
              ``HashTableCache`` serves every shard from one build.
  replicate — :func:`replicate` pins small arrays (dim hash tables) to
              every mesh device once, instead of re-transferring per
              launch.
  reduce    — per-shard partial group aggregates merge pairwise
              (:func:`tree_merge`, the host mirror of the mesh's
              ``psum``).  SSB measures are integer-valued, and f32
              partial sums of integers stay exact far beyond SSB
              cardinalities — so ANY association order yields the same
              bits and sharded results are bit-identical to the solo
              fused pass (property-tested in tests/test_shard.py via
              :class:`GroupPartial`).

The compiler's ``sharded`` strategy (``repro.sql.compile``) consumes
this module two ways: a host loop running the existing fused lowering
unchanged per shard (``mode="ref"``, or no mesh), and a
``shard_map``-over-mesh path feeding :func:`stacked_stream` batches to
the unchanged kernels with the reduction fused in as a ``psum``
(``ops.spja(..., axis_name=...)``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.distributed.sharding import dp_size
from repro.sql import ssb
from repro.sql import storage as ST

SHARD_AXIS = "data"
# stacked shard streams pad to a multiple of 32 rows so every packed
# physical width (1..32 bits -> 32..1 values per word) fills whole words
_LANE = 32


def default_mesh(n_shards: Optional[int] = None) -> Mesh:
    """A 1-D ``(SHARD_AXIS,)`` mesh over the first ``n_shards`` visible
    devices (all of them when None)."""
    devs = jax.devices()
    n = len(devs) if n_shards is None else min(int(n_shards), len(devs))
    return Mesh(np.array(devs[:n]), (SHARD_AXIS,))


@dataclass
class ShardedDatabase:
    """A Database plus its row-partitioned fact shards.

    ``base`` is the unsharded original; ``shards[i]`` is a Database
    whose fact attribute is rows ``[bounds[i], bounds[i+1])`` and whose
    dimension tables are the base's own objects.  Attribute access
    delegates to ``base`` (``sdb.lineorder``, ``sdb.sf``, ...), so a
    ShardedDatabase quacks like its Database for the oracle, the cost
    model, the hash-table cache and every non-sharded strategy — only
    the ``sharded`` execution path looks inside."""
    base: ssb.Database
    shards: List[ssb.Database]
    bounds: np.ndarray                  # (S+1,) fact-row offsets
    fact: str
    mesh: Optional[Mesh] = None
    # stacked-stream memos for the shard_map path: a resident sharded
    # database uploads each column's (S, pad_rows) batch once
    _streams: Dict[str, Tuple] = field(default_factory=dict, repr=False)
    _validity: Optional[Tuple] = field(default=None, repr=False)

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def pad_rows(self) -> int:
        """Uniform per-shard row count of the stacked layout: the widest
        shard rounded up to the packing lane."""
        widths = np.diff(self.bounds)
        w = int(widths.max()) if len(widths) else 0
        return max(_LANE, -(-w // _LANE) * _LANE)

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self.base, name)


def base_of(db) -> ssb.Database:
    """The unsharded Database behind ``db`` (identity for a plain one)."""
    return db.base if isinstance(db, ShardedDatabase) else db


def shard_count(db) -> int:
    return db.n_shards if isinstance(db, ShardedDatabase) else 1


def shard_database(db: ssb.Database,
                   parts: Union[int, Mesh, None] = None,
                   fact: str = "lineorder") -> ShardedDatabase:
    """Partition ``db``'s fact table row-wise into contiguous per-device
    shards.  ``parts`` is a shard count, a Mesh (its data-parallel size
    gives the count), or None (one shard per visible device).

    Shard ``i`` holds rows ``[i*n//S, (i+1)*n//S)`` — sizes differ by at
    most one row, and S may exceed the row count (the tail shards are
    then empty; execution and the merge handle zero-row shards).  When
    at least S devices are visible the result carries a mesh and the
    compiler may run the shards under ``shard_map``; otherwise only the
    host-loop path applies (the shard count is a LOGICAL choice,
    deliberately decoupled from the physical device count so
    equivalence holds at any S on any host)."""
    db = base_of(db)
    mesh: Optional[Mesh] = None
    if parts is None:
        mesh = default_mesh()
        s = dp_size(mesh)
    elif isinstance(parts, Mesh):
        mesh = parts
        s = dp_size(mesh)
    else:
        s = int(parts)
        if s < 1:
            raise ValueError(f"shard count must be >= 1, got {s}")
        if s > 1 and len(jax.devices()) >= s:
            mesh = default_mesh(s)
    table = getattr(db, fact)
    n = table.n_rows
    bounds = np.array([(i * n) // s for i in range(s + 1)], np.int64)
    shards = [dataclasses.replace(
        db, **{fact: ST.slice_rows(table, int(bounds[i]),
                                   int(bounds[i + 1]))})
        for i in range(s)]
    return ShardedDatabase(db, shards, bounds, fact, mesh)


# ---------------------------------------------------------------------------
# tree reduction of partial aggregates
# ---------------------------------------------------------------------------


def tree_merge(partials) -> np.ndarray:
    """Pairwise (binary-tree) reduction of per-shard partial aggregate
    grids — the host mirror of the mesh ``psum``.  On integer-valued f32
    partials (SSB measures) addition is exact, so every association
    order — host tree, mesh ring, sequential — produces identical bits;
    the hypothesis property test pins this down."""
    parts = [np.asarray(p) for p in partials]
    if not parts:
        raise ValueError("tree_merge needs at least one partial")
    while len(parts) > 1:
        parts = [parts[i] + parts[i + 1] if i + 1 < len(parts)
                 else parts[i]
                 for i in range(0, len(parts), 2)]
    return parts[0]


@dataclass(frozen=True)
class GroupPartial:
    """Mergeable per-shard partial of a dense group-aggregate grid:
    f32 sums + int64 counts per group.  ``merge`` is associative and
    commutative bit-for-bit on integer-valued measures (exact f32
    sums, exact integer counts); ``finalize`` derives sum/count/avg
    AFTER the merge, so avg divides the globally merged sum by the
    globally merged count — exactly what the unsharded computation
    divides.  Empty shards contribute all-zero partials; groups absent
    from a shard contribute zero in that shard only."""
    sums: np.ndarray                    # (G,) f32
    counts: np.ndarray                  # (G,) int64

    @staticmethod
    def from_rows(group_ids, values, n_groups: int) -> "GroupPartial":
        g = np.asarray(group_ids, np.int64)
        v = np.asarray(values, np.float32)
        sums = np.zeros(n_groups, np.float32)
        np.add.at(sums, g, v)
        counts = np.bincount(g, minlength=n_groups).astype(np.int64)
        return GroupPartial(sums, counts)

    def merge(self, other: "GroupPartial") -> "GroupPartial":
        return GroupPartial(self.sums + other.sums,
                            self.counts + other.counts)

    def finalize(self, op: str = "sum") -> np.ndarray:
        if op == "sum":
            return self.sums.copy()
        if op == "count":
            return self.counts.astype(np.float32)
        if op == "avg":
            with np.errstate(invalid="ignore", divide="ignore"):
                out = self.sums / self.counts.astype(np.float32)
            return np.where(self.counts > 0, out,
                            np.float32(0)).astype(np.float32)
        raise ValueError(f"unknown aggregate op {op!r}")


def merge_partials(parts) -> GroupPartial:
    """:func:`tree_merge` over :class:`GroupPartial` shards."""
    parts = list(parts)
    if not parts:
        raise ValueError("merge_partials needs at least one partial")
    while len(parts) > 1:
        parts = [parts[i].merge(parts[i + 1]) if i + 1 < len(parts)
                 else parts[i]
                 for i in range(0, len(parts), 2)]
    return parts[0]


# ---------------------------------------------------------------------------
# stacked streams + replication (the shard_map path's inputs)
# ---------------------------------------------------------------------------


def _place(sdb: ShardedDatabase, stack: np.ndarray) -> jnp.ndarray:
    """An ``(S, L)`` host stack on the device(s): row ``i`` goes straight
    to mesh device ``i`` (``jnp.asarray`` would land the whole stack on
    the first device before ``shard_map`` resharded it)."""
    if sdb.mesh is None:
        return jnp.asarray(stack)
    return jax.device_put(
        stack, NamedSharding(sdb.mesh, PartitionSpec(SHARD_AXIS, None)))


def stacked_stream(sdb: ShardedDatabase, col: str) -> Tuple:
    """``(array, phys, ref)`` of one fact column as the shard_map path
    loads it: an ``(S, L)`` batch whose row ``i`` is shard ``i``'s
    stream padded to ``pad_rows`` — the same triple
    ``storage.column_stream`` yields per shard, stacked.  Packed columns
    re-pack per shard at the PARENT encoding with ``ref``-valued padding
    (encodes to zero lanes; :func:`validity_stream` gates pad rows out
    of every predicate).  Memoized on the ShardedDatabase."""
    hit = sdb._streams.get(col)
    if hit is not None:
        return hit
    table = getattr(sdb.base, sdb.fact)
    enc = ST.encoding_of(table, col)
    vals = np.asarray(table[col])
    npad = sdb.pad_rows
    b = sdb.bounds
    if enc is None or enc.kind == "plain":
        out = np.zeros((sdb.n_shards, npad), np.int32)
        for i in range(sdb.n_shards):
            seg = vals[b[i]:b[i + 1]]
            out[i, :len(seg)] = seg
        entry = (_place(sdb, out), 32, 0)
    else:
        words = []
        for i in range(sdb.n_shards):
            padded = np.full(npad, enc.ref, np.int32)
            seg = vals[b[i]:b[i + 1]]
            padded[:len(seg)] = seg
            words.append(ST.pack_words(padded, enc.width, enc.ref))
        entry = (_place(sdb, np.stack(words)), enc.phys, enc.ref)
    sdb._streams[col] = entry
    return entry


def stacked_window(sdb: ShardedDatabase, col: str, lo: int, hi: int,
                   pad: int) -> Tuple:
    """:func:`stacked_stream` restricted to per-shard rows ``[lo, hi)``
    and padded to ``pad`` — the mesh path's morsel window.  Decodes only
    the window of each shard (``PackedColumn.decode_range``: O(window)
    work and memory however large the fact table is) and is NOT
    memoized: windows are transient by design, the double buffer in
    ``compile._execute_fused_map`` owns their lifetime."""
    table = getattr(sdb.base, sdb.fact)
    enc = ST.encoding_of(table, col)
    b = sdb.bounds

    def window(i: int) -> np.ndarray:
        s = int(b[i]) + lo
        e = min(int(b[i]) + hi, int(b[i + 1]))
        if e <= s:
            return np.zeros(0, np.int32)
        if isinstance(table, ST.PackedTable):
            return table.columns[col].decode_range(s, e)
        return np.asarray(table.columns[col][s:e])

    if enc is None or enc.kind == "plain":
        out = np.zeros((sdb.n_shards, pad), np.int32)
        for i in range(sdb.n_shards):
            seg = window(i)
            out[i, :len(seg)] = seg
        return _place(sdb, out), 32, 0
    words = []
    for i in range(sdb.n_shards):
        padded = np.full(pad, enc.ref, np.int32)
        seg = window(i)
        padded[:len(seg)] = seg
        words.append(ST.pack_words(padded, enc.width, enc.ref))
    return _place(sdb, np.stack(words)), enc.phys, enc.ref


def validity_window(sdb: ShardedDatabase, lo: int, hi: int,
                    pad: int) -> Tuple:
    """The 1/0 real-row mask for per-shard rows ``[lo, hi)`` padded to
    ``pad`` (see :func:`validity_stream`)."""
    v = np.zeros((sdb.n_shards, pad), np.int32)
    for i in range(sdb.n_shards):
        n = int(sdb.bounds[i + 1] - sdb.bounds[i])
        v[i, :max(0, min(hi, n) - lo)] = 1
    return _place(sdb, v), 32, 0


def validity_stream(sdb: ShardedDatabase) -> Tuple:
    """``(S, pad_rows)`` int32 1/0 mask of real vs pad rows, consumed as
    one extra predicate stream with bounds ``(1, 1)`` — the stacked
    layout's row-count raggedness folded into the kernels' existing
    predicate machinery instead of a new masking code path."""
    if sdb._validity is None:
        v = np.zeros((sdb.n_shards, sdb.pad_rows), np.int32)
        for i in range(sdb.n_shards):
            v[i, :int(sdb.bounds[i + 1] - sdb.bounds[i])] = 1
        sdb._validity = (_place(sdb, v), 32, 0)
    return sdb._validity


def replicate(mesh: Mesh, tree):
    """``device_put`` every leaf fully replicated over ``mesh`` — the
    per-device pinning of small shared state (dim hash tables), done
    once per build instead of per launch."""
    sh = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)
