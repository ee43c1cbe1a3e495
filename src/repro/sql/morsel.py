"""MorselStream: bounded-memory iteration over the fact table.

The paper's thesis — analytic scans are memory-bandwidth bound — only
bites once the working set stresses the memory system, and the exemplar
systems it measures (SF-1+, 6M+ fact rows) cannot assume the whole fact
table is one device-resident array.  This module deletes that
assumption: the fact table is cut into fixed-byte-budget **morsels**
(row ranges re-sliced via ``storage.slice_rows``), every executor in
``sql.compile`` becomes a fold over the stream with incremental
``GroupPartial`` merge, and uploads are **double-buffered** — morsel
N+1's ``device_put`` is issued while morsel N computes — so the
device-resident fact footprint is bounded by ``2 × morsel_bytes``
regardless of scale factor.

Cut geometry
------------
Morsel boundaries are multiples of ``LANE`` (32) rows.  32 is a common
multiple of every packed column's ``values_per_word`` (32/phys for phys
in {1,2,4,8,16,32}), so every cut lands on an int32-word boundary of
every column and ``slice_rows`` serves each packed morsel as a pure
word-window view — zero decode, zero re-pack (the trailing lanes of a
window's last word may hold the parent's next rows; kernels mask rows
``>= n_rows`` and the ref path slices ``[:n]``, so they are never
observed).  The target rows per morsel come from the byte budget over
the table's *encoded* bytes-per-row, floored at one lane so a tiny
budget still makes progress.

Delta batches
-------------
Append-only ingest batches (``storage.append_rows``) are spliced into
the stream after the base rows, each batch cut by the same geometry —
queries observe ingested rows with no flush and no repack of the base.

Accounting
----------
``MorselReport`` carries what the server surfaces per query:
``n_morsels`` and ``peak_resident_bytes`` — the maximum encoded bytes
of any two adjacent morsels' *scanned columns* (the double-buffer
invariant: while morsel N computes, only N and N+1 are device-resident).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Iterable, Iterator, List, Optional, Tuple

from repro.sql import storage as ST

# Morsel cuts land on multiples of LANE rows: one int32-word boundary of
# every packed width (lcm of 32/phys for phys in PHYS_WIDTHS).
LANE = 32

# Default per-morsel budget.  64 MiB keeps every current test/benchmark
# database (SF <= 1: packed fact ~30 MB) single-morsel, so the refactor
# is behaviour-preserving until a caller asks for a bound.
DEFAULT_MORSEL_BYTES = 64 << 20


def rows_per_morsel(bytes_per_row: float, morsel_bytes: int) -> int:
    """LANE-aligned row count whose encoded footprint fits the budget
    (floored at one lane: a sub-lane budget still makes progress, it
    just overshoots to 32 rows)."""
    if bytes_per_row <= 0:
        return LANE
    rows = int(morsel_bytes // bytes_per_row)
    return max(LANE, (rows // LANE) * LANE)


def plan_cuts(n_rows: int, rows_per: int) -> List[Tuple[int, int]]:
    """The ``[lo, hi)`` row ranges covering ``[0, n_rows)`` in
    ``rows_per``-row steps (the tail morsel is shorter; an empty table
    yields no cuts)."""
    return [(lo, min(lo + rows_per, n_rows))
            for lo in range(0, n_rows, rows_per)]


@dataclass(frozen=True)
class Morsel:
    """One fact-table cut: a table of ``hi - lo`` rows plus where it
    came from (``base`` rows are offset ``lo`` of the base table; delta
    morsels carry their batch index)."""
    table: object                # sliced Table / PackedTable
    lo: int                      # row range within its source
    hi: int
    source: str = "base"         # "base" | "delta"
    batch: int = -1              # delta batch index ("delta" only)
    offset: int = 0              # global row index of row ``lo`` in the
    #   base+deltas concatenation (row-plan folds offset their
    #   morsel-local survivor ids by this)

    @property
    def n_rows(self) -> int:
        return self.hi - self.lo


@dataclass
class MorselReport:
    """Per-query out-of-core accounting (mutated by the fold)."""
    n_morsels: int = 0
    peak_resident_bytes: int = 0

    def observe(self, resident_bytes: int) -> None:
        self.n_morsels += 1
        if resident_bytes > self.peak_resident_bytes:
            self.peak_resident_bytes = resident_bytes

    def merge(self, other: "MorselReport") -> "MorselReport":
        """Combine accounting across independently-folded streams (the
        per-shard composition): morsels add, peaks take the max —
        shards on distinct devices each hold their own double buffer."""
        return MorselReport(
            n_morsels=self.n_morsels + other.n_morsels,
            peak_resident_bytes=max(self.peak_resident_bytes,
                                    other.peak_resident_bytes))


def scanned_morsel_bytes(table, cols: Optional[Iterable[str]]) -> int:
    """Encoded bytes of the columns a query actually streams from one
    morsel (all columns when ``cols`` is None)."""
    if isinstance(table, ST.PackedTable):
        if cols is None:
            return table.nbytes
        return sum(table.encoding(c).nbytes for c in cols)
    names = table.columns if cols is None else cols
    return sum(4 * len(table.columns[c]) for c in names)


class MorselStream:
    """The bounded-memory scan spine: cuts a fact table (base rows plus
    any pending delta batches) into LANE-aligned morsels under a byte
    budget and drives the double-buffered fold every executor uses.

    ``n_morsels == 1`` is the degenerate in-memory case — the single
    morsel IS the table (no slice, no copy), so small databases take
    exactly the pre-refactor path.
    """

    def __init__(self, table, morsel_bytes: int = DEFAULT_MORSEL_BYTES,
                 cols: Optional[Iterable[str]] = None):
        self.table = table
        self.morsel_bytes = int(morsel_bytes)
        self.cols = list(cols) if cols is not None else None
        bpr = self._bytes_per_row(table)
        self.rows_per = rows_per_morsel(bpr, self.morsel_bytes)
        self.deltas = ST.delta_batches(table)
        self._items: List[Tuple[object, int, int, str, int, int]] = []
        for lo, hi in plan_cuts(table.n_rows, self.rows_per):
            self._items.append((table, lo, hi, "base", -1, lo))
        off = table.n_rows
        for bi, batch in enumerate(self.deltas):
            for lo, hi in plan_cuts(batch.n_rows, self.rows_per):
                self._items.append((batch, lo, hi, "delta", bi, off + lo))
            off += batch.n_rows

    def _bytes_per_row(self, table) -> float:
        if isinstance(table, ST.PackedTable):
            names = self.cols if self.cols is not None else table.columns
            return sum(table.encoding(c).bytes_per_row for c in names)
        names = self.cols if self.cols is not None else table.columns
        return 4.0 * len(list(names))

    @property
    def n_morsels(self) -> int:
        return len(self._items)

    @property
    def total_rows(self) -> int:
        return self.table.n_rows + sum(b.n_rows for b in self.deltas)

    def morsel_nbytes(self, i: int) -> int:
        """Encoded bytes of the scanned columns of morsel ``i`` (exact
        per-cut math, no slicing needed)."""
        src, lo, hi, _, _, _ = self._items[i]
        if isinstance(src, ST.PackedTable):
            names = (self.cols if self.cols is not None
                     else list(src.columns))
            total = 0
            for c in names:
                e = src.encoding(c)
                if e.kind == "plain":
                    total += 4 * (hi - lo)
                else:
                    vw = e.values_per_word
                    total += 4 * ((hi + vw - 1) // vw - lo // vw)
            return total
        names = self.cols if self.cols is not None else src.columns
        return 4 * len(list(names)) * (hi - lo)

    def peak_resident_bytes(self) -> int:
        """The double-buffer bound: the largest encoded footprint of any
        two adjacent morsels (just the largest single morsel when the
        stream has one)."""
        sizes = [self.morsel_nbytes(i) for i in range(self.n_morsels)]
        if not sizes:
            return 0
        if len(sizes) == 1:
            return sizes[0]
        return max(a + b for a, b in zip(sizes, sizes[1:]))

    def morsels(self) -> Iterator[Morsel]:
        """Materialize each cut lazily.  A single-item stream of the
        whole base table yields the table itself (identity — the
        in-memory fast path keeps its resident column uploads)."""
        for src, lo, hi, kind, bi, off in self._items:
            if lo == 0 and hi == src.n_rows:
                yield Morsel(src, lo, hi, kind, bi, off)
            else:
                yield Morsel(ST.slice_rows(src, lo, hi), lo, hi, kind, bi,
                             off)

    def fold(self, compute: Callable[[Morsel], object],
             report: Optional[MorselReport] = None) -> List[object]:
        """Run ``compute`` over every morsel with double-buffered
        uploads: morsel N+1's device transfer (``device_put`` of its
        scanned column streams) is issued asynchronously while morsel N
        computes, so copy and compute overlap and at most two morsels
        are device-resident.  Returns the per-morsel results in stream
        order; ``report`` (if given) accumulates n_morsels and the
        residency peak."""
        results: List[object] = []
        it = self.morsels()
        cur = next(it, None)
        i = 0
        while cur is not None:
            nxt = next(it, None)
            try:
                if nxt is not None:
                    self._prefetch(nxt)
                if report is not None:
                    resident = self.morsel_nbytes(i)
                    if nxt is not None:
                        resident += self.morsel_nbytes(i + 1)
                    report.observe(resident)
                results.append(compute(cur))
            except Exception:
                # exception-safe teardown: a fault at morsel k must not
                # leave either in-flight double buffer device-resident
                self._release(cur, keep=None)
                if nxt is not None:
                    self._release(nxt, keep=None)
                raise
            self._release(cur, keep=nxt)
            cur, i = nxt, i + 1
        return results

    def _prefetch(self, m: Morsel) -> None:
        """Issue the async host→device copy of the next morsel's scanned
        columns (``storage.upload``; jax transfers are asynchronous: the
        call returns once the copy is issued and overlaps with the
        in-flight compute)."""
        from repro.sql import faults
        faults.maybe_fault("upload")
        table = m.table
        names = (self.cols if self.cols is not None
                 else list(table.columns))
        if isinstance(table, ST.PackedTable):
            for c in names:
                table.columns[c].words_jax()
        else:
            # plain tables upload inside the executor's column_stream;
            # issue the same transfers early
            for c in names:
                ST.upload(table.columns[c])

    def _release(self, m: Morsel, keep: Optional[Morsel]) -> None:
        """Drop a finished morsel's device buffers and decode memos —
        unless the morsel IS the base table (single-morsel identity
        path: resident uploads are the point of the memo)."""
        if m.table is self.table or (keep is not None
                                     and m.table is keep.table):
            return
        if isinstance(m.table, ST.PackedTable):
            m.table.release(device=True)
