"""Compressed columnar storage: per-column lightweight encodings.

The paper's central claim is that analytic operators are memory-bandwidth
bound (§4) — so once an engine saturates the streaming rate, the only way
left to go faster is to *move fewer bytes*.  Most SSB columns have tiny
domains (``lo_discount`` in [0,11), ``lo_quantity`` in [1,51),
``s_region`` in [0,5)) yet the seed stored and scanned every one as a
full-width int32.  This module packs each column with the cheapest
lossless encoding its statistics allow:

  plain    — raw int32 passthrough (domain needs the full word)
  bitpack  — values packed ``phys`` bits each into int32 words, lanes
             within a word (value k of a word lives at bit ``k*phys``)
  for      — frame-of-reference: ``value - ref`` bit-packed, for offset
             domains (``lo_orderdate`` ∈ [0, 2555) needs 12 bits; a
             column in [10^9, 10^9+100) needs 7)

``phys`` is the *physical* width: the logical width (minimal bits for
the domain) rounded up to a divisor of 32 (1, 2, 4, 8, 16, 32), so
values never span word boundaries and in-kernel decode is ONE logical
shift + ONE mask per tile — the alignment trade every production
bit-packing layout (FastLanes, DuckDB's bit-packing groups) makes.  The
cost model and the bytes-moved benchmark price the *physical* width:
encoded bytes are what actually streams from HBM.

Decode has three consumers, and only the first ever materializes:

  * ``PackedColumn.decode()`` / ``np.asarray`` — the numpy oracle (host
    paths, ``pred_mask``, ``db_fingerprint``); memoized, so repeated
    host access costs one decode.
  * ``column_stream`` — the (words, phys, ref) triple the packed-aware
    kernels (``kernels/ssb_fused``, ``kernels/multi_fused``,
    ``kernels/select_scan``) load per tile and shift/mask-decode in
    registers, never writing the decoded column to HBM.  Every column
    of a packed table, plain ones included, is served from its memoized
    device buffer (``PackedColumn.words_jax``): a resident database
    copies nothing but a query's parameters to the device.
  * ``take`` — positional gather-decode for the operator-at-a-time
    paths: gathers the *words* the row ids touch and decodes in
    registers, so opat/part on a packed database also never stream a
    full-width copy.

Range predicates on packed columns are rewritten into the encoded
domain at lowering time (``encoded_bounds``): the kernels compare the
raw unpacked lanes against ``(lo-ref, hi-ref)``, so filtering needs no
reference correction at all.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import gather_decode
from repro.sql import spans as SP
from repro.sql import ssb

PHYS_WIDTHS = (1, 2, 4, 8, 16, 32)      # divisors of 32: lane-aligned decode
_I32_MIN, _I32_MAX = -(1 << 31), (1 << 31) - 1

# Decode-memo policy: ``PackedColumn.decode()`` pins its full-width
# result only while the decoded column stays under this budget.  Out-of-
# core scale is exactly where the old unconditional memo broke: at SF-1 a
# single ``table[col]`` access (oracle, ``pred_mask``, fingerprinting)
# decoded and pinned 24 MB per column, defeating the morsel bound the
# executor worked for.  Columns over the budget decode on demand (callers
# that stream should use :meth:`PackedColumn.decode_range` instead) and
# :meth:`PackedColumn.release` drops whatever is pinned.
DECODE_MEMO_LIMIT = 1 << 24             # 16 MiB decoded bytes


def set_decode_memo_limit(n_bytes: int) -> int:
    """Set the decode-memo budget; returns the previous value (tests and
    memory-constrained drivers scope it)."""
    global DECODE_MEMO_LIMIT
    prev = DECODE_MEMO_LIMIT
    DECODE_MEMO_LIMIT = int(n_bytes)
    return prev


def phys_width(width: int) -> int:
    """Smallest lane-aligned physical width >= the logical width."""
    if not 1 <= width <= 32:
        raise ValueError(f"width must be in [1, 32], got {width}")
    for p in PHYS_WIDTHS:
        if p >= width:
            return p
    raise AssertionError  # unreachable


@dataclass(frozen=True)
class ColumnEncoding:
    """Per-column encoding descriptor — the single source of the layout
    rule shared by the numpy oracle, the device gather-decode and the
    Pallas kernels."""
    kind: str                   # "plain" | "bitpack" | "for"
    width: int                  # logical bits: minimal for (max - ref)
    phys: int                   # physical bits per value: 1,2,4,8,16,32
    ref: int                    # frame of reference (0 unless kind="for")
    n_rows: int

    @property
    def values_per_word(self) -> int:
        return 32 // self.phys

    @property
    def bytes_per_row(self) -> float:
        """Encoded bytes per value as streamed — what the cost model
        prices (4.0 for plain)."""
        return self.phys / 8.0

    @property
    def nbytes(self) -> int:
        """Total encoded bytes of the stored column."""
        if self.kind == "plain":
            return 4 * self.n_rows
        c = self.values_per_word
        return 4 * ((self.n_rows + c - 1) // c)


def bits_for(span: int) -> int:
    """Minimal width that represents values in [0, span]."""
    return max(int(span).bit_length(), 1)


def encoding_from_stats(vmin: int, vmax: int, n: int) -> ColumnEncoding:
    """Pick the cheapest encoding from min/max statistics alone.
    Prefers ``bitpack`` (ref=0, one op less per decode) whenever the
    zero-referenced width lands on the same physical width as the
    frame-of-reference one; falls back to ``plain`` when packing would
    not shrink the column (phys == 32).  Split out of
    :func:`choose_encoding` so the streaming generator
    (``ssb.generate_packed``) can pick encodings from a stats-only first
    pass without ever holding a full column."""
    if n == 0:
        return ColumnEncoding("plain", 32, 32, 0, 0)
    vmin, vmax = int(vmin), int(vmax)
    w_for = bits_for(vmax - vmin)
    if phys_width(w_for) >= 32:
        return ColumnEncoding("plain", 32, 32, 0, n)
    if vmin >= 0 and phys_width(bits_for(vmax)) == phys_width(w_for):
        w = bits_for(vmax)
        return ColumnEncoding("bitpack", w, phys_width(w), 0, n)
    return ColumnEncoding("for", w_for, phys_width(w_for), vmin, n)


def choose_encoding(values: np.ndarray) -> ColumnEncoding:
    """Pick the cheapest encoding for a materialized column (min/max
    statistics via :func:`encoding_from_stats`)."""
    n = len(values)
    if n == 0:
        return ColumnEncoding("plain", 32, 32, 0, 0)
    return encoding_from_stats(int(values.min()), int(values.max()), n)


# ---------------------------------------------------------------------------
# encode / decode (numpy oracle)
# ---------------------------------------------------------------------------


def pack_words(values: np.ndarray, width: int, ref: int = 0) -> np.ndarray:
    """Pack ``values - ref`` into int32 words, ``phys_width(width)`` bits
    per value, lane k of a word at bit ``k*phys``.  Values must satisfy
    ``0 <= v - ref < 2**width``; the packed array is the int32 view of
    the uint32 word stream (everything downstream shifts logically)."""
    enc = np.asarray(values).astype(np.int64) - int(ref)
    if enc.size and (enc.min() < 0 or enc.max() >= (1 << width)):
        raise ValueError(
            f"values out of range for width={width} ref={ref}: "
            f"[{int(enc.min()) + ref}, {int(enc.max()) + ref}]")
    phys = phys_width(width)
    if phys == 32:
        return enc.astype(np.uint32).view(np.int32)
    c = 32 // phys
    pad = (-len(enc)) % c
    enc = np.pad(enc, (0, pad)).astype(np.uint32).reshape(-1, c)
    shifts = (np.arange(c, dtype=np.uint32) * phys).astype(np.uint32)
    return np.bitwise_or.reduce(enc << shifts[None, :], axis=1).view(np.int32)


def unpack_words(words: np.ndarray, n: int, width: int,
                 ref: int = 0) -> np.ndarray:
    """Numpy decode oracle: exact inverse of :func:`pack_words` for the
    first ``n`` values."""
    phys = phys_width(width)
    w = np.asarray(words).view(np.uint32)
    if phys == 32:
        vals = w.astype(np.int64)
        if width < 32:          # width<32 values are stored zero-extended
            vals &= (1 << width) - 1
    else:
        c = 32 // phys
        shifts = (np.arange(c, dtype=np.uint32) * phys).astype(np.uint32)
        vals = ((w[:, None] >> shifts[None, :])
                & np.uint32((1 << phys) - 1)).reshape(-1).astype(np.int64)
    return (vals[:n] + int(ref)).astype(np.int32)


# ---------------------------------------------------------------------------
# packed tables
# ---------------------------------------------------------------------------


@dataclass
class PackedColumn:
    """One encoded column.  ``np.asarray(col)`` (and ``decode()``) yields
    the original int32 values — host/oracle paths stay transparent —
    while ``words_jax()`` serves the packed device stream the kernels
    consume."""
    encoding: ColumnEncoding
    words: np.ndarray                   # packed stream (plain: raw data)
    _decoded: Optional[np.ndarray] = field(default=None, repr=False)
    _words_jax: Optional[jnp.ndarray] = field(default=None, repr=False)

    def decode(self) -> np.ndarray:
        if self.encoding.kind == "plain":
            return self.words
        if self._decoded is not None:
            return self._decoded
        e = self.encoding
        out = unpack_words(self.words, e.n_rows, e.width, e.ref)
        # Memoize only while the decoded column fits the budget: pinning
        # a 24 MB decode per column at SF-1 would defeat the out-of-core
        # bound the morsel executor maintains.  Streaming callers should
        # prefer :meth:`decode_range`.
        if 4 * e.n_rows <= DECODE_MEMO_LIMIT:
            self._decoded = out
        return out

    def decode_range(self, lo: int, hi: int) -> np.ndarray:
        """Decode rows ``[lo, hi)`` touching only the word window that
        holds them — the per-morsel decode for oracle/``pred_mask``
        paths, O(hi - lo) regardless of column length."""
        if self.encoding.kind == "plain":
            return self.words[lo:hi]
        if self._decoded is not None:
            return self._decoded[lo:hi]
        e = self.encoding
        c = e.values_per_word
        w0, w1 = lo // c, (hi + c - 1) // c
        vals = unpack_words(self.words[w0:w1], (w1 - w0) * c, e.width,
                            e.ref)
        return vals[lo - w0 * c: hi - w0 * c]

    def release(self, device: bool = False) -> None:
        """Drop the pinned full-column decode (and, with ``device=True``,
        the uploaded word stream) — the explicit end of the bounded-cache
        policy for callers that know a column is done."""
        self._decoded = None
        if device:
            self._words_jax = None

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        """Full numpy conversion protocol: dtype- and copy-aware
        callers (``np.asarray(col, np.int64)``, NumPy 2's
        ``np.array(col, copy=False)``) must not crash on the memoized
        decode."""
        arr = self.decode()
        if dtype is not None and arr.dtype != np.dtype(dtype):
            return arr.astype(dtype)
        if copy:
            return arr.copy()
        return arr

    def __len__(self) -> int:
        return self.encoding.n_rows

    @property
    def resident(self) -> bool:
        """Whether the word stream is on the device (``words_jax`` then
        copies nothing)."""
        return self._words_jax is not None

    def words_jax(self) -> jnp.ndarray:
        """The packed word stream (a plain column's int32 values) as a
        device array, memoized so a resident database uploads each
        column once."""
        if self._words_jax is None:
            self._words_jax = upload(self.words)
        return self._words_jax


@dataclass
class PackedTable:
    """Drop-in ``ssb.Table`` replacement: ``table[col]`` returns decoded
    numpy (host paths and the oracle never notice), the packed-aware
    lowering asks :func:`column_stream` / :func:`encoding_of` instead."""
    name: str
    columns: Dict[str, PackedColumn]

    def __getitem__(self, col: str) -> np.ndarray:
        return self.columns[col].decode()

    @property
    def n_rows(self) -> int:
        return len(next(iter(self.columns.values())))

    def encoding(self, col: str) -> ColumnEncoding:
        return self.columns[col].encoding

    @property
    def nbytes(self) -> int:
        return sum(c.encoding.nbytes for c in self.columns.values())

    @property
    def plain_nbytes(self) -> int:
        return sum(4 * c.encoding.n_rows for c in self.columns.values())

    def release(self, device: bool = False) -> None:
        """Release every column's pinned decode (see
        :meth:`PackedColumn.release`); recurses into delta batches."""
        for col in self.columns.values():
            col.release(device=device)
        for batch in delta_batches(self):
            batch.release(device=device)


def pack_column(values: np.ndarray,
                enc: Optional[ColumnEncoding] = None) -> PackedColumn:
    values = np.asarray(values, np.int32)
    enc = choose_encoding(values) if enc is None else enc
    if enc.kind == "plain":
        return PackedColumn(enc, values)
    return PackedColumn(enc, pack_words(values, enc.width, enc.ref))


def slice_rows(table, lo: int, hi: int):
    """Row-range copy ``[lo, hi)`` of a table — the fact-table shard and
    morsel cut (``repro.sql.shard``, ``repro.sql.morsel``).  Plain tables
    slice each column (numpy views: a shard of a plain database shares
    its parent's buffers); packed columns keep the PARENT encoding (same
    kind/width/ref), so predicate rewrites, stream widths and frames of
    reference computed against the parent table stay valid on every cut.

    When ``lo`` lands on an int32-word boundary of a column (``lo %
    values_per_word == 0`` — every morsel cut, since morsels are LANE-
    aligned and LANE is a multiple of all ``values_per_word``), the
    packed slice is a pure word-window VIEW: zero decode, zero re-pack.
    The window's final word may carry trailing lanes of the parent's next
    rows; that is safe everywhere packed streams flow — kernels mask rows
    ``>= n_rows`` (``valid_mask``) and the ref path slices ``[:n]`` after
    decode.  Unaligned cuts fall back to a range decode + re-pack under
    the parent encoding."""
    if isinstance(table, PackedTable):
        cols = {}
        for name, col in table.columns.items():
            enc = replace(col.encoding, n_rows=hi - lo)
            if enc.kind == "plain":
                cols[name] = PackedColumn(enc, col.words[lo:hi])
                continue
            c = enc.values_per_word
            if lo % c == 0:
                cols[name] = PackedColumn(
                    enc, col.words[lo // c:(hi + c - 1) // c])
            else:
                cols[name] = pack_column(col.decode_range(lo, hi), enc)
        return PackedTable(table.name, cols)
    return ssb.Table(table.name, {c: v[lo:hi]
                                  for c, v in table.columns.items()})


def pack_table(table: ssb.Table) -> PackedTable:
    return PackedTable(table.name, {c: pack_column(v)
                                    for c, v in table.columns.items()})


def pack_database(db: ssb.Database) -> ssb.Database:
    """Encode every table of a Database; the result serves every entry
    point — oracle, all four solo strategies, shared waves, the query
    server — transparently (``db_fingerprint`` of a packed database
    equals its plain original's, so a warmed ``HashTableCache`` carries
    over a plain->packed reload)."""
    return ssb.Database(
        lineorder=pack_table(db.lineorder), date=pack_table(db.date),
        supplier=pack_table(db.supplier), customer=pack_table(db.customer),
        part=pack_table(db.part), sf=db.sf)


# ---------------------------------------------------------------------------
# lowering helpers (what the compiler / cost model ask)
# ---------------------------------------------------------------------------


def encoding_of(table, col: str) -> Optional[ColumnEncoding]:
    """The column's encoding, or None for an un-packed table (plain
    ``ssb.Table``) — the "is this packed?" question in one place."""
    if isinstance(table, PackedTable):
        return table.encoding(col)
    return None


def upload(x) -> jnp.ndarray:
    """``jnp.asarray(x)``, the one host-to-device copy of the query path:
    under a ``sql.upload`` span, counted in ``LAUNCH_STATS``' ``uploads``
    and ``upload_bytes``.  A device array passes through uncounted.  The
    span ends when the copy is issued: a transfer that completes later
    is waited for by whoever reads its result."""
    if isinstance(x, jax.Array):
        return x
    with SP.span(SP.UPLOAD):
        out = jnp.asarray(x)
    SP.LAUNCH_STATS["uploads"] += 1
    SP.LAUNCH_STATS["upload_bytes"] += out.nbytes
    return out


def column_stream(table, col: str) -> Tuple[jnp.ndarray, int, int]:
    """``(array, phys, ref)`` as the kernels load it.  A column of a
    packed table comes from its memoized device buffer
    (``PackedColumn.words_jax``; a plain column's words are its int32
    values, phys=32, ref=0), uploaded on the first call only; a plain
    ``ssb.Table`` has no buffer to keep, and uploads its int32 column
    on every call.  Counted in ``LAUNCH_STATS``: ``streams`` every call,
    ``streams_resident`` those served by a buffer already on the
    device."""
    SP.LAUNCH_STATS["streams"] += 1
    enc = encoding_of(table, col)
    if enc is None:
        return upload(table[col]), 32, 0
    pc = table.columns[col]
    SP.LAUNCH_STATS["streams_resident"] += pc.resident
    return pc.words_jax(), enc.phys, enc.ref


def take(table, col: str, rowids: jnp.ndarray) -> jnp.ndarray:
    """Positional column access for the materializing (opat/part) paths:
    plain gather on a plain table, word-gather + register decode on a
    packed one — either way only the touched positions move."""
    arr, phys, ref = column_stream(table, col)
    if phys == 32:
        return arr[rowids]
    return gather_decode(arr, rowids, phys, ref)


def encoded_bounds(enc: Optional[ColumnEncoding], lo: int,
                   hi: int) -> Tuple[int, int]:
    """Rewrite a closed range predicate into the encoded domain (the
    compile-time rewrite): packed lanes are compared raw, so the bounds
    absorb the reference.  Clamped to int32 — encoded values are
    non-negative, so a clamped lower bound stays all-pass-correct."""
    if enc is None or enc.kind == "plain":
        return lo, hi
    lo2 = max(_I32_MIN, min(_I32_MAX, int(lo) - enc.ref))
    hi2 = max(_I32_MIN, min(_I32_MAX, int(hi) - enc.ref))
    return lo2, hi2


def scan_bytes_per_row(table, col: str) -> float:
    """Bytes one streamed pass moves per row of this column — the
    encoded width for packed columns, the paper's nominal 4 otherwise.
    The cost model's per-column replacement for the flat ``W``."""
    enc = encoding_of(table, col)
    return 4.0 if enc is None else enc.bytes_per_row


def sample_column(table, col: str, stride: int) -> np.ndarray:
    """Every ``stride``-th value of a column without materializing a
    full decode: a strided word gather + lane shift on packed columns
    (O(n/stride) work and memory), a plain strided view otherwise — the
    selectivity estimator's probe (``sql.model``), which previously
    full-decoded SF-1 columns just to look at 1/64th of the rows."""
    stride = max(1, int(stride))
    if isinstance(table, PackedTable):
        pc = table.columns[col]
        e = pc.encoding
        if e.kind != "plain" and pc._decoded is None:
            idx = np.arange(0, e.n_rows, stride, dtype=np.int64)
            w = pc.words.view(np.uint32)[idx // e.values_per_word]
            sh = ((idx % e.values_per_word) * e.phys).astype(np.uint32)
            vals = ((w >> sh)
                    & np.uint32((1 << e.phys) - 1)).astype(np.int64)
            return (vals + e.ref).astype(np.int32)
    return np.asarray(table[col])[::stride]


# ---------------------------------------------------------------------------
# append-only delta batches (ingest under load)
# ---------------------------------------------------------------------------
#
# A table accepts appended row batches without repacking its base
# columns: each batch is packed immediately (under the parent encoding
# when the new values fit its domain — same kernel trace, predicate
# rewrites stay valid — or fresh statistics otherwise) and stashed on
# the table.  The morsel iterator (``repro.sql.morsel``) appends delta
# batches after the base rows at scan time, so queries observe ingested
# rows with no flush; ``flush_deltas`` is the explicit compaction that
# folds them back into one freshly-encoded table.


def append_rows(table, rows: Dict[str, np.ndarray]):
    """Append one delta batch (full row set, dict of column arrays) to a
    table; returns the packed batch table."""
    if set(rows) != set(table.columns):
        raise ValueError(
            f"delta batch columns {sorted(rows)} != table columns "
            f"{sorted(table.columns)}")
    lens = {len(np.asarray(v)) for v in rows.values()}
    if len(lens) != 1:
        raise ValueError(f"ragged delta batch: column lengths {lens}")
    n_new = lens.pop()
    # stage-then-publish: every column is packed into ``batch`` before
    # the single mutation below appends it — a failure anywhere in this
    # loop (including an injected ingest fault) leaves ``_deltas``
    # exactly as it was, never a half-ingested batch
    from repro.sql import faults
    if isinstance(table, PackedTable):
        cols = {}
        for name, col in table.columns.items():
            faults.maybe_fault("ingest")
            vals = np.asarray(rows[name], np.int32)
            enc = replace(col.encoding, n_rows=n_new)
            try:
                cols[name] = pack_column(vals, enc)
            except ValueError:
                # outside the parent's domain: encode from the batch's
                # own stats (costs a retrace for this batch's scans)
                cols[name] = pack_column(vals)
        batch = PackedTable(table.name, cols)
    else:
        cols = {}
        for name in table.columns:
            faults.maybe_fault("ingest")
            cols[name] = np.asarray(rows[name], np.int32)
        batch = ssb.Table(table.name, cols)
    pending = getattr(table, "_deltas", None)
    if pending is None:
        pending = []
        table._deltas = pending
    pending.append(batch)
    return batch


def delta_batches(table) -> list:
    """The pending delta batches of a table (empty list if none)."""
    return list(getattr(table, "_deltas", ()))


def delta_rows(table) -> int:
    """Total appended-but-unflushed rows."""
    return sum(b.n_rows for b in delta_batches(table))


def flush_deltas(table):
    """Compact base + deltas into one fresh table (re-encoded from the
    merged statistics).  Returns ``table`` itself when nothing is
    pending; the result carries no deltas."""
    pending = delta_batches(table)
    if not pending:
        return table
    # the whole compaction stages into fresh columns; ``table`` (and its
    # ``_deltas``) is never mutated, so a mid-flush failure — real or
    # injected — leaves the source observable state untouched and the
    # flush can simply be retried
    from repro.sql import faults
    merged = {}
    for c in table.columns:
        faults.maybe_fault("ingest")
        merged[c] = np.concatenate(
            [np.asarray(table[c])] + [np.asarray(b[c]) for b in pending])
    if isinstance(table, PackedTable):
        return PackedTable(table.name,
                           {c: pack_column(v) for c, v in merged.items()})
    return ssb.Table(table.name, merged)
