"""Public jit'd entry points for the Crystal kernels.

Each op dispatches between its Pallas kernel and its jnp path, which XLA
compiles for whatever backend runs it.  The SQL engine (repro/sql) calls
these; ``mode`` is usually left as "auto":

  auto   -> the jnp path, except on a TPU for the ops in ``TPU_KERNELS``
  kernel -> force Pallas (compiled on a TPU, interpreted elsewhere) —
            what the kernel tests exercise and what A/B runs compare
  ref    -> force the jnp path
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import agg as _agg
from repro.kernels import hash_join as _hj
from repro.kernels import part_probe as _pp
from repro.kernels import project as _proj
from repro.kernels import radix_part as _radix
from repro.kernels import ref as _ref
from repro.kernels import select_scan as _sel
from repro.kernels import unpack as _unp
from repro.kernels.common import DEFAULT_TILE, gather_decode

#: The op families that dispatch here (the ``op`` names below).
OPS = ("select_scan", "unpack", "project", "build_hash_table", "probe_agg",
       "probe_join", "part_probe", "radix_sort", "radix_partition",
       "reduce_sum", "group_sum", "multi_spja", "spja")

#: Ops whose Pallas kernel ``mode="auto"`` runs on a TPU.  An op joins
#: only once its kernel compiles for the chip and a chip run shows it
#: beating the op's XLA path; until then the chip runs the jnp path.
#: Empty: no kernel has met that bar yet (several do not lower at all —
#: scatter-add, cumsum and ``pl.ANY`` loads are refused by the TPU
#: compiler).
TPU_KERNELS: frozenset = frozenset()


def use_kernel(op: str, mode: str) -> bool:
    """Whether ``op`` runs its Pallas kernel under ``mode``."""
    if mode == "kernel":
        return True
    if mode == "ref":
        return False
    return op in TPU_KERNELS and jax.default_backend() == "tpu"


def impl(op: str, mode: str) -> str:
    """``"pallas"`` or ``"xla"``: the implementation ``op`` runs."""
    return "pallas" if use_kernel(op, mode) else "xla"


def select_scan(x, y, lo, hi, mode: str = "auto", tile: int = DEFAULT_TILE):
    if use_kernel("select_scan", mode):
        out, cnt = _sel.select_scan(x, y, lo, hi, tile=tile)
        return out[:x.shape[0]], cnt
    return _ref.select_scan(x, y, lo, hi)


# ---------------------------------------------------------------------------
# compressed-storage decode primitives (layout: repro.sql.storage)
# ---------------------------------------------------------------------------


_unpack_ref_jit = functools.partial(
    jax.jit, static_argnames=("n", "phys"))(_ref.unpack)


def unpack(words, n: int, phys: int, ref=0, mode: str = "auto",
           tile: int = DEFAULT_TILE):
    """Materializing bit-unpack: ``(n_words,)`` packed int32 words at
    ``phys`` bits/value -> first ``n`` decoded int32 values (+ ref).
    The hot scan paths decode in-kernel instead; this is the standalone
    primitive (host paths, tests, the in-register decode's oracle)."""
    if phys == 32:
        return words[:n] + jnp.int32(ref)
    if use_kernel("unpack", mode):
        return _unp.unpack(words, jnp.int32(ref), phys, tile=tile)[:n]
    return _unpack_ref_jit(words, n, phys, jnp.int32(ref))


@functools.partial(jax.jit, static_argnames=("phys",))
def _select_packed_ref_jit(words, y, lo, hi, *, phys):
    x = _decode_stream(words, phys, 0, y.shape[0])
    return _ref.select_scan(x, y, lo, hi)


def select_scan_packed(words, y, lo, hi, phys: int, mode: str = "auto",
                       tile: int = DEFAULT_TILE):
    """``select_scan`` over a bit-packed predicate column: the word
    stream decodes per tile in registers, and ``(lo, hi)`` are already
    rewritten into the encoded domain (``storage.encoded_bounds``) so
    filtering needs no reference correction at all."""
    if phys == 32:
        return select_scan(words, y, lo, hi, mode=mode, tile=tile)
    if use_kernel("select_scan", mode):
        out, cnt = _sel.select_scan_packed(words, y, lo, hi, phys,
                                           tile=tile)
        return out[:y.shape[0]], cnt
    return _select_packed_ref_jit(words, y, lo, hi, phys=phys)


def _decode_stream(arr, width: int, ref, n: int):
    """XLA-path stream normalizer: identity for plain streams, in-trace
    decode of the first ``n`` values (fused by XLA with the consuming
    scan, never materialized between ops) for packed ones.  Positional
    (``gather_decode`` of ``0..n-1``), not ``decode_words``: the TPU
    compiler lays the latter's ``(n_words, 32 // width)`` intermediate
    out per width, and streams of mixed widths then relayout through
    padded temporaries (GBs per 4M-row block, minutes of compile)."""
    if width == 32:
        return arr
    return gather_decode(arr, jnp.arange(n, dtype=jnp.int32), width, ref)


def project(x1, x2, a, b, sigmoid=False, mode: str = "auto",
            tile: int = DEFAULT_TILE):
    if use_kernel("project", mode):
        return _proj.project(x1, x2, a, b, sigmoid=sigmoid, tile=tile)
    return _ref.project(x1, x2, a, b, sigmoid=sigmoid)


def build_hash_table(keys, vals, n_slots, mode: str = "auto",
                     tile: int = DEFAULT_TILE):
    if use_kernel("build_hash_table", mode):
        return _hj.build(keys, vals, n_slots, tile=tile)
    return _ref.build(keys, vals, n_slots)


def probe_agg(keys, vals, ht_keys, ht_vals, mode: str = "auto",
              tile: int = DEFAULT_TILE):
    if use_kernel("probe_agg", mode):
        return _hj.probe_agg(keys, vals, ht_keys, ht_vals, tile=tile)
    return _ref.probe_agg(keys, vals, ht_keys, ht_vals)


def probe_join(keys, vals, ht_keys, ht_vals, mode: str = "auto",
               tile: int = DEFAULT_TILE):
    if use_kernel("probe_join", mode):
        outp, outv, cnt = _hj.probe_join(keys, vals, ht_keys, ht_vals,
                                         tile=tile)
        return outp[:keys.shape[0]], outv[:keys.shape[0]], cnt
    return _ref.probe_join(keys, vals, ht_keys, ht_vals)


# the ref path's probe while_loop must run under jit (eagerly it
# dispatches every probe iteration — the overhead the fused kernel
# exists to kill); one cached executable per (shape, layout) combination
_part_probe_ref_jit = jax.jit(_ref.part_probe)


def part_probe(keys, rowids, groups, offs, counts, htk, htv, mult,
               mode: str = "auto", tile: int = DEFAULT_TILE):
    """Single-launch partitioned probe: flat partition-major probe side
    (keys + rowid/group payloads), per-partition (offs, counts), packed
    (P, S) hash tables.  Returns stable partition-major
    (out_rowids, out_groups(+payload*mult), count).  Rows with a
    negative rowid are dead (pad) rows and never match.

    The probe side is pow2-padded here so XLA compiles O(log n) probe
    shapes across queries instead of one per cardinality (pad rows sit
    beyond every partition's run and are masked by the counts)."""
    n = keys.shape[0]
    if n == 0:
        z = jnp.zeros((0,), jnp.int32)
        return z, z, jnp.int32(0)
    n_pad = 1 << max((n - 1).bit_length(), 0)
    keys = jnp.pad(keys, (0, n_pad - n))
    rowids = jnp.pad(rowids, (0, n_pad - n), constant_values=-1)
    groups = jnp.pad(groups, (0, n_pad - n))
    mult = jnp.asarray(mult, jnp.int32)
    if use_kernel("part_probe", mode):
        outr, outg, cnt = _pp.part_probe(keys, rowids, groups, offs,
                                         counts, htk, htv, mult, tile=tile)
        return outr, outg, cnt
    return _part_probe_ref_jit(keys, rowids, groups, offs, counts,
                               htk, htv, mult)


_LSB_IDX_BITS = 22          # probe sides up to 2^22 rows ride one int32


def _lsb_partition_multi(keys, vals, bits: int, digit: int = 1):
    """Stable low-bit shuffle for the jitted host path: LSD passes of
    ``digit`` bits each over a single packed (bucket << idx_bits |
    position) int32 — a counting sort of 2^digit buckets (one cumsum per
    bucket) + one scatter per pass, then one gather per column.
    Equivalent to ``ref.partition_multi(..., start_bit=0)`` for every
    digit width (tested against it) but ~4x faster than XLA's stable
    sort on CPU — the shuffle is the shared cost of every partitioned
    join, so it decides how much of the fused kernel's dispatch win
    survives end to end.

    ``digit`` trades cumsums for scatters: a d-bit pass costs 2^d
    cumsums but covers d bits with ONE scatter, so wider digits halve
    the scatter traffic.  The empirical winner is hardware-specific
    (scatter-vs-scan throughput), which is why ``repro.sql.tune`` sweeps
    it; ``digit=1`` is byte-for-byte the pre-tuner pass sequence."""
    n = keys.shape[0]
    if n > (1 << _LSB_IDX_BITS):        # fall back to the sort-based oracle
        return _ref.partition_multi(keys, vals, 0, bits)
    iota = jnp.arange(n, dtype=jnp.int32)
    comb = ((keys & ((1 << bits) - 1)) << _LSB_IDX_BITS) | iota
    s = 0
    while s < bits:
        d = min(max(digit, 1), bits - s)
        if d == 1:
            bit = (comb >> (_LSB_IDX_BITS + s)) & 1
            c0 = _ref.prefix_sum(1 - bit)
            pos = jnp.where(bit == 0, c0 - 1, c0[-1] + iota - c0)
        else:
            dig = (comb >> (_LSB_IDX_BITS + s)) & ((1 << d) - 1)
            pos = jnp.zeros(n, jnp.int32)
            base = jnp.int32(0)
            for b in range(1 << d):
                c = _ref.prefix_sum((dig == b).astype(jnp.int32))
                pos = jnp.where(dig == b, base + c - 1, pos)
                base = base + c[-1]
        comb = jnp.zeros_like(comb).at[pos].set(comb)
        s += d
    idx = comb & ((1 << _LSB_IDX_BITS) - 1)
    return keys[idx], tuple(v[idx] for v in vals)


@functools.partial(jax.jit, static_argnames=("bits", "kernel", "tile",
                                             "width", "digit"))
def _part_join_jit(col, rowids, groups, htk, htv, mult, ref, *, bits: int,
                   kernel: bool, tile: int, width: int, digit: int):
    """The whole partitioned join step traced as ONE executable:
    FK-column gather (+ in-register bit-unpack when the column is
    packed) -> multi-payload radix shuffle -> device-side boundary
    histogram -> fused single-launch probe.  No host round-trip anywhere
    inside."""
    if width == 32:
        keys = col[jnp.clip(rowids, 0, col.shape[0] - 1)]
    else:
        n_vals = col.shape[0] * (32 // width)
        keys = gather_decode(col, jnp.clip(rowids, 0, n_vals - 1),
                             width, ref)
    if kernel:
        outk, (orow, ogrp) = _radix.partition_multi(
            keys, (rowids, groups), 0, bits, tile=tile)
        counts = jnp.bincount(outk & ((1 << bits) - 1),
                              length=1 << bits).astype(jnp.int32)
        offs = (jnp.cumsum(counts) - counts).astype(jnp.int32)
        return _pp.part_probe(outk, orow, ogrp, offs, counts, htk, htv,
                              mult, tile=tile)
    outk, (orow, ogrp) = _lsb_partition_multi(keys, (rowids, groups), bits,
                                              digit)
    # boundaries by binary search: the shuffled keys' buckets are already
    # ascending, so 2^bits searchsorteds beat a scatter-add histogram
    buckets = outk & jnp.int32((1 << bits) - 1)
    ends = jnp.searchsorted(
        buckets, jnp.arange(1, (1 << bits) + 1, dtype=jnp.int32),
        side="left").astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    counts = ends - offs
    return _ref.part_probe(outk, orow, ogrp, offs, counts, htk, htv, mult)


def part_join(col, rowids, groups, htk, htv, mult, bits: int,
              mode: str = "auto", tile: int = DEFAULT_TILE,
              width: int = 32, ref=0, digit: int = 1):
    """Fused radix-partitioned join: gather the live rows' FK keys from
    ``col``, partition them by the key's low ``bits`` bits (rowid +
    running group id ride the shuffle), then probe every partition
    against its packed ``(P, S)`` table in a single kernel launch.
    Returns stable partition-major (out_rowids,
    out_groups(+payload*mult), count).

    ``col`` may be a bit-packed word stream (``width != 32``, frame of
    reference ``ref``): the FK gather then touches only the words the
    live rows reference and decodes in registers inside the same
    executable.

    The probe side is pow2-padded BEFORE the shuffle so XLA compiles
    O(log n) shapes across query cardinalities; pad rows carry
    ``rowid = -1`` (the probe's dead-row sentinel) so wherever the
    shuffle buckets them they can never contribute a match.

    ``digit`` is the host shuffle's LSD pass width
    (:func:`_lsb_partition_multi`); the kernel path partitions in one
    ``bits``-wide pass and ignores it."""
    n = rowids.shape[0]
    if n == 0:
        z = jnp.zeros((0,), jnp.int32)
        return z, z, jnp.int32(0)
    n_pad = 1 << max((n - 1).bit_length(), 0)
    rowids = jnp.pad(rowids, (0, n_pad - n), constant_values=-1)
    groups = jnp.pad(groups, (0, n_pad - n))
    return _part_join_jit(col, rowids, groups, htk, htv,
                          jnp.asarray(mult, jnp.int32),
                          jnp.asarray(ref, jnp.int32), bits=bits,
                          kernel=use_kernel("part_probe", mode),
                          tile=tile, width=width, digit=digit)


def radix_sort(keys, vals, mode: str = "auto", r: int = 8,
               tile: int = DEFAULT_TILE):
    if use_kernel("radix_sort", mode):
        return _radix.radix_sort(keys, vals, r=r, tile=tile)
    return _ref.radix_sort(keys, vals)


def radix_partition(keys, vals, start_bit, r, mode: str = "auto",
                    tile: int = DEFAULT_TILE):
    if use_kernel("radix_partition", mode):
        return _radix.partition(keys, vals, start_bit, r, tile=tile)
    return _ref.partition(keys, vals, start_bit, r)


def radix_partition_multi(keys, vals, start_bit, r, mode: str = "auto",
                          tile: int = DEFAULT_TILE):
    """Stable partition pass with N payload columns riding the key
    (keys', (vals0', ...)) — the partitioned-join shuffle."""
    vals = tuple(vals)
    if keys.shape[0] == 0:
        return keys, vals
    if use_kernel("radix_partition", mode):
        return _radix.partition_multi(keys, vals, start_bit, r, tile=tile)
    return _ref.partition_multi(keys, vals, start_bit, r)


def reduce_sum(x, mode: str = "auto", tile: int = DEFAULT_TILE):
    if use_kernel("reduce_sum", mode):
        return _agg.reduce_sum(x, tile=tile)
    return _ref.reduce_sum(x)


def group_sum(group_ids, vals, n_groups, mode: str = "auto",
              tile: int = DEFAULT_TILE):
    if use_kernel("group_sum", mode):
        return _agg.group_sum(group_ids, vals, n_groups, tile=tile)
    return _group_sum_xla(group_ids, vals, n_groups)


_group_sum_xla = jax.jit(_ref.group_sum, static_argnames=("n_groups",))


# Row block of the XLA SPJA paths: full blocks run in a fori_loop, so one
# block's intermediates (decoded columns, probe state, bitmaps — per
# join, and for a wave per member) are live at a time, however long the
# fact table is: the jnp analogue of the kernels' grid.  The TPU's XLA
# scatter-add pads its (rows, 1) index operand to 128 lanes (~512 B per
# row), so the block size also bounds that temporary: 2^20 rows keep it
# near 0.5 GB.  A multiple of 32 rows, so every block starts on a word of
# every packed width.
XLA_BLOCK_ROWS = 1 << 20


def _decode_planes(words, width: int, ref, rows: int):
    """A block of ``rows`` (a multiple of 32) rows of a stream as a
    ``(32, rows // 32)`` array: entry ``[k, i]`` is row ``32 * i + k``.
    Every width lands in this same order (row-order-free SPJA sums do
    not care which), lane-dense for the TPU: the block's words go
    through one ``(rows/32, width) -> (width, rows/32)`` transpose, then
    a shift and a mask per value lane.  Plain streams keep their
    dtype."""
    cols = rows // 32
    planes = words.reshape(cols, width).T           # (words per 32 rows, .)
    if width == 32:
        return planes
    per_word = 32 // width
    shifts = (jnp.arange(per_word, dtype=jnp.int32) * width)[None, :, None]
    vals = jax.lax.shift_right_logical(planes[:, None, :], shifts) \
        & jnp.int32((1 << width) - 1)
    return vals.reshape(32, cols) + ref


def _fold_rows(streams, n_rows: int, step, acc):
    """``acc = step(acc, cols)`` over the fact rows in blocks of
    ``XLA_BLOCK_ROWS``: ``cols`` holds each ``(array, width, ref)``
    stream decoded for the block's rows — full blocks as
    :func:`_decode_planes` arrays, the rows past the last full block as
    one static 1-D tail block — so no stream is padded or copied."""
    n_full, tail = divmod(n_rows, XLA_BLOCK_ROWS)

    def full_block(i):
        cols = []
        with jax.named_scope(_ref.DECODE):
            for arr, width, ref in streams:
                n_words = XLA_BLOCK_ROWS * width // 32
                cols.append(_decode_planes(
                    jax.lax.dynamic_slice(arr, (i * n_words,), (n_words,)),
                    width, ref, XLA_BLOCK_ROWS))
        return cols

    if n_full:
        acc = jax.lax.fori_loop(0, n_full,
                                lambda i, a: step(a, full_block(i)), acc)
    if tail:
        start = n_full * XLA_BLOCK_ROWS
        cols = []
        with jax.named_scope(_ref.DECODE):
            for arr, width, ref in streams:
                per_word = 32 // width
                w0 = start // per_word
                cols.append(_decode_stream(
                    arr[w0:w0 + -(-tail // per_word)], width, ref, tail))
        acc = step(acc, cols)
    return acc


def _sum_pairs(a, b):
    return jax.tree.map(jnp.add, a, b)


def _zero_sums(shape, exact: bool):
    """Accumulator for a ``ref.group_sums`` pair."""
    return (jnp.zeros(shape, jnp.int32) if exact else None,
            jnp.zeros(shape, jnp.float32))


def _streams(cols, widths, refs):
    return [(c, w, refs[i] if w != 32 else 0)
            for i, (c, w) in enumerate(zip(cols, widths))]


def _integer(arr, width: int) -> bool:
    """Packed streams decode to int32; plain ones keep their dtype."""
    return width != 32 or jnp.issubdtype(arr.dtype, jnp.integer)


# one jitted executable per wave *shape* (Q, C, J, M, n_groups, n, widths):
# the member queries themselves are data (stacked SMEM-style parameter
# arrays), so re-running a wave of any composition over the same unions
# hits the trace cache — the multi-query analogue of _part_probe_ref_jit.
# Packed streams decode inside the trace, fused with the scan by XLA.
@functools.partial(jax.jit,
                   static_argnames=("n_groups", "pred_widths", "key_widths",
                                    "m_widths", "n_rows"))
def _multi_spja_xla(pred_cols, pred_bounds, join_keys, key_refs,
                    join_tables, join_mults, join_use, q_valid,
                    measure_cols, m_refs, measure_sel, *, n_groups,
                    pred_widths, key_widths, m_widths, n_rows):
    n_p, n_j = len(pred_cols), len(join_keys)
    streams = (_streams(pred_cols, pred_widths, [0] * n_p)
               + _streams(join_keys, key_widths, key_refs)
               + _streams(measure_cols, m_widths, m_refs))
    exact = all(_integer(m, w) for m, w in zip(measure_cols, m_widths))

    def step(acc, cols):
        return _sum_pairs(acc, _ref.multi_spja_sums(
            cols[:n_p], pred_bounds, cols[n_p:n_p + n_j], join_tables,
            join_mults, join_use, q_valid, cols[n_p + n_j:], measure_sel,
            n_groups=n_groups))

    acc = _zero_sums((pred_bounds.shape[0], n_groups), exact)
    return _ref.finish_sums(*_fold_rows(streams, n_rows, step, acc))


def multi_spja(pred_cols, pred_bounds, join_keys, join_tables, join_mults,
               join_use, q_valid, measure_cols, measure_sel, n_groups=1,
               mode: str = "auto", tile: int = DEFAULT_TILE,
               pred_widths=None, key_widths=None, key_refs=None,
               m_widths=None, m_refs=None, n_rows=None, axis_name=None):
    """Whole-wave shared-scan SPJA: Q stacked queries, one fact pass.
    Argument semantics documented on ``repro.kernels.ref.multi_spja``
    (the oracle); returns (Q, n_groups) f32.  Streams may be bit-packed
    (``*_widths[i] != 32``) per ``repro.sql.storage``'s layout.
    ``axis_name`` mirrors :func:`spja`'s sharded hook: under a
    ``shard_map``, the whole wave's (Q, n_groups) partial grid is
    ``psum``'d over the named mesh axis."""
    pred_widths = tuple(pred_widths or (32,) * len(pred_cols))
    key_widths = tuple(key_widths or (32,) * len(join_keys))
    m_widths = tuple(m_widths or (32,) * len(measure_cols))
    if key_refs is None:
        key_refs = jnp.zeros((len(join_keys),), jnp.int32)
    if m_refs is None:
        m_refs = jnp.zeros((len(measure_cols),), jnp.int32)
    if n_rows is None:
        if m_widths and m_widths[0] != 32:
            # a packed measure's length is the WORD count, not the row
            # count — guessing would silently scan a fraction of the rows
            raise ValueError("n_rows is required when the measure stream "
                             "is bit-packed")
        n_rows = int(measure_cols[0].shape[0])
    if use_kernel("multi_spja", mode):
        from repro.kernels import multi_fused
        out = multi_fused.multi_spja(
            tuple(pred_cols), pred_bounds, tuple(join_keys),
            tuple(join_tables), join_mults, join_use, q_valid,
            tuple(measure_cols), measure_sel, n_groups=n_groups, tile=tile,
            pred_widths=pred_widths, key_widths=key_widths,
            key_refs=key_refs, m_widths=m_widths, m_refs=m_refs,
            n_rows=n_rows)
    else:
        out = _multi_spja_xla(
            tuple(pred_cols), pred_bounds, tuple(join_keys), key_refs,
            tuple(join_tables), join_mults, join_use, q_valid,
            tuple(measure_cols), m_refs, measure_sel, n_groups=n_groups,
            pred_widths=pred_widths, key_widths=key_widths,
            m_widths=m_widths, n_rows=n_rows)
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return out


# the whole single-query SPJA XLA path under jit: one cached executable
# per (shapes, widths, measure_op, n_groups) combination, and for packed
# streams the in-trace decode fuses with the scan instead of
# materializing a full-width column between ops
@functools.partial(jax.jit,
                   static_argnames=("measure_op", "n_groups", "pred_widths",
                                    "key_widths", "m_widths", "n_rows"))
def _spja_xla(pred_cols, pred_bounds, join_keys, key_refs, join_tables,
              group_mults, m1, m2, m_refs, *, measure_op, n_groups,
              pred_widths, key_widths, m_widths, n_rows):
    n_p, n_j = len(pred_cols), len(join_keys)
    ms = [m1] if m2 is None else [m1, m2]
    streams = (_streams(pred_cols, pred_widths, [0] * n_p)
               + _streams(join_keys, key_widths, key_refs)
               + _streams(ms, m_widths, m_refs))
    exact = all(_integer(m, w) for m, w in zip(ms, m_widths))

    def step(acc, cols):
        meas = cols[n_p + n_j:]
        return _sum_pairs(acc, _ref.spja_sums(
            cols[:n_p], pred_bounds, cols[n_p:n_p + n_j], join_tables,
            group_mults, meas[0], meas[1] if len(meas) == 2 else None,
            measure_op=measure_op, n_groups=n_groups))

    acc = _zero_sums((n_groups,), exact)
    return _ref.finish_sums(*_fold_rows(streams, n_rows, step, acc))


def spja(pred_cols, pred_bounds, join_keys, join_tables, group_mults,
         m1, m2=None, measure_op="first", n_groups=1, mode: str = "auto",
         tile: int = DEFAULT_TILE, pred_widths=None, key_widths=None,
         key_refs=None, m_widths=None, m_refs=None, n_rows=None,
         axis_name=None):
    """``axis_name`` is the sharded-execution hook: inside a
    ``shard_map`` over a device mesh, the kernel runs UNCHANGED on its
    shard's streams and the dispatch layer ``psum``s the dense
    ``(n_groups,)`` grid over the named mesh axis — the tree-reduce of
    per-shard partial aggregates, fused into the same launch."""
    n_meas = 2 if measure_op in ("mul", "sub") else 1
    if n_meas == 1:
        m2 = None                   # accept-and-ignore: "first" reads m1 only
    pred_widths = tuple(pred_widths or (32,) * len(pred_cols))
    key_widths = tuple(key_widths or (32,) * len(join_keys))
    m_widths = tuple(m_widths or (32,) * n_meas)
    if key_refs is None:
        key_refs = jnp.zeros((len(join_keys),), jnp.int32)
    if m_refs is None:
        m_refs = jnp.zeros((n_meas,), jnp.int32)
    if n_rows is None:
        if m_widths[0] != 32:
            # a packed measure's length is the WORD count, not the row
            # count — guessing would silently scan a fraction of the rows
            raise ValueError("n_rows is required when the measure stream "
                             "is bit-packed")
        n_rows = int(m1.shape[0])
    if use_kernel("spja", mode):
        from repro.kernels import ssb_fused
        out = ssb_fused.spja(tuple(pred_cols), pred_bounds,
                             tuple(join_keys), tuple(join_tables),
                             group_mults, m1, m2, measure_op=measure_op,
                             n_groups=n_groups, tile=tile,
                             pred_widths=pred_widths,
                             key_widths=key_widths, key_refs=key_refs,
                             m_widths=m_widths, m_refs=m_refs,
                             n_rows=n_rows)
    else:
        out = _spja_xla(tuple(pred_cols), pred_bounds, tuple(join_keys),
                        key_refs, tuple(join_tables), group_mults, m1, m2,
                        m_refs, measure_op=measure_op, n_groups=n_groups,
                        pred_widths=pred_widths, key_widths=key_widths,
                        m_widths=m_widths, n_rows=n_rows)
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return out
