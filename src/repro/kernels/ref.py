"""Pure-jnp oracles for every kernel — the correctness ground truth.

Each function has the same signature/semantics as its kernel counterpart
but is a jnp program with no tiling, used by tests/test_kernels.py
(shape/dtype sweeps + hypothesis properties) and, compiled by XLA, as
each op's path wherever its kernel does not run (``repro.kernels.ops``).
Prefix sums and compactions are written in bounded pieces because the
TPU's compiler takes tens of seconds for a single cumsum or scatter over
millions of elements, and the fact table has 120M rows at SF-20.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import blocks as B
from repro.kernels.common import decode_words

# named scopes of the SPJA phases: they mark the ops of each phase in the
# compiled program's metadata, where a profiler trace reads them
DECODE = "spja.decode"
FILTER = "spja.filter"
PROBE = "spja.probe"
AGGREGATE = "spja.aggregate"


def unpack(words: jax.Array, n: int, phys: int, ref=0) -> jax.Array:
    """Bit-unpack oracle: first ``n`` values of a packed word stream at
    ``phys`` bits per value, plus the frame of reference (semantics owned
    by ``repro.sql.storage``; this is the device-side inverse)."""
    return decode_words(words, phys, ref)[:n]


_SCAN_WINDOW = 1024                 # largest cumsum window in one piece
_SCATTER_BLOCK = 1 << 20            # largest scatter in one piece


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D array: cumsums over windows of at
    most ``_SCAN_WINDOW`` plus the prefix sum of the window totals."""
    n = x.shape[0]
    if n <= _SCAN_WINDOW:
        return jnp.cumsum(x)
    m = -(-n // _SCAN_WINDOW)
    inner = jnp.cumsum(jnp.pad(x, (0, m * _SCAN_WINDOW - n)).reshape(
        m, _SCAN_WINDOW), axis=1)
    totals = inner[:, -1]
    return (inner + (prefix_sum(totals) - totals)[:, None]).reshape(-1)[:n]


def compact(keep: jax.Array, cols):
    """Stable compaction: each of ``cols`` with its kept rows packed to
    the front (zero-padded to the input length), and the kept count.
    Rows compact in blocks of ``_SCATTER_BLOCK`` (a scatter into a small
    buffer), each block landing after the previous block's survivors."""
    n = keep.shape[0]

    def block(keep_b, cols_b):
        m = keep_b.shape[0]
        bitmap = keep_b.astype(jnp.int32)
        idx = jnp.where(keep_b, prefix_sum(bitmap) - bitmap, m)
        packed = tuple(jnp.zeros((m + 1,), c.dtype).at[idx].set(
            c, mode="drop")[:m] for c in cols_b)
        return packed, jnp.sum(bitmap)

    if n <= _SCATTER_BLOCK:
        return block(keep, cols)
    # room for a whole block past the end: a block lands at its offset
    # unclamped, zeros and all, and the next block overwrites the zeros
    outs = tuple(jnp.zeros((n + _SCATTER_BLOCK,), c.dtype) for c in cols)

    def land(carry, start, size, sliced):
        outs, at = carry
        packed, cnt = block(sliced(keep, start, size),
                            tuple(sliced(c, start, size) for c in cols))
        return (tuple(jax.lax.dynamic_update_slice(o, p, (at,))
                      for o, p in zip(outs, packed)), at + cnt)

    n_full, tail = divmod(n, _SCATTER_BLOCK)
    carry = jax.lax.fori_loop(0, n_full, lambda b, c: land(
        c, b * _SCATTER_BLOCK, _SCATTER_BLOCK,
        lambda a, s, z: jax.lax.dynamic_slice(a, (s,), (z,))),
        (outs, jnp.int32(0)))
    if tail:
        carry = land(carry, n - tail, tail, lambda a, s, z: a[s:s + z])
    outs, count = carry
    return tuple(o[:n] for o in outs), count


def select_scan(x: jax.Array, y: jax.Array, lo, hi
                ) -> Tuple[jax.Array, jax.Array]:
    """Returns (compacted y where lo<=x<=hi — stable, padded, count)."""
    (out,), count = compact((x >= lo) & (x <= hi), (y,))
    return out, count


def project(x1, x2, a, b, sigmoid: bool = False) -> jax.Array:
    y = a * x1 + b * x2
    if sigmoid:
        y = 1.0 / (1.0 + jnp.exp(-y))
    return y


def build(keys, vals, n_slots) -> Tuple[jax.Array, jax.Array]:
    return B.build_hash_table(keys, vals, n_slots)


def probe_agg(keys, vals, ht_keys, ht_vals) -> jax.Array:
    payload, found = B.block_lookup(keys, ht_keys, ht_vals)
    return jnp.sum(jnp.where(found > 0, payload + vals, 0))


def probe_join(keys, vals, ht_keys, ht_vals):
    payload, found = B.block_lookup(keys, ht_keys, ht_vals)
    (outp, outv), count = compact(found > 0, (payload, vals))
    return outp, outv, count


def part_probe(keys, rowids, groups, offs, counts, htk, htv, mult):
    """Fused partitioned-probe oracle: probe every element of the flat
    partition-major probe side against ITS partition's table in the
    packed ``(P, S)`` layout, then stably compact the matches.

    The element's partition is its key's low bits — the same rule that
    packed the tables and shuffled the probe side — so the probe needs
    no position bookkeeping beyond the run total; ``offs``/``counts``
    bound the live region (rows beyond it are padding)."""
    n_parts, n_slots = htk.shape
    n = keys.shape[0]
    total = (offs[-1] + counts[-1]).astype(jnp.int32) if n_parts else 0
    pos = jnp.arange(n, dtype=jnp.int32)
    pid = keys & jnp.int32(n_parts - 1)
    base = pid * n_slots
    flat_k = htk.reshape(-1)
    flat_v = htv.reshape(-1)
    slot0 = B.hash_fn(keys, n_slots)

    # lock-step linear probe carrying only (slot, done, found): one
    # gather per iteration; a finished lane parks its slot on the hit
    # (or the chain-terminating empty) slot, so payloads are a single
    # gather after the loop
    def cond(state):
        return ~jnp.all(state[1])

    def body(state):
        slot, done, found = state
        k_at = flat_k[base + slot]
        hit = (k_at == keys) & ~done
        empty = k_at == B.EMPTY
        found = found | hit
        done = done | hit | empty
        slot = jnp.where(done, slot, (slot + 1) & (n_slots - 1))
        return slot, done, found

    done0 = jnp.zeros(keys.shape, bool)
    slot, _, found = jax.lax.while_loop(
        cond, body, (slot0, done0, done0))
    payload = jnp.where(found, flat_v[base + slot], 0)
    # pad rows beyond the runs + dead rows (negative rowid sentinel)
    # inside them both never match
    found = found & (pos < total) & (rowids >= 0)
    grp_out = groups + payload * jnp.asarray(mult, groups.dtype)
    (outr, outg), count = compact(found, (rowids, grp_out))
    return outr, outg, count


def multi_spja(pred_cols, pred_bounds, join_keys, join_tables, join_mults,
               join_use, q_valid, measure_cols, measure_sel,
               n_groups=1) -> jax.Array:
    """Returns (Q, n_groups) f32; see :func:`multi_spja_sums`."""
    return finish_sums(*multi_spja_sums(
        pred_cols, pred_bounds, join_keys, join_tables, join_mults,
        join_use, q_valid, measure_cols, measure_sel, n_groups=n_groups))


def multi_spja_sums(pred_cols, pred_bounds, join_keys, join_tables,
                    join_mults, join_use, q_valid, measure_cols,
                    measure_sel, n_groups=1):
    """Multi-query SPJA oracle: Q queries evaluated in ONE pass over the
    fact table.  Shared work is factored exactly the way the fused kernel
    factors it — every predicate column is compared once per query against
    that query's (lo, hi) bounds, every deduplicated dim hash table is
    probed ONCE for all queries — and only the per-query bitmap / group-id
    / aggregate work fans out by Q.

    Stacked per-query parameters (Q = wave size, member q may be padding):
      pred_bounds  (Q, C, 2) int32 — closed ranges per (query, column);
                   a query that does not filter column c carries the
                   all-pass range (INT32_MIN, INT32_MAX)
      join_mults   (Q, J) int32 — group-id multiplier (0: unused payload)
      join_use     (Q, J) int32 — 1 when a probe miss on join j filters
                   query q's row, 0 when query q ignores join j
      q_valid      (Q,)   int32 — 0 marks a padding slot (no contribution)
      measure_sel  (Q, 3) int32 — (m1 idx, m2 idx, op) into measure_cols;
                   op: 0 = m1, 1 = m1*m2, 2 = m1-m2
    Returns the (Q, n_groups) per-query per-group sums as a
    :func:`group_sums` pair (integer measure columns sum exactly)."""
    Q = pred_bounds.shape[0]
    C = len(pred_cols)
    J = len(join_keys)
    M = len(measure_cols)
    shape = measure_cols[0].shape     # rows: any layout, all streams alike
    mdt = (jnp.int32 if all(jnp.issubdtype(c.dtype, jnp.integer)
                            for c in measure_cols) else jnp.float32)

    # --- shared once-per-wave work: column predicates stay per-query,
    # but each dim table is probed exactly once for every member ---
    payloads, founds = [], []
    with jax.named_scope(PROBE):
        for j in range(J):
            payload, found = B.block_lookup(
                join_keys[j], join_tables[2 * j], join_tables[2 * j + 1])
            payloads.append(payload)
            founds.append(found)

    rows = []
    for q in range(Q):
        with jax.named_scope(FILTER):
            bitmap = jnp.full(shape, q_valid[q], jnp.int32)
            for c in range(C):
                bitmap = bitmap * (
                    (pred_cols[c] >= pred_bounds[q, c, 0])
                    & (pred_cols[c] <= pred_bounds[q, c, 1])
                ).astype(jnp.int32)
        group = jnp.zeros(shape, jnp.int32)
        for j in range(J):
            use = join_use[q, j]
            bitmap = bitmap * (1 - use + use * founds[j])
            group = group + payloads[j] * join_mults[q, j]
        with jax.named_scope(AGGREGATE):
            # measure: data-selected from the stacked measure columns so
            # one trace serves any member composition
            m1 = jnp.zeros(shape, mdt)
            m2 = jnp.zeros(shape, mdt)
            for m in range(M):
                m1 = m1 + jnp.where(measure_sel[q, 0] == m,
                                    measure_cols[m].astype(mdt), 0)
                m2 = m2 + jnp.where(measure_sel[q, 1] == m,
                                    measure_cols[m].astype(mdt), 0)
            op = measure_sel[q, 2]

            def pick(forms):            # op: 0 = m1, 1 = m1*m2, 2 = m1-m2
                return jnp.where(op == 1, forms[1],
                                 jnp.where(op == 2, forms[2], forms[0]))

            mi = _measure(m1, m2, jnp.int32)
            rows.append(group_sums(group, bitmap,
                                   None if mi is None else pick(mi),
                                   pick(_measure(m1, m2, jnp.float32)),
                                   n_groups))
    wrapped = (None if rows[0][0] is None
               else jnp.stack([r[0] for r in rows]))
    return wrapped, jnp.stack([r[1] for r in rows])


def histogram(keys, start_bit, r, tile) -> jax.Array:
    """Per-tile histograms, matching the kernel's (n_tiles, 2^r) layout."""
    n = keys.shape[0]
    pad = (-n) % tile
    b = jax.lax.shift_right_logical(keys, start_bit) & ((1 << r) - 1)
    b = jnp.pad(b.astype(jnp.int32), (0, pad), constant_values=1 << r)
    nt = b.shape[0] // tile
    onehot = b.reshape(nt, tile)[:, :, None] == jnp.arange(1 << r)
    return jnp.sum(onehot.astype(jnp.int32), axis=1)


def partition(keys, vals, start_bit, r) -> Tuple[jax.Array, jax.Array]:
    """One stable radix-partition pass (argsort-stable oracle)."""
    b = jax.lax.shift_right_logical(keys, start_bit) & ((1 << r) - 1)
    order = jnp.argsort(b, stable=True)
    return keys[order], vals[order]


def partition_multi(keys, vals, start_bit, r):
    """Stable radix-partition pass carrying N payload columns: one stable
    argsort of the bucket ids, every column gathered through it."""
    b = jax.lax.shift_right_logical(keys, start_bit) & ((1 << r) - 1)
    order = jnp.argsort(b, stable=True)
    return keys[order], tuple(v[order] for v in vals)


def radix_sort(keys, vals) -> Tuple[jax.Array, jax.Array]:
    order = jnp.argsort(keys, stable=True)
    return keys[order], vals[order]


def reduce_sum(x) -> jax.Array:
    dt = jnp.float32 if jnp.issubdtype(x.dtype, jnp.floating) else jnp.int32
    return jnp.sum(x.astype(dt))


def group_sum(group_ids, vals, n_groups) -> jax.Array:
    """SELECT SUM(vals) GROUP BY group_ids -> (n_groups,) f32; integer
    values sum exactly (see :func:`group_sums`)."""
    ones = jnp.ones(group_ids.shape, jnp.int32)
    exact = jnp.issubdtype(vals.dtype, jnp.integer)
    return finish_sums(*group_sums(group_ids, ones,
                                   vals.astype(jnp.int32) if exact else None,
                                   vals.astype(jnp.float32), n_groups))


# ---------------------------------------------------------------------------
# exact group sums of integer measures
# ---------------------------------------------------------------------------
#
# An f32 accumulator is exact only while a group's total stays below 2^24;
# at SF-20 the flight-1 totals pass 2^31, and an f32 sum then depends on
# the order of the additions (a sequential f32 sum of q1.1's 2.2M terms
# is off by ~2.5e-4 relative).  Integer measures therefore sum twice: an
# int32 scatter-add, whose two's-complement wrap keeps each total exact
# modulo 2^32, and an f32 scatter-add, which places the total to far
# better than 2^31.  Together they fix the integer total, which
# ``finish_sums`` rounds to f32 once.  No int64 is needed (the TPU has
# none), and the int32 halves of partial sums add associatively, so the
# row-block fold of the XLA path (``repro.kernels.ops``) accumulates
# them across blocks before finishing.  Exact while each row's measure
# fits int32 and the f32 estimate errs by less than 2^31.


def _measure(m1, m2, dt):
    """``(first, mul, sub)`` per-row measures in dtype ``dt``; ``None``
    for the integer form of a float measure (no exact sum exists)."""
    if jnp.issubdtype(dt, jnp.integer) and not (
            jnp.issubdtype(m1.dtype, jnp.integer)
            and (m2 is None or jnp.issubdtype(m2.dtype, jnp.integer))):
        return None
    a = m1.astype(dt)
    b = a if m2 is None else m2.astype(dt)
    return a, a * b, a - b


def group_sums(group, bitmap, mi, mf, n_groups: int):
    """Per-group sums over the rows whose ``bitmap`` is set, as the pair
    ``(wrapped, approx)``: the int32 modulo-2^32 sums of the integer
    measure ``mi`` (``None`` for a float measure) and the f32 sums of
    ``mf``.  Pairs add elementwise; :func:`finish_sums` makes the
    totals."""
    keep = bitmap > 0
    if n_groups == 1:
        # one group: a reduction, not a scatter — the scatter's indices
        # would be a constant the compiler folds row by row
        approx = jnp.sum(jnp.where(keep, mf, 0.0)).reshape(1)
        wrapped = (None if mi is None
                   else jnp.sum(jnp.where(keep, mi, 0)).reshape(1))
        return wrapped, approx
    safe = jnp.where(keep, group, 0)
    approx = jnp.zeros((n_groups,), jnp.float32).at[safe].add(
        jnp.where(keep, mf, 0.0))
    if mi is None:
        return None, approx
    wrapped = jnp.zeros((n_groups,), jnp.int32).at[safe].add(
        jnp.where(keep, mi, 0))
    return wrapped, approx


def finish_sums(wrapped, approx) -> jax.Array:
    """f32 totals from a :func:`group_sums` pair: the integer congruent
    to ``wrapped`` modulo 2^32 that lies nearest ``approx``, rounded to
    f32 once (``(k * 2^16 + hi) * 2^16`` is exact while |total| < 2^40,
    so only the final ``+ lo`` rounds)."""
    if wrapped is None:
        return approx
    hi = jax.lax.shift_right_logical(wrapped, 16).astype(jnp.float32)
    lo = (wrapped & 0xFFFF).astype(jnp.float32)
    k = jnp.round((approx - (hi * 65536.0 + lo)) * (1.0 / 4294967296.0))
    return (k * 65536.0 + hi) * 65536.0 + lo


def spja(pred_cols, pred_bounds, join_keys, join_tables, group_mults,
         m1, m2, measure_op="first", n_groups=1) -> jax.Array:
    """Returns (n_groups,) f32; see :func:`spja_sums`."""
    return finish_sums(*spja_sums(pred_cols, pred_bounds, join_keys,
                                  join_tables, group_mults, m1, m2,
                                  measure_op=measure_op, n_groups=n_groups))


def spja_sums(pred_cols, pred_bounds, join_keys, join_tables, group_mults,
              m1, m2, measure_op="first", n_groups=1):
    """Single-query SPJA as a :func:`group_sums` pair (integer measures
    sum exactly)."""
    with jax.named_scope(FILTER):
        bitmap = jnp.ones(m1.shape, jnp.int32)   # rows: any layout
        for p, col in enumerate(pred_cols):
            bitmap = bitmap * ((col >= pred_bounds[p, 0])
                               & (col <= pred_bounds[p, 1])
                               ).astype(jnp.int32)
    group = jnp.zeros(m1.shape, jnp.int32)
    for j, keys in enumerate(join_keys):
        with jax.named_scope(PROBE):
            payload, found = B.block_lookup(keys, join_tables[2 * j],
                                            join_tables[2 * j + 1])
        bitmap = bitmap * found
        group = group + payload * group_mults[j]
    if measure_op not in ("mul", "sub"):
        m2 = None
    pick = {"first": 0, "mul": 1, "sub": 2}[measure_op]
    with jax.named_scope(AGGREGATE):
        mi = _measure(m1, m2, jnp.int32)
        mf = _measure(m1, m2, jnp.float32)
        return group_sums(group, bitmap, None if mi is None else mi[pick],
                          mf[pick], n_groups)
