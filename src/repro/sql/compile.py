"""Plan compiler: lower a logical plan to one of the physical strategies.

``fused``  — collapse the whole SPJA subtree into the single-pass
             ``kernels/ssb_fused.spja`` kernel (the paper's Crystal model,
             §5.3: zero intermediate materialization, one HBM pass over
             the fact table).
``opat``   — operator-at-a-time: each plan node lowers to an individual
             ``kernels/ops`` primitive with *materialized* intermediates
             between operators (the paper's CPU-engine model).  Each
             operator emits a positional *selection vector* (one
             select_scan/probe per node), and every live column (row ids,
             running group id) is re-materialized through it by gather —
             MonetDB-style positional reconstruction.  That per-operator
             memory traffic is exactly the overhead Fig. 16/§5.3
             attributes to non-fused engines, and
             ``benchmarks/run.py fig17`` measures it.
``part``   — radix-partitioned hash join (paper §4.4, Fig. 8): opat's
             chain, but every join partitions probe side *and* build side
             by the key's low radix bits — the multi-payload shuffle
             carries row ids and the running group id along with the key
             — then probes each partition against its own small
             cache/VMEM-resident hash table.  The probe phase is ONE
             fused kernel launch (``kernels/part_probe.py``): the grid
             iterates over partitions, each step windows its partition's
             packed table and walks its slice of the shuffled probe
             arrays.  The extra partition pass buys probes that never
             miss to device memory; ``benchmarks/run.py fig8`` measures
             the crossover against build-side cardinality.
``part_loop`` — the same partitioned join, probe phase orchestrated from
             the host partition-at-a-time (one jitted ``probe_join`` per
             partition, O(2^bits) dispatches).  Kept as the A/B baseline
             the fused kernel is measured against (fig8's
             ``part_loop`` series); not a candidate for ``auto``'s
             argmin in spirit, but priced by the model (launch overhead
             included) so the comparison is honest.
``shared`` — shared-scan *group* lowering: a wave of fusable aggregate
             plans over the same fact table executes as ONE fused pass
             (``kernels/multi_fused.py``) — the fact table is streamed
             once per wave, each deduplicated dim hash table is probed
             once for every member, and only per-query bitmaps/group
             ids/aggregates fan out.  ``execute_shared`` is the group
             entry point; ``compile_plan(plan, "shared")`` is its
             single-member degenerate form (a 1-wave).
``sharded`` — the fused lowering over a row-partitioned fact table
             (``repro.sql.shard``): each shard runs the UNCHANGED fused
             kernel, dim hash tables are replicated (built once, served
             to every shard), and the per-shard ``(n_groups,)`` partial
             grids tree-reduce to the final answer.  Two execution
             paths: a ``shard_map`` over the database's mesh feeding
             stacked ``(S, pad_rows)`` streams to the kernel with the
             reduction fused in as a ``psum`` (``ops.spja(...,
             axis_name=...)``), and a host loop + host tree merge
             (``mode="ref"``, or no mesh).  Both are bit-identical to
             the solo fused pass — SSB's integer-valued f32 partial
             sums are exact under any association order.
``auto``   — pick fused/opat/part/sharded per query from the bandwidth cost
             model (``repro.sql.model``): predicted bytes moved per
             strategy, argmin at execute time (when the database — and
             therefore the cardinalities — is known).  Group-level
             shared-vs-solo arbitration lives in the query server (it
             sees the wave); ``model.predict_shared`` prices it.

``compile_plan(plan, "fused")`` validates fusability first; plans the
fused kernel cannot express (non-range fact predicates, row-returning
roots, OrderBy) *fall back* to ``opat`` with the reason recorded on the
``CompiledQuery`` so callers and the query server can report it.
``part`` and ``part_loop`` fall back the same way on plans with nothing
to partition (row-returning plans, no joins) — both paths carry the
reason (the fused path included, so ``QueryResult`` reporting never goes
stale on it).

``LAUNCH_STATS`` counts probe/partition dispatches per process so the
single-launch claim is *observable*: ``part`` issues exactly one probe
launch per join, ``part_loop`` one per non-empty partition; it also
counts every host-to-device copy of ``storage.upload`` and its bytes.
The fused paths run each kernel call under a ``sql.dispatch`` span and
bring its result to the host under ``sql.pull`` (``repro.sql.spans``).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.kernels import ops
from repro.kernels.common import DEFAULT_TILE, gather_decode
from repro.sql import tune as TN
from repro.sql import faults as FLT
from repro.sql import hashtable as HT
from repro.sql import morsel as MS
from repro.sql import plan as P
from repro.sql import shard as SH
from repro.sql import spans as SP
from repro.sql import ssb
from repro.sql import storage as ST

STRATEGIES = ("fused", "opat", "part", "part_loop", "shared", "sharded",
              "auto")

_INT32_MIN, _INT32_MAX = -(1 << 31), (1 << 31) - 1
_MEASURE_OP_CODE = {"first": 0, "mul": 1, "sub": 2}

# process-wide dispatch and upload counters (``spans.LAUNCH_STATS``)
LAUNCH_STATS = SP.LAUNCH_STATS


def reset_launch_stats() -> Dict[str, int]:
    """Zero ``LAUNCH_STATS`` and return the previous counts."""
    prev = dict(LAUNCH_STATS)
    for k in LAUNCH_STATS:
        LAUNCH_STATS[k] = 0
    return prev


# per-family record of the launch configuration the last execution
# actually used (tile, radix width, partition depth, and where each came
# from: an explicit ``tile=`` argument, the tune store, or the shipped
# default) — ``CompiledQuery.execute`` snapshots it onto the query so
# ``QueryResult`` can report what ran, mirroring LAUNCH_STATS' pattern.
LAUNCH_CONFIG: Dict[str, Dict] = {}


def reset_launch_config() -> Dict[str, Dict]:
    """Clear ``LAUNCH_CONFIG`` and return the previous record."""
    prev = dict(LAUNCH_CONFIG)
    LAUNCH_CONFIG.clear()
    return prev


def snapshot_launch_config() -> Dict[str, Dict]:
    """Deep-enough copy of the current per-family launch record."""
    return {k: dict(v) for k, v in LAUNCH_CONFIG.items()}


def _launch(family: str, tile: Optional[int], width: int = 32, *,
            mode: str, op: Optional[str] = None, **extra) -> int:
    """Resolve + record one kernel family's launch tile.  An explicit
    ``tile=`` argument always wins (tests and A/B sweeps stay
    deterministic); ``None`` consults the tune store's winner for this
    (family, packed-width bucket) and falls back to ``DEFAULT_TILE`` on
    a cold store — byte-for-byte the pre-tuner launch.  The resolved
    configuration (with any ``extra`` knobs: radix width, partition
    depth) lands in ``LAUNCH_CONFIG`` for result reporting, with the
    implementation that runs under ``mode`` (``ops.impl`` of ``op``,
    the dispatching op when it is not named like the family)."""
    if tile is not None:
        t, src = int(tile), "explicit"
    else:
        store = TN.cached_store()
        cfg = store.get(family, width) if store is not None else None
        if cfg is not None:
            t, src = cfg.tile, "tuned"
        else:
            t, src = DEFAULT_TILE, "default"
    LAUNCH_CONFIG[family] = {"tile": t, "width": width, "source": src,
                             "impl": ops.impl(op or family, mode), **extra}
    return t


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------


def classify(plan: P.Plan) -> str:
    """Check chain well-formedness; return result kind: "agg" | "rows".

    Aggregate plans:  Scan [Filter|HashJoin]* Project GroupAgg
    Row plans:        Scan [Filter|HashJoin]* [OrderBy]
    """
    chain = plan.chain
    if not isinstance(chain[0], P.Scan):
        raise ValueError(f"{plan.name}: chain must start with Scan")
    i = 1
    while i < len(chain) and isinstance(chain[i], (P.Filter, P.HashJoin)):
        i += 1
    rest = chain[i:]
    kinds = tuple(type(n).__name__ for n in rest)
    if kinds == ("Project", "GroupAgg"):
        return "agg"
    if kinds in ((), ("OrderBy",)):
        return "rows"
    raise ValueError(
        f"{plan.name}: unsupported chain tail {kinds} — expected "
        "Project+GroupAgg (aggregate) or optional OrderBy (row plan)")


def fusability(plan: P.Plan) -> Optional[str]:
    """None if the plan can lower to the fused SPJA kernel, else the
    human-readable reason it cannot.  Raises (via classify) on malformed
    chains — an invalid plan is an error, not a fallback."""
    kind = classify(plan)
    if kind != "agg":
        return ("row-returning plan (no Project+GroupAgg root): the fused "
                "kernel only produces per-group aggregates")
    for pred in plan.filters:
        if not isinstance(pred, (P.RangePred, P.EqPred)):
            return (f"fact predicate {pred!r} is not a range predicate; "
                    "the fused kernel evaluates SMEM-resident (lo, hi) "
                    "bounds only")
    if plan.project.op not in ("first", "mul", "sub"):
        return f"measure op {plan.project.op!r} not supported by the kernel"
    return None


def shareability(plan: P.Plan) -> Optional[str]:
    """None if the plan can join a shared-scan wave, else the reason.
    A shareable plan is exactly a fusable one — the multi-query kernel
    generalizes the single-query fused kernel, so its constraints (SPJA
    aggregate chain, range-expressible fact predicates, supported measure
    ops) are inherited unchanged.  Group-level compatibility (every
    member scanning the same fact table) is checked by
    ``execute_shared``/the server, which see the whole wave."""
    return fusability(plan)


def shardability(plan: P.Plan) -> Optional[str]:
    """None if the plan can run sharded, else the reason.  A shardable
    plan is exactly a fusable one: the sharded strategy runs the fused
    kernel per shard unchanged, so it inherits its constraints — plus
    row partitioning is only sound for aggregate roots (which fusability
    already requires; per-shard partial grids sum, row order does not
    survive a partition)."""
    return fusability(plan)


def partability(plan: P.Plan) -> Optional[str]:
    """None if the plan benefits from the radix-partitioned join lowering
    (fused ``part`` or host-orchestrated ``part_loop`` alike), else the
    reason it lowers operator-at-a-time instead."""
    kind = classify(plan)
    if kind != "agg":
        return ("row-returning plan: partition-at-a-time probes reorder "
                "surviving rows, so row plans lower operator-at-a-time")
    if not plan.joins:
        return "no joins to partition; plan lowers operator-at-a-time"
    return None


# ---------------------------------------------------------------------------
# fused lowering (Crystal model)
# ---------------------------------------------------------------------------


def _rewritten_bounds(fact, bounds) -> np.ndarray:
    """(n_preds, 2) int32 predicate bounds, rewritten into the encoded
    domain for packed columns (``storage.encoded_bounds``) — the
    compile-time predicate rewrite: the kernels then compare raw
    unpacked lanes and never touch the frame of reference."""
    out = np.empty((len(bounds), 2), np.int32)
    for p, (col, lo, hi) in enumerate(bounds):
        out[p] = ST.encoded_bounds(ST.encoding_of(fact, col), lo, hi)
    return out


def _measure_streams(fact, proj):
    """The measure inputs as the kernels consume them: each column's
    ``(words, phys, ref)`` stream (``storage.column_stream``; a plain
    column is its int32 values at phys=32).
    Returns (m1, m2, m_widths, m_refs).  Stream count follows the
    measure *op*, matching the kernels' accounting — an m2 on an
    op="first" projection is ignored (never loaded), as it always was
    on the plain path."""
    streams = [ST.column_stream(fact, c)
               for c in ([proj.m1] if proj.op not in ("mul", "sub")
                         else [proj.m1, proj.m2])]
    m1 = streams[0][0]
    m2 = streams[1][0] if len(streams) == 2 else None
    widths = tuple(w for _, w, _ in streams)
    refs = ST.upload(np.array([r for _, _, r in streams], np.int32))
    return m1, m2, widths, refs


def _execute_fused(plan: P.Plan, db: ssb.Database, mode: str,
                   tile: Optional[int],
                   cache: Optional[HT.HashTableCache],
                   fact=None,
                   prebuilt: Optional[List[jnp.ndarray]] = None
                   ) -> np.ndarray:
    """One fused SPJA pass over ``fact`` (the plan's scan table by
    default; the morsel fold passes each cut).  ``prebuilt`` is the
    flattened ``[htk, htv, ...]`` join-table list when the caller built
    the wave's tables once — the per-morsel path must not re-fetch from
    the cache and inflate its hit stats."""
    if fact is None:
        fact = getattr(db, plan.scan.table)
    bounds = plan.preds           # fusability guarantees the range view
    pred_streams = [ST.column_stream(fact, c) for c, _, _ in bounds]
    pred_cols = [s[0] for s in pred_streams]
    pred_widths = tuple(s[1] for s in pred_streams)
    pred_bounds = ST.upload(_rewritten_bounds(fact, bounds))
    joins = plan.joins
    key_streams = [ST.column_stream(fact, j.fact_col) for j in joins]
    join_keys = [s[0] for s in key_streams]
    key_widths = tuple(s[1] for s in key_streams)
    key_refs = ST.upload(np.array([s[2] for s in key_streams], np.int32))
    if prebuilt is not None:
        join_tables = prebuilt
    else:
        join_tables = []
        for j in joins:
            htk, htv = (cache.get_or_build(db, j) if cache is not None
                        else HT.build_dim_table(db, j))
            join_tables.extend([htk, htv])
    mults = ST.upload(np.array([j.mult for j in joins], np.int32))
    proj = plan.project
    m1, m2, m_widths, m_refs = _measure_streams(fact, proj)
    FLT.maybe_fault("kernel")
    with SP.span(SP.DISPATCH):
        out = ops.spja(pred_cols, pred_bounds, join_keys, join_tables,
                       mults, m1, m2, measure_op=proj.op,
                       n_groups=plan.n_groups, mode=mode,
                       tile=_launch("spja", tile, mode=mode),
                       pred_widths=pred_widths,
                       key_widths=key_widths, key_refs=key_refs,
                       m_widths=m_widths, m_refs=m_refs, n_rows=fact.n_rows)
    with SP.span(SP.PULL):
        return np.asarray(out)


def _fused_scan_cols(plan: P.Plan) -> List[str]:
    """The fact columns one fused pass streams (deduplicated in load
    order) — the morsel budget is sized over exactly these."""
    cols: List[str] = []
    for c, _, _ in plan.preds:
        if c not in cols:
            cols.append(c)
    for j in plan.joins:
        if j.fact_col not in cols:
            cols.append(j.fact_col)
    proj = plan.project
    for c in ([proj.m1] if proj.op not in ("mul", "sub")
              else [proj.m1, proj.m2]):
        if c not in cols:
            cols.append(c)
    return cols


def _fused_morsels(plan: P.Plan, db: ssb.Database, mode: str,
                   tile: Optional[int],
                   cache: Optional[HT.HashTableCache], morsel_bytes: int,
                   fact=None) -> Tuple[np.ndarray, MS.MorselReport]:
    """The fused lowering as a fold over the morsel stream: dim hash
    tables build ONCE, each morsel runs the unchanged fused kernel
    (uploads double-buffered by ``MorselStream.fold``), and the
    per-morsel ``(n_groups,)`` partial grids tree-merge — the same exact
    f32 merge the sharded path trusts, so any morsel partition is
    bit-identical to the whole-table pass.  A single-morsel stream is
    the degenerate in-memory case: the one morsel IS the fact table and
    the pass is byte-for-byte the pre-refactor one."""
    if fact is None:
        fact = getattr(db, plan.scan.table)
    stream = MS.MorselStream(fact, morsel_bytes,
                             cols=_fused_scan_cols(plan))
    report = MS.MorselReport()
    if stream.n_morsels == 0:       # empty fact table: zero groups
        report.observe(0)
        return _execute_fused(plan, db, mode, tile, cache,
                              fact=fact), report
    prebuilt: List[jnp.ndarray] = []
    for j in plan.joins:
        htk, htv = (cache.get_or_build(db, j) if cache is not None
                    else HT.build_dim_table(db, j))
        prebuilt.extend([htk, htv])
    partials = stream.fold(
        lambda m: _execute_fused(plan, db, mode, tile, cache,
                                 fact=m.table, prebuilt=prebuilt),
        report)
    return SH.tree_merge(partials), report


# ---------------------------------------------------------------------------
# sharded lowering (fused kernel per fact shard + tree-reduced aggregates)
# ---------------------------------------------------------------------------


def _execute_sharded(plan: P.Plan, db, mode: str, tile: Optional[int],
                     cache: Optional[HT.HashTableCache],
                     morsel_bytes: int = MS.DEFAULT_MORSEL_BYTES
                     ) -> Tuple[np.ndarray, List[float], int,
                                MS.MorselReport]:
    """Run ``plan`` fused-per-shard and merge the partial group grids;
    returns ``(result, shard_times_s, device_count, morsel_report)``.

    Degenerate cases — a plain Database, a single shard, or a plan that
    scans something other than the sharded fact table — run the solo
    fused lowering (timed, so callers always get a breakdown).  With a
    mesh and a compiled mode the shards run under ``shard_map`` over
    uniform per-shard row *windows* with the reduction fused in as a
    ``psum``; otherwise a host loop folds each shard's own morsel stream
    and tree-merges on the host.  Either way the per-device fact
    footprint stays bounded by two morsels — shard and morsel
    composition is reports merged (morsels add, peaks max: each device
    holds its own double buffer)."""
    if (not isinstance(db, SH.ShardedDatabase) or db.n_shards == 1
            or plan.scan.table != db.fact):
        base = SH.base_of(db)
        t0 = time.perf_counter()
        out, report = _fused_morsels(plan, base, mode, tile, cache,
                                     morsel_bytes)
        return out, [time.perf_counter() - t0], 1, report
    if mode != "ref" and db.mesh is not None:
        return _execute_fused_map(plan, db, mode, tile, cache,
                                  morsel_bytes=morsel_bytes)
    partials, times = [], []
    report = MS.MorselReport()
    for shard in db.shards:
        t0 = time.perf_counter()
        fact = getattr(shard, db.fact)
        out, rep = _fused_morsels(plan, shard, mode, tile, cache,
                                  morsel_bytes, fact=fact)
        partials.append(out)
        times.append(time.perf_counter() - t0)
        report = report.merge(rep)
    return SH.tree_merge(partials), times, db.n_shards, report


def _execute_fused_map(plan: P.Plan, sdb, mode: str, tile: Optional[int],
                       cache: Optional[HT.HashTableCache],
                       morsel_bytes: int = MS.DEFAULT_MORSEL_BYTES
                       ) -> Tuple[np.ndarray, List[float], int,
                                  MS.MorselReport]:
    """The mesh path: ``shard_map`` launches over stacked ``(S, W)``
    streams.  Each mesh device sees its shard's slice, runs the
    unchanged fused kernel, and the ``psum`` inside (``ops.spja(...,
    axis_name=...)``) reduces the partial grids on the interconnect —
    the host only sees ``(n_groups,)`` answers.  Pad rows are gated out
    by the validity stream, an extra all-pass predicate with bounds
    ``(1, 1)`` on the 1/0 mask.

    When the per-shard streams exceed the morsel budget, the shard rows
    are cut into uniform LANE-aligned *windows* (every window padded to
    the same width, so ONE executable serves them all) and launched in
    sequence with at most two windows in flight — compute on window N
    overlaps the host assembly + upload of window N+1, and the window
    partial grids sum on the host.  A single window is byte-for-byte
    the pre-refactor whole-shard launch (memoized stacked streams)."""
    mesh = sdb.mesh
    tile = _launch("spja", tile, mode=mode)  # once, outside shard_fn
    base_fact = getattr(sdb.base, sdb.fact)
    scan_cols = _fused_scan_cols(plan)
    # per-shard bytes-per-row of the scanned streams + validity mask
    bpr = 4.0 + sum(ST.scan_bytes_per_row(base_fact, c)
                    for c in scan_cols)
    rows_per = MS.rows_per_morsel(bpr, morsel_bytes)
    windows = MS.plan_cuts(sdb.pad_rows, rows_per)
    whole = len(windows) <= 1
    w_pad = sdb.pad_rows if whole else rows_per

    def wbytes(lo: int, hi: int) -> int:
        total = 4 * (hi - lo)           # validity stream
        for c in scan_cols:
            enc = ST.encoding_of(base_fact, c)
            if enc is None or enc.kind == "plain":
                total += 4 * (hi - lo)
            else:
                vw = enc.values_per_word
                total += 4 * ((hi + vw - 1) // vw - lo // vw)
        return total

    bounds = plan.preds
    pb = np.concatenate([_rewritten_bounds(base_fact, bounds),
                         np.array([[1, 1]], np.int32)])
    joins = plan.joins
    join_tables: List[jnp.ndarray] = []
    for j in joins:
        if cache is not None:
            htk, htv = cache.get_or_build_replicated(sdb, j, mesh)
        else:
            htk, htv = SH.replicate(mesh, HT.build_dim_table(sdb.base, j))
        join_tables.extend([htk, htv])
    mults = jnp.asarray(np.array([j.mult for j in joins], np.int32))
    proj = plan.project
    m_cols = [proj.m1] if proj.op not in ("mul", "sub") \
        else [proj.m1, proj.m2]

    def window_inputs(lo: int, hi: int):
        """The (sharded, replicated) shard_map operands for per-shard
        rows [lo, hi) padded to w_pad (whole-table: memoized streams)."""
        if whole:
            pred_streams = ([SH.stacked_stream(sdb, c)
                             for c, _, _ in bounds]
                            + [SH.validity_stream(sdb)])
            key_streams = [SH.stacked_stream(sdb, j.fact_col)
                           for j in joins]
            m_streams = [SH.stacked_stream(sdb, c) for c in m_cols]
        else:
            pred_streams = ([SH.stacked_window(sdb, c, lo, hi, w_pad)
                             for c, _, _ in bounds]
                            + [SH.validity_window(sdb, lo, hi, w_pad)])
            key_streams = [SH.stacked_window(sdb, j.fact_col, lo, hi,
                                             w_pad) for j in joins]
            m_streams = [SH.stacked_window(sdb, c, lo, hi, w_pad)
                         for c in m_cols]
        sharded = {"pred": [s[0] for s in pred_streams],
                   "key": [s[0] for s in key_streams],
                   "m": [s[0] for s in m_streams]}
        repl = {"pb": jnp.asarray(pb), "tables": join_tables,
                "mults": mults,
                "kref": jnp.asarray(np.array([s[2] for s in key_streams],
                                             np.int32)),
                "mref": jnp.asarray(np.array([r for _, _, r in m_streams],
                                             np.int32))}
        widths = (tuple(s[1] for s in pred_streams),
                  tuple(s[1] for s in key_streams),
                  tuple(w for _, w, _ in m_streams))
        return sharded, repl, widths

    first = window_inputs(*windows[0]) if windows else None
    pred_widths, key_widths, m_widths = first[2] if first else ((), (), ())
    n_m = len(m_cols)

    def shard_fn(shd, rep):
        # each device's block arrives (1, w_pad); drop the leading dim
        flat = jax.tree.map(lambda x: x.reshape(x.shape[1:]), shd)
        ms = flat["m"]
        out = ops.spja(flat["pred"], rep["pb"], flat["key"],
                       rep["tables"], rep["mults"], ms[0],
                       ms[1] if n_m == 2 else None, measure_op=proj.op,
                       n_groups=plan.n_groups, mode=mode, tile=tile,
                       pred_widths=pred_widths, key_widths=key_widths,
                       key_refs=rep["kref"], m_widths=m_widths,
                       m_refs=rep["mref"], n_rows=w_pad,
                       axis_name=SH.SHARD_AXIS)
        return out

    mapped = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: PartitionSpec(SH.SHARD_AXIS, None),
                               first[0] if first else {}),
                  jax.tree.map(lambda _: PartitionSpec(),
                               first[1] if first else {})),
        out_specs=PartitionSpec(),
        check_vma=False)        # Pallas calls have no replication rule

    report = MS.MorselReport()
    t0 = time.perf_counter()
    partials, inflight = [], []
    for wi, (lo, hi) in enumerate(windows):
        sharded, repl, _ = first if wi == 0 else window_inputs(lo, hi)
        resident = wbytes(lo, hi)
        if wi + 1 < len(windows):
            resident += wbytes(*windows[wi + 1])
        report.observe(resident)
        FLT.maybe_fault("kernel")
        inflight.append(mapped(sharded, repl))   # async dispatch
        if len(inflight) == 2:       # bound: at most two windows resident
            partials.append(np.asarray(inflight.pop(0)))
    partials.extend(np.asarray(jax.block_until_ready(x)) for x in inflight)
    dt = time.perf_counter() - t0
    out = partials[0] if len(partials) == 1 else SH.tree_merge(partials)
    return out, [dt], sdb.n_shards, report


# ---------------------------------------------------------------------------
# shared-scan group lowering (one fused pass per wave)
# ---------------------------------------------------------------------------


def shared_join_key(join: P.HashJoin) -> Tuple:
    """Probe identity of a join inside a shared wave: the fact FK column
    plus the logical build side.  Two members whose joins agree on both
    share ONE probe stream (their ``mult``s may differ — the multiplier
    is per-member data)."""
    return (join.fact_col, HT.join_cache_key(join))


def shared_member_key(plan: P.Plan) -> Tuple:
    """Structural *execution* identity of a shareable member: two plans
    with equal keys produce byte-identical rows of the stacked wave
    parameters, so the server aggregates one and fans the result out to
    every duplicate (predicates canonicalized by sort — bound
    intersection is commutative; joins by probe identity + mult, kept in
    chain order — fingerprints may contain unorderable callables).
    Callers must have validated shareability first (``plan.preds``
    requires range-expressible predicates)."""
    proj = plan.project
    return (plan.scan.table,
            tuple(sorted(plan.preds)),
            tuple((shared_join_key(j), j.mult) for j in plan.joins),
            (proj.m1, proj.m2, proj.op),
            plan.n_groups)


def shared_footprint(plans: List[P.Plan]):
    """The union streams of a shared wave, exactly as the kernel loads
    them: predicate columns (deduplicated by name), joins (deduplicated
    by :func:`shared_join_key`; two distinct build sides on the same
    fact FK are two probe streams AND two key loads), measure columns
    (deduplicated by name — a column that is both predicate and measure
    is still two streams, matching the solo fused kernel's accounting).

    Returns ``(col_ix, join_nodes, mcol_ix)`` — ordered name->index maps
    for predicate/measure columns and the deduplicated join list.  The
    single owner of the union/dedup rule: ``shared_params`` builds the
    kernel parameters from it, ``model.predict_shared`` prices it, and
    the ``shared_throughput`` benchmark reports it."""
    col_ix: Dict[str, int] = {}
    join_ix: Dict[Tuple, int] = {}
    join_nodes: List[P.HashJoin] = []
    mcol_ix: Dict[str, int] = {}
    for plan in plans:
        for col, _, _ in plan.preds:
            col_ix.setdefault(col, len(col_ix))
        for j in plan.joins:
            k = shared_join_key(j)
            if k not in join_ix:
                join_ix[k] = len(join_nodes)
                join_nodes.append(j)
        proj = plan.project
        mcol_ix.setdefault(proj.m1, len(mcol_ix))
        if proj.m2 is not None:
            mcol_ix.setdefault(proj.m2, len(mcol_ix))
    return col_ix, join_nodes, mcol_ix


def validate_wave(plans: List[P.Plan]) -> None:
    """Raise ``ValueError`` unless ``plans`` form a legal shared wave:
    non-empty, all scanning the same fact table, every member shareable.
    Group validation is ultimately the caller's contract — the server
    filters before calling — but both the lowering and the morsel fold
    check it up front so a bad group fails with the reason, not an
    attribute error mid-footprint."""
    if not plans:
        raise ValueError("shared wave must contain at least one plan")
    table = plans[0].scan.table
    for plan in plans:
        if plan.scan.table != table:
            raise ValueError(
                f"shared wave is scan-incompatible: {plan.name} scans "
                f"{plan.scan.table!r}, wave scans {table!r}")
        reason = shareability(plan)
        if reason is not None:
            raise ValueError(f"{plan.name} cannot join a shared wave: "
                             f"{reason}")


def shared_params(plans: List[P.Plan], db: ssb.Database,
                  cache: Optional[HT.HashTableCache] = None,
                  pad_to: Optional[int] = None,
                  prebuilt: Optional[Dict[Tuple, Tuple]] = None,
                  fact=None,
                  anchor: Optional[List[P.Plan]] = None):
    """Lower a group of shareable plans over one fact table to the
    stacked parameter arrays of ``ops.multi_spja``.

    ``anchor`` widens the lowered *footprint* (union predicate columns,
    probe streams, measure columns, group span) to cover the given plan
    pool without adding members: anchor-only columns get all-pass
    bounds, anchor-only joins get ``use``/``mult`` zero for every real
    member.  A serving loop that anchors every wave on its known query
    pool maps ANY member subset onto one executable per pow2 member
    bucket — fixed shapes bought with inert lanes, exactly the
    LM-server padding trade.  Callers pass a pre-filtered anchor
    (:func:`anchor_for`); ``None`` lowers the wave-only footprint and
    is bit-identical to the unanchored path.

    Returns ``(fact, args, kwargs, n_groups)`` where ``args`` are the
    positional arguments of the kernel and ``kwargs`` its stream
    encoding keywords (per-column widths + frame-of-reference arrays).  Raises on a group that is not
    scan-compatible (different fact tables) or contains an unshareable
    member — group validation is the caller's contract; the server
    filters before calling.

    ``prebuilt`` maps :func:`shared_join_key` to an already-built
    ``(htk, htv)`` pair: a caller that built the wave's tables itself
    (the server does, per member, for fault isolation and per-request
    hit/miss attribution) passes them through so the lowering does not
    re-fetch from the cache and double-count its hit stats."""
    validate_wave(plans)
    table = plans[0].scan.table
    if fact is None:
        fact = getattr(db, table)
    q_n = len(plans)
    q_pad = max(q_n, pad_to or q_n)
    foot = list(plans) + list(anchor or [])
    col_ix, join_nodes, mcol_ix = shared_footprint(foot)
    if anchor:
        # canonical stream order: footprint maps insert wave members
        # first, so two waves over the same anchored union would still
        # lower their streams in different positions — different static
        # width tuples and packed-stream shapes, hence one executable
        # per member ORDER instead of one per pow2 bucket.  Sorting
        # makes the whole parameterization membership-invariant.
        col_ix = {c: i for i, c in enumerate(sorted(col_ix))}
        join_nodes = sorted(join_nodes,
                            key=lambda j: repr(shared_join_key(j)))
        mcol_ix = {c: i for i, c in enumerate(sorted(mcol_ix))}
    join_ix = {shared_join_key(j): ji for ji, j in enumerate(join_nodes)}

    # per-member bounds over the union predicate columns, intersected
    # when one member filters the same column twice; all-pass for
    # non-filtering members (the kernel evaluates every union column for
    # every member).  Intersection happens in the ORIGINAL domain, then
    # each column's bounds are rewritten into its encoded domain (packed
    # lanes are compared raw — the compile-time predicate rewrite).
    bounds = np.empty((q_pad, len(col_ix), 2), np.int64)
    bounds[..., 0] = _INT32_MIN
    bounds[..., 1] = _INT32_MAX
    for qi, plan in enumerate(plans):
        for col, lo, hi in plan.preds:
            ci = col_ix[col]
            bounds[qi, ci, 0] = max(bounds[qi, ci, 0], lo)
            bounds[qi, ci, 1] = min(bounds[qi, ci, 1], hi)
    for col, ci in col_ix.items():
        enc = ST.encoding_of(fact, col)
        if enc is not None and enc.kind != "plain":
            bounds[:, ci, :] -= enc.ref
    bounds = np.clip(bounds, _INT32_MIN, _INT32_MAX).astype(np.int32)

    # deduplicated joins: one probe stream per distinct (fact FK,
    # logical build side), per-member use/mult as data
    mults = np.zeros((q_pad, len(join_nodes)), np.int32)
    use = np.zeros((q_pad, len(join_nodes)), np.int32)
    for qi, plan in enumerate(plans):
        for j in plan.joins:
            ji = join_ix[shared_join_key(j)]
            use[qi, ji] = 1
            mults[qi, ji] += j.mult
    key_streams = [ST.column_stream(fact, j.fact_col) for j in join_nodes]
    join_keys = [s[0] for s in key_streams]
    key_widths = tuple(s[1] for s in key_streams)
    key_refs = ST.upload(np.array([s[2] for s in key_streams], np.int32))
    join_tables: List[jnp.ndarray] = []
    for j in join_nodes:
        k = shared_join_key(j)
        if prebuilt is not None and k in prebuilt:
            htk, htv = prebuilt[k]
        elif cache is not None:
            htk, htv = cache.get_or_build(db, j)
        else:
            htk, htv = HT.build_dim_table(db, j)
        join_tables.extend([htk, htv])

    # per-member (m1, m2, op) selectors into the union measure columns
    msel = np.zeros((q_pad, 3), np.int32)
    for qi, plan in enumerate(plans):
        proj = plan.project
        msel[qi, 0] = mcol_ix[proj.m1]
        if proj.m2 is not None:
            msel[qi, 1] = mcol_ix[proj.m2]
        msel[qi, 2] = _MEASURE_OP_CODE[proj.op]
    m_streams = [ST.column_stream(fact, c) for c in mcol_ix]
    measure_cols = [arr for arr, _, _ in m_streams]
    m_widths = tuple(w for _, w, _ in m_streams)
    m_refs = ST.upload(np.array([r for _, _, r in m_streams], np.int32))

    q_valid = np.zeros(q_pad, np.int32)
    q_valid[:q_n] = 1
    n_groups = max(plan.n_groups for plan in foot)
    pred_streams = [ST.column_stream(fact, c) for c in col_ix]
    args = ([s[0] for s in pred_streams], ST.upload(bounds),
            join_keys, join_tables, ST.upload(mults), ST.upload(use),
            ST.upload(q_valid), measure_cols, ST.upload(msel))
    kwargs = dict(pred_widths=tuple(s[1] for s in pred_streams),
                  key_widths=key_widths, key_refs=key_refs,
                  m_widths=m_widths, m_refs=m_refs, n_rows=fact.n_rows)
    return fact, args, kwargs, n_groups


def anchor_for(plans: List[P.Plan],
               pool: Optional[List[P.Plan]]) -> Optional[List[P.Plan]]:
    """Filter a footprint-anchor pool down to the plans that could
    legally share this wave's scan — same fact table, shareable — so an
    anchored lowering never widens the footprint with streams the
    kernel could not load.  Returns ``None`` when nothing survives (the
    unanchored path)."""
    if not pool:
        return None
    table = plans[0].scan.table
    kept = [p for p in pool
            if p.scan.table == table and shareability(p) is None]
    return kept or None


def _shared_prebuilt(plans: List[P.Plan], db,
                     cache: Optional[HT.HashTableCache],
                     prebuilt: Optional[Dict[Tuple, Tuple]]
                     ) -> Dict[Tuple, Tuple]:
    """Complete a wave's join-table map (one build per distinct probe
    identity, respecting whatever the caller prebuilt) so the morsel
    fold never re-fetches per morsel."""
    _, join_nodes, _ = shared_footprint(plans)
    tables = dict(prebuilt) if prebuilt else {}
    for j in join_nodes:
        k = shared_join_key(j)
        if k not in tables:
            tables[k] = (cache.get_or_build(db, j) if cache is not None
                         else HT.build_dim_table(db, j))
    return tables


def execute_shared_morsels(plans: List[P.Plan], db: ssb.Database,
                           mode: str = "auto", tile: Optional[int] = None,
                           cache: Optional[HT.HashTableCache] = None,
                           pad_to: Optional[int] = None,
                           prebuilt: Optional[Dict[Tuple, Tuple]] = None,
                           morsel_bytes: int = MS.DEFAULT_MORSEL_BYTES,
                           anchor: Optional[List[P.Plan]] = None
                           ) -> Tuple[List[np.ndarray], MS.MorselReport]:
    """:func:`execute_shared` as a fold over the morsel stream: the wave
    streams each morsel ONCE (one ``multi_spja`` launch per morsel, so
    the shared-scan win multiplies with the out-of-core bound), the
    per-morsel ``(Q, n_groups)`` partial grids tree-merge exactly, and
    the dim tables build once up front.  Returns ``(results, report)``
    with each member's ``(n_groups,)`` f32 result in submission order.
    ``anchor`` (a plan pool, see :func:`shared_params`) pins the lowered
    footprint so any member subset reuses one executable per pow2
    member bucket."""
    validate_wave(plans)
    reset_launch_config()
    tile = _launch("multi_spja", tile, mode=mode)
    anchor = anchor_for(plans, anchor)
    foot = list(plans) + list(anchor or [])
    col_ix, join_nodes, mcol_ix = shared_footprint(foot)
    tables = _shared_prebuilt(foot, db, cache, prebuilt)
    fact = getattr(db, plans[0].scan.table)
    cols = list(col_ix)
    cols += [j.fact_col for j in join_nodes if j.fact_col not in cols]
    cols += [c for c in mcol_ix if c not in cols]
    stream = MS.MorselStream(fact, morsel_bytes, cols=cols)
    report = MS.MorselReport()
    if stream.n_morsels == 0:           # empty fact: all-zero grids
        report.observe(0)
        return [np.zeros(plan.n_groups, np.float32)
                for plan in plans], report

    def run(m):
        _, args, kwargs, n_groups = shared_params(
            plans, db, cache=None, pad_to=pad_to, prebuilt=tables,
            fact=m.table, anchor=anchor)
        LAUNCH_STATS["probe"] += 1      # one whole-wave launch per morsel
        FLT.maybe_fault("kernel")
        with SP.span(SP.DISPATCH):
            out = ops.multi_spja(*args, n_groups=n_groups, mode=mode,
                                 tile=tile, **kwargs)
        with SP.span(SP.PULL):
            return np.asarray(out)

    partials = stream.fold(run, report)
    out = partials[0] if len(partials) == 1 else SH.tree_merge(partials)
    return [out[qi, :plan.n_groups].copy()
            for qi, plan in enumerate(plans)], report


def execute_shared(plans: List[P.Plan], db: ssb.Database,
                   mode: str = "auto", tile: Optional[int] = None,
                   cache: Optional[HT.HashTableCache] = None,
                   pad_to: Optional[int] = None,
                   prebuilt: Optional[Dict[Tuple, Tuple]] = None
                   ) -> List[np.ndarray]:
    """Execute a scan-compatible group of aggregate plans as one shared
    fused pass per morsel over their common fact table; returns each
    member's ``(n_groups,)`` f32 result in submission order.  Under the
    default budget every current database is a single morsel, so this
    is the single-launch wave it always was.

    ``pad_to`` pads the stacked member dimension with inert slots so one
    jitted executable serves any member count up to the wave size (the
    padded members contribute nothing — their validity bit is 0)."""
    results, _ = execute_shared_morsels(plans, db, mode=mode, tile=tile,
                                        cache=cache, pad_to=pad_to,
                                        prebuilt=prebuilt)
    return results


def execute_shared_sharded(plans: List[P.Plan], db,
                           mode: str = "auto", tile: Optional[int] = None,
                           cache: Optional[HT.HashTableCache] = None,
                           pad_to: Optional[int] = None,
                           prebuilt: Optional[Dict[Tuple, Tuple]] = None,
                           morsel_bytes: int = MS.DEFAULT_MORSEL_BYTES,
                           anchor: Optional[List[P.Plan]] = None
                           ) -> Tuple[List[np.ndarray], List[float],
                                      MS.MorselReport]:
    """Shared-scan wave over a sharded fact table: PR 4's wave formation
    composed with sharding, each shard folding its own morsel stream.
    Each shard runs the whole wave one ``multi_spja`` pass per morsel
    (the dim tables are built once — the cache binds every shard replica
    to the base database), then the per-shard ``(Q, n_groups)`` partial
    grids tree-merge on the host.  Returns
    ``(results_in_submission_order, shard_times_s, morsel_report)``.

    The merge is the host path by construction — a wave's stacked
    parameters are per-shard anyway (bounds/mults/selectors are
    replicated, streams are not), and the host tree merge is
    bit-identical to a mesh ``psum`` on SSB's exact f32 partials."""
    if not isinstance(db, SH.ShardedDatabase) or db.n_shards == 1:
        base = SH.base_of(db)
        t0 = time.perf_counter()
        results, report = execute_shared_morsels(
            plans, base, mode=mode, tile=tile, cache=cache, pad_to=pad_to,
            prebuilt=prebuilt, morsel_bytes=morsel_bytes, anchor=anchor)
        return results, [time.perf_counter() - t0], report
    tables = _shared_prebuilt(plans, db, cache, prebuilt)
    partials, times = [], []
    report = MS.MorselReport()
    for shard in db.shards:
        t0 = time.perf_counter()
        shard_results, rep = execute_shared_morsels(
            plans, shard, mode=mode, tile=tile, cache=None,
            pad_to=pad_to, prebuilt=tables, morsel_bytes=morsel_bytes,
            anchor=anchor)
        partials.append(np.stack(
            [np.pad(r, (0, max(p.n_groups for p in plans) - len(r)))
             for r in shard_results]))
        times.append(time.perf_counter() - t0)
        report = report.merge(rep)
    out = SH.tree_merge(partials)
    return ([out[qi, :plan.n_groups].copy()
             for qi, plan in enumerate(plans)], times, report)


# ---------------------------------------------------------------------------
# operator-at-a-time / partitioned lowering (materializing engine model)
# ---------------------------------------------------------------------------


def _probe_whole(node: P.HashJoin, fact, db, rowids, group, mode, tile,
                 cache) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """opat join: one probe of the monolithic dim table; matched positions
    come back as a selection vector and the live columns are gathered
    through it."""
    htk, htv = (cache.get_or_build(db, node) if cache is not None
                else HT.build_dim_table(db, node))
    col, width, ref = ST.column_stream(fact, node.fact_col)
    LAUNCH_STATS["probe"] += 1
    FLT.maybe_fault("kernel")
    rowids, group, cnt = _probe_step(
        col, rowids, group, htk, htv, jnp.int32(node.mult),
        jnp.int32(ref), width=width, mode=mode,
        tile=_launch("probe_join", tile, mode=mode))
    cnt = int(cnt)
    return rowids[:cnt], group[:cnt]


@functools.partial(jax.jit, static_argnames=("width", "mode", "tile"))
def _probe_step(col, rowids, group, htk, htv, mult, ref, *, width, mode,
                tile):
    """One opat join as one executable (one compile per live-row count,
    not one per eager op): gather the live rows' keys (``ST.take``'s
    decode), probe, and gather the live columns through the selection
    vector.  Rows past the returned count are padding."""
    keys = gather_decode(col, rowids, width, ref)
    payload, sel, cnt = ops.probe_join(
        keys, jnp.arange(rowids.shape[0], dtype=jnp.int32), htk, htv,
        mode=mode, tile=tile)
    return rowids[sel], group[sel] + payload * mult, cnt


@functools.partial(jax.jit, static_argnames=("mode", "tile"))
def _probe_join_jit(keys, vals, htk, htv, mode, tile):
    """probe_join under jit: the ref path's eager ``lax.while_loop``
    dispatches every probe iteration separately, which multiplied by
    2^bits partitions dominates the partitioned join; jitting collapses
    each (shape, table-size) combination to one cached executable."""
    return ops.probe_join(keys, vals, htk, htv, mode=mode, tile=tile)


def _part_bits_of(node: P.HashJoin, db, cache) -> Tuple[int, Optional[tuple]]:
    """Radix bits for one join's partitioned lowering (+ the filtered
    build side when it had to be computed because no cache was given)."""
    from repro.sql import model as M
    if cache is not None:
        return M.part_bits(cache.get_build_count(db, node)), None
    side = HT.filtered_build_side(db, node)
    return M.part_bits(len(side[0])), side


def _probe_part_fused(node: P.HashJoin, fact, db, rowids, group, mode,
                      tile, cache) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """part join (paper §4.4), fused probe: bucket both sides by the
    key's low radix bits, then probe every partition in ONE kernel launch
    — the grid iterates over partitions, each step windows its
    partition's table from the packed ``(P, S)`` layout and walks its
    slice of the shuffled probe arrays (``kernels/part_probe.py``).

    The probe side moves in one multi-payload shuffle pass (row ids and
    the running group id ride along with the key); partition boundaries
    are a device-side bincount of the shuffled keys' low bits; shuffle,
    histogram and probe are traced as ONE executable
    (``ops.part_join``) — no host round-trip anywhere between the
    fact-column gather and the final count read.  Surviving rows come
    back partition-major, exactly the order the host loop produced."""
    bits, side = _part_bits_of(node, db, cache)
    packed = (cache.get_or_build_parts(db, node, bits, packed=True)
              if cache is not None else
              HT.build_dim_partitions(db, node, bits, side=side,
                                      packed=True))
    col, width, colref = ST.column_stream(fact, node.fact_col)
    LAUNCH_STATS["partition"] += 1      # the shuffle pass inside part_join
    LAUNCH_STATS["probe"] += 1          # the single fused probe launch
    digit = TN.tuned_digit()            # host shuffle's tuned pass width
    outr, outg, cnt = ops.part_join(
        col, rowids, group, packed.htk, packed.htv, node.mult, bits,
        mode=mode, tile=_launch("part_probe", tile, mode=mode, bits=bits,
                                digit=digit),
        width=width, ref=colref, digit=digit)
    LAUNCH_STATS["host_syncs"] += 1
    cnt = int(cnt)                      # the one device->host sync
    return outr[:cnt], outg[:cnt]


def _probe_part_loop(node: P.HashJoin, fact, db, rowids, group, mode,
                     tile, cache) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """part join, host-orchestrated probe loop — the pre-fusion baseline
    (strategy ``part_loop``), kept for A/B measurement of the fused
    kernel's dispatch-overhead win (fig8).

    Bucketing is identical to ``_probe_part_fused``; the probe phase then
    runs partition-at-a-time from the host: probe batches are padded to a
    power of two so XLA compiles O(log n) probe shapes instead of one per
    partition, and pad rows are discarded by position (they sit at the
    tail of the stable selection vector, so any phantom pad hit is
    filtered regardless of the pad key's value).  Surviving rows come
    back partition-major (fine for aggregates; row plans never take this
    lowering — see ``partability``)."""
    bits, side = _part_bits_of(node, db, cache)
    parts = (cache.get_or_build_parts(db, node, bits)
             if cache is not None else
             HT.build_dim_partitions(db, node, bits, side=side))
    keys = ST.take(fact, node.fact_col, rowids)
    LAUNCH_STATS["partition"] += 1
    outk, (orow, ogrp) = ops.radix_partition_multi(
        keys, (rowids, group), 0, bits,
        mode=mode, tile=_launch("partition_multi", tile, mode=mode,
                                op="radix_partition", bits=bits))
    LAUNCH_STATS["host_syncs"] += 3
    outk_h = np.asarray(outk)
    orow_h = np.asarray(orow)
    ogrp_h = np.asarray(ogrp)
    # partition boundaries: host-side bucket counts of the shuffled keys
    counts = np.bincount(outk_h & ((1 << bits) - 1), minlength=1 << bits)
    ends = np.cumsum(counts)
    mult = np.int32(node.mult)
    out_rows, out_grps = [], []
    for p in range(1 << bits):
        s, e = int(ends[p] - counts[p]), int(ends[p])
        if s == e:
            continue
        n_real = e - s
        n_pad = 1 << (n_real - 1).bit_length()      # smallest pow2 >= n
        pk = np.zeros(n_pad, np.int32)
        pk[:n_real] = outk_h[s:e]
        htk, htv = parts[p]
        LAUNCH_STATS["probe"] += 1
        payload, sel, cnt = _probe_join_jit(
            jnp.asarray(pk), jnp.arange(n_pad, dtype=jnp.int32),
            htk, htv, mode=mode,
            tile=_launch("probe_join", tile, mode=mode))
        LAUNCH_STATS["host_syncs"] += 3
        cnt = int(cnt)
        if cnt == 0:
            continue
        sel_h = np.asarray(sel)[:cnt]
        pay_h = np.asarray(payload)[:cnt]
        real = sel_h < n_real           # drop phantom pad-row hits
        sel_h = sel_h[real]
        out_rows.append(orow_h[s:e][sel_h])
        out_grps.append(ogrp_h[s:e][sel_h] + pay_h[real] * mult)
    if not out_rows:
        z = jnp.zeros((0,), jnp.int32)
        return z, z
    return (jnp.asarray(np.concatenate(out_rows)),
            jnp.asarray(np.concatenate(out_grps)))


_JOIN_LOWERINGS = {
    "opat": _probe_whole,
    "part": _probe_part_fused,
    "part_loop": _probe_part_loop,
}


def _execute_chain(plan: P.Plan, db: ssb.Database, mode: str,
                   tile: Optional[int],
                   cache: Optional[HT.HashTableCache],
                   join_mode: str = "opat", fact=None,
                   defer_order: bool = False,
                   partial_agg: bool = False):
    """Shared operator-at-a-time chain walker; ``join_mode`` selects the
    HashJoin lowering — monolithic probe (``opat``), fused partitioned
    probe (``part``), or the host partition loop (``part_loop``);
    everything else — filters, projection, aggregation, ordering — is
    identical.

    The morsel fold drives the two hooks: ``fact`` substitutes one
    morsel for the plan's scan table, ``partial_agg`` returns the
    pre-aggregation ``GroupPartial`` instead of the summed grid (merged
    across morsels via ``SH.merge_partials``), and ``defer_order`` skips
    a trailing OrderBy so the fold can run ONE global sort over the
    concatenated survivors (opat probes preserve row order, so the
    global sort over per-morsel survivors is bit-identical to the
    whole-table sort)."""
    if fact is None:
        fact = getattr(db, plan.scan.table)
    n = fact.n_rows
    join_fn = _JOIN_LOWERINGS[join_mode]
    # live intermediate state, re-materialized by every operator:
    rowids = jnp.arange(n, dtype=jnp.int32)
    group = jnp.zeros((n,), jnp.int32)
    measure = None
    dense = True        # rowids still the identity: the leading filter
    #   on a packed column can select straight off the word stream
    #   (ops.select_scan_packed) with no gather and no decode pass

    for node in plan.chain[1:]:
        empty = int(rowids.shape[0]) == 0
        if isinstance(node, P.Filter):
            for pred in node.preds:
                if int(rowids.shape[0]) == 0:
                    break
                if isinstance(pred, (P.RangePred, P.EqPred)):
                    col, lo, hi = P.range_bounds(pred)
                    enc = ST.encoding_of(fact, col)
                    if dense and enc is not None and enc.kind != "plain":
                        # decode-on-scan over the packed words; bounds
                        # rewritten into the encoded domain
                        lo2, hi2 = ST.encoded_bounds(enc, lo, hi)
                        words, phys, _ = ST.column_stream(fact, col)
                        out, cnt = ops.select_scan_packed(
                            words, rowids, lo2, hi2, phys, mode=mode,
                            tile=_launch("select_scan", tile, width=phys,
                                         mode=mode))
                        out = out[:int(cnt)]
                        group = group[out]  # identity rowids: value==pos
                        rowids = out
                        dense = False
                        continue
                    x = ST.take(fact, col, rowids)
                    # emit a selection vector, then gather each live
                    # column through it — the materialization traffic
                    # the fused path avoids
                    sel, cnt = ops.select_scan(
                        x, jnp.arange(rowids.shape[0], dtype=jnp.int32),
                        lo, hi, mode=mode,
                        tile=_launch("select_scan", tile, mode=mode))
                    sel = sel[:int(cnt)]
                    rowids = rowids[sel]
                    group = group[sel]
                else:                       # generic predicate: host mask
                    keep = jnp.asarray(P.pred_mask(pred, fact))[rowids]
                    rowids = rowids[keep]
                    group = group[keep]
                dense = False
        elif isinstance(node, P.HashJoin):
            dense = False
            if empty:
                continue
            rowids, group = join_fn(node, fact, db, rowids, group, mode,
                                    tile, cache)
        elif isinstance(node, P.Project):
            # integer measures: group_sum sums them exactly
            m = ST.take(fact, node.m1, rowids)
            if node.op == "mul":
                m = m * ST.take(fact, node.m2, rowids)
            elif node.op == "sub":
                m = m - ST.take(fact, node.m2, rowids)
            measure = m
        elif isinstance(node, P.GroupAgg):
            if partial_agg:
                if empty:
                    return SH.GroupPartial(
                        np.zeros(node.n_groups, np.float32),
                        np.zeros(node.n_groups, np.int64))
                return SH.GroupPartial.from_rows(
                    np.asarray(group), np.asarray(measure), node.n_groups)
            if empty:
                return np.zeros(node.n_groups, np.float32)
            out = ops.group_sum(group, measure, node.n_groups, mode=mode,
                                tile=_launch("group_sum", tile, mode=mode))
            return np.asarray(out, np.float32)
        elif isinstance(node, P.OrderBy):
            if defer_order or empty:
                break
            keys = ST.take(fact, node.key_col, rowids)
            r = TN.tuned_r()
            _, rowids = ops.radix_sort(keys, rowids, mode=mode, r=r,
                                       tile=_launch("radix_sort", tile,
                                                    mode=mode, r=r))
        else:
            raise TypeError(f"{plan.name}: cannot lower node {node!r}")

    # only row plans (classify()-checked at compile time) fall through
    return np.asarray(rowids)


def _chain_scan_cols(plan: P.Plan) -> Optional[List[str]]:
    """The fact columns a chain lowering touches, or None when a
    generic predicate hides its column set (then the morsel budget is
    sized over the whole row — conservative, never under-counts)."""
    cols: List[str] = []

    def add(c):
        if c is not None and c not in cols:
            cols.append(c)

    for node in plan.chain[1:]:
        if isinstance(node, P.Filter):
            for pred in node.preds:
                col = getattr(pred, "col", None)
                if col is None:
                    return None
                add(col)
        elif isinstance(node, P.HashJoin):
            add(node.fact_col)
        elif isinstance(node, P.Project):
            add(node.m1)
            add(node.m2)
        elif isinstance(node, P.OrderBy):
            add(node.key_col)
    return cols


def _chain_morsels(plan: P.Plan, db: ssb.Database, mode: str,
                   tile: Optional[int],
                   cache: Optional[HT.HashTableCache], join_mode: str,
                   morsel_bytes: int
                   ) -> Tuple[np.ndarray, MS.MorselReport]:
    """The materializing lowerings (opat/part/part_loop) as a fold over
    the morsel stream.  Aggregate plans fold each morsel's
    pre-aggregation state into a ``GroupPartial`` and merge exactly
    (``SH.merge_partials`` — PR 6's shard merge, reused unchanged); row
    plans concatenate per-morsel survivors (offset to global row ids; a
    trailing OrderBy is DEFERRED to one global sort over the
    concatenated survivors, bit-identical because opat probes preserve
    row order).  A single-morsel stream takes the pre-refactor chain
    byte-for-byte."""
    fact = getattr(db, plan.scan.table)
    stream = MS.MorselStream(fact, morsel_bytes,
                             cols=_chain_scan_cols(plan))
    report = MS.MorselReport()
    kind = classify(plan)
    if stream.n_morsels == 0:
        report.observe(0)
        return _execute_chain(plan, db, mode, tile, cache,
                              join_mode=join_mode, fact=fact), report
    if stream.n_morsels == 1:
        out = stream.fold(
            lambda m: _execute_chain(plan, db, mode, tile, cache,
                                     join_mode=join_mode, fact=m.table),
            report)[0]
        return out, report
    if kind == "agg":
        partials = stream.fold(
            lambda m: _execute_chain(plan, db, mode, tile, cache,
                                     join_mode=join_mode, fact=m.table,
                                     partial_agg=True),
            report)
        return SH.merge_partials(partials).finalize("sum"), report
    order_node = next((nd for nd in plan.chain
                       if isinstance(nd, P.OrderBy)), None)

    def run(m):
        rows = np.asarray(_execute_chain(plan, db, mode, tile, cache,
                                         join_mode=join_mode,
                                         fact=m.table, defer_order=True))
        if order_node is not None and len(rows):
            keys = np.asarray(ST.take(m.table, order_node.key_col,
                                      jnp.asarray(rows)))
        else:
            keys = np.zeros(len(rows), np.int32)
        return (rows + np.int32(m.offset)).astype(np.int32), keys

    pieces = stream.fold(run, report)
    rowids = np.concatenate([p[0] for p in pieces])
    if order_node is None or len(rowids) == 0:
        return rowids, report
    keys = np.concatenate([p[1] for p in pieces])
    r = TN.tuned_r()
    _, out = ops.radix_sort(jnp.asarray(keys), jnp.asarray(rowids),
                            mode=mode, r=r,
                            tile=_launch("radix_sort", tile, mode=mode,
                                         r=r))
    return np.asarray(out), report


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@dataclass
class CompiledQuery:
    """An executable lowering of a logical plan.

    ``strategy`` is the strategy that will actually run; when the caller
    asked for ``fused``/``part`` on a plan that lowering cannot express,
    ``strategy == "opat"`` and ``fallback_reason`` says why.

    ``strategy == "auto"`` defers the choice to the bandwidth cost model
    at execute time (cardinalities need the database); after ``execute``,
    ``decided`` holds the strategy that ran and ``predictions`` the
    model's per-strategy predicted seconds (for "fixed" strategies,
    ``decided`` is just the strategy).

    After a ``sharded`` execution, ``device_count`` holds the shard
    count that ran and ``shard_times_s`` the per-shard wall times (one
    entry for the whole launch on the ``shard_map`` path, which the
    host cannot decompose).

    Every execution streams the fact table through the morsel spine
    (``repro.sql.morsel``; ``morsel_bytes`` bounds the per-buffer
    footprint): afterwards ``n_morsels`` holds the stream length and
    ``peak_resident_bytes`` the observed double-buffer peak — the
    out-of-core bound, ``<= 2 × morsel_bytes`` up to one lane of
    rounding.  Under the default budget small databases are one morsel
    and the execution is byte-for-byte the in-memory pass.
    """
    plan: P.Plan
    strategy: str
    requested: str
    fallback_reason: Optional[str] = None
    decided: Optional[str] = None
    predictions: Optional[Dict[str, float]] = field(default=None,
                                                    repr=False)
    device_count: Optional[int] = None
    shard_times_s: Optional[List[float]] = field(default=None, repr=False)
    n_morsels: Optional[int] = None
    peak_resident_bytes: Optional[int] = None
    # per-family launch configuration the last execute actually used
    # (tile / radix width / partition depth + source: explicit argument,
    # tune store, or shipped default) — snapshot of LAUNCH_CONFIG
    launch_config: Optional[Dict[str, Dict]] = field(default=None,
                                                     repr=False)

    def _note(self, report: MS.MorselReport) -> None:
        self.n_morsels = report.n_morsels
        self.peak_resident_bytes = report.peak_resident_bytes

    def execute(self, db: ssb.Database, mode: str = "auto",
                tile: Optional[int] = None,
                cache: Optional[HT.HashTableCache] = None,
                morsel_bytes: int = MS.DEFAULT_MORSEL_BYTES) -> np.ndarray:
        """``tile=None`` launches every kernel at its tuned (or default)
        configuration; an explicit tile pins every family to it."""
        reset_launch_config()
        try:
            return self._execute(db, mode, tile, cache, morsel_bytes)
        finally:
            self.launch_config = snapshot_launch_config()

    def _execute(self, db: ssb.Database, mode: str, tile: Optional[int],
                 cache: Optional[HT.HashTableCache],
                 morsel_bytes: int) -> np.ndarray:
        strategy = self.strategy
        if strategy == "auto":
            from repro.sql import model as M
            with SP.span(SP.PLAN):
                choice = M.choose(self.plan, db,
                                  n_shards=SH.shard_count(db),
                                  morsel_bytes=morsel_bytes)
            strategy = choice.strategy
            self.predictions = choice.predictions
        self.decided = strategy
        if strategy == "sharded":
            out, times, dc, report = _execute_sharded(
                self.plan, db, mode, tile, cache,
                morsel_bytes=morsel_bytes)
            self.shard_times_s, self.device_count = times, dc
            self._note(report)
            return out
        base = SH.base_of(db)
        if strategy == "fused":
            out, report = _fused_morsels(self.plan, base, mode, tile,
                                         cache, morsel_bytes)
            self._note(report)
            return out
        if strategy == "shared":        # degenerate 1-member wave
            results, report = execute_shared_morsels(
                [self.plan], base, mode=mode, tile=tile, cache=cache,
                morsel_bytes=morsel_bytes)
            self._note(report)
            return results[0]
        out, report = _chain_morsels(
            self.plan, base, mode, tile, cache,
            join_mode=(strategy if strategy in _JOIN_LOWERINGS
                       else "opat"),
            morsel_bytes=morsel_bytes)
        self._note(report)
        return out

    __call__ = execute


def compile_plan(plan: P.Plan, strategy: str = "fused") -> CompiledQuery:
    """Validate + lower ``plan``.  ``strategy``:

    * ``fused`` — Crystal single-kernel lowering; falls back to ``opat``
      (with ``fallback_reason`` set) when the plan is not fusable.
    * ``opat``  — force operator-at-a-time lowering.
    * ``part``  — radix-partitioned joins, single fused probe launch per
      join; falls back to ``opat`` (reason set) when nothing is
      partitionable.
    * ``part_loop`` — radix-partitioned joins, host partition-at-a-time
      probe loop (the fused kernel's A/B baseline); same fallback rule
      and reason reporting as ``part``.
    * ``sharded`` — fused kernel per fact shard + tree-reduced partial
      aggregates; same fusability constraints (and fallback rule) as
      ``fused`` — on an unsharded database it degenerates to the solo
      fused pass.
    * ``auto``  — defer to the bandwidth cost model per database at
      execute time.
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; "
                         f"expected one of {STRATEGIES}")
    if strategy == "fused":
        reason = fusability(plan)       # classifies; raises on malformed
        if reason is None:
            return CompiledQuery(plan, "fused", "fused")
        return CompiledQuery(plan, "opat", "fused", fallback_reason=reason)
    if strategy == "sharded":
        reason = shardability(plan)     # classifies; raises on malformed
        if reason is None:
            return CompiledQuery(plan, "sharded", "sharded")
        return CompiledQuery(plan, "opat", "sharded",
                             fallback_reason=reason)
    if strategy == "shared":
        reason = shareability(plan)     # classifies; raises on malformed
        if reason is None:
            return CompiledQuery(plan, "shared", "shared")
        return CompiledQuery(plan, "opat", "shared",
                             fallback_reason=reason)
    if strategy in ("part", "part_loop"):
        reason = partability(plan)      # classifies; raises on malformed
        if reason is None:
            return CompiledQuery(plan, strategy, strategy)
        return CompiledQuery(plan, "opat", strategy,
                             fallback_reason=reason)
    classify(plan)                      # raise on malformed chains
    return CompiledQuery(plan, strategy, strategy)
