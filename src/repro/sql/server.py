"""Batched query-serving engine: queue -> waves of compiled plans ->
execute, with a keyed cache of built dimension hash tables.

Mirrors the wave pattern of ``serve/engine.py`` (the LM batch server):
submitted requests queue up, ``run()`` drains the queue in *waves*, and
every wave executes against a shared ``HashTableCache``.  Scheduling is
sequential on the host (one device stream, like the LM server's wave
loop): the concurrency story is many *queued* clients sharing one
resident database, amortized builds, and per-wave batching — not
thread-level overlap.

Waves are bucketed by **scan-compatibility**, not just by requested
strategy: requests whose strategy is ``shared`` (or ``auto``) and whose
plan is shareable — an aggregate SPJA plan the fused kernel could run —
are grouped by the fact table they scan, and a compatible wave executes
as ONE shared fused pass (``compile.execute_shared``): the fact table is
streamed once per wave, each deduplicated dim hash table is probed once
for every member, and each member's ``QueryResult`` reports the wave it
rode in via ``shared_wave_size``.  That is the serving analogue of the
paper's operator-fusion result: N concurrent queries stop costing N full
fact-table scans.  ``auto`` waves consult the cost model's
shared-vs-solo term (``model.predict_shared``) and fall back to
per-query execution when sharing does not pay (e.g. a single-member
wave).  Everything else — fixed ``fused``/``opat``/``part`` requests,
row plans, unshareable plans — buckets by strategy as before.

Wave sizing is *enforced*, not assumed: the shared kernel's
``(Q_padded, n_groups)`` f32 accumulator must fit ``acc_budget_bytes``
of VMEM, so ``_waves()`` splits a bucket when padded-member-count x
group-count would blow it (``stats["budget_splits"]``); and identical
members inside a wave (``compile.shared_member_key``) aggregate ONCE,
with the result fanned out per duplicate (``stats["dedup_saved"]``).

Repeated queries (or distinct queries sharing a join build side, e.g.
every SSB flight's ``date`` join) skip the hash-table build phase
entirely; the cache's hit/miss stats quantify the saved build work, the
serving analogue of the paper's observation that dimension builds are
amortizable setup rather than per-query cost.

The resident database may be a *packed* one
(``repro.sql.storage.pack_database``): every strategy consumes the
compressed word streams directly (decode-on-scan), results are
bit-identical to plain storage, and each ``QueryResult`` reports the
scan's encoded vs nominal bytes (``bytes_scanned`` /
``bytes_scanned_plain``).

Every execution — solo or wave, plain or sharded — streams the fact
table through the bounded-memory morsel spine (``repro.sql.morsel``)
under the server's ``morsel_bytes`` budget; each ``QueryResult``
reports the stream's ``n_morsels`` and ``peak_resident_bytes`` (the
double-buffer residency bound), so out-of-core executions are
observable per request.

Per-request metrics (latency, strategy actually used, fallback reason)
ride back on the ``QueryResult`` so a traffic driver can tell fused
executions from materializing fallbacks.  ``strategy="auto"`` routes the
choice through the bandwidth cost model (``repro.sql.model``); the
result then also reports the model's choice and its predicted time next
to the measured latency, so the model's calibration is observable in
production traffic.

``stats`` is a ``defaultdict(int)``-backed counter: the per-strategy
tallies (``stats[ran] += 1``) must never ``KeyError`` on a strategy the
fixed seed dict didn't anticipate — that poisoned the request before
the fix.

Resilience (``repro.sql.resilience``): every request terminates with a
result or a *typed* error.  Failures classify into the ``QueryError``
taxonomy and surface as a structured :class:`~.resilience.ErrorInfo` on
``QueryResult.error`` (kind, message, strategy attempted, attempt count;
the original traceback rides on ``exception.__cause__``).  A request may
carry a ``deadline_s`` budget: on a retryable fault the server walks the
degradation ladder (e.g. ``sharded → fused → opat → ref``) with capped
exponential backoff, skipping rungs the cost model predicts will not fit
the remaining budget, and returns ``DeadlineExceeded`` when the budget
runs out.  A per-(strategy, backend) circuit breaker opens after K
consecutive failures (half-open probe after a cooldown), a faulted
shared-wave member — or a faulted whole wave — re-enters the ladder solo
instead of dying, and a ``ResourceGovernor`` reacts to memory pressure
by shrinking ``morsel_bytes``, evicting soft caches, and (past a
high-water mark) shedding new admissions with a typed
``MemoryPressure``.
"""
from __future__ import annotations

import time
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple, Union

import numpy as np

from repro.sql import compile as C
from repro.sql import resilience as RS
from repro.sql import result_cache as RC
from repro.sql import spans as SP
from repro.sql import ssb
from repro.sql import storage as ST
from repro.sql.compile import compile_plan, shareability
from repro.sql.hashtable import HashTableCache
from repro.sql.plan import Plan


@dataclass
class QueryRequest:
    rid: int
    plan: Plan
    strategy: str = "fused"
    deadline_s: Optional[float] = None  # wall-clock budget; None = no bound


@dataclass
class QueryResult:
    rid: int
    name: str
    result: Optional[np.ndarray]        # None when the request errored
    strategy: str                       # strategy that actually ran
    fallback_reason: Optional[str]
    latency_s: float
    cache_hits: int                     # dim-table builds skipped
    cache_misses: int                   # dim-table builds performed
    error: Optional[Union[str, RS.ErrorInfo]] = None  # failed request:
    #   structured ErrorInfo (error_kind / message / strategy attempted /
    #   attempt count, original traceback on exception.__cause__);
    #   stringifies as "Kind: message" and supports substring `in`
    attempts: int = 1                   # ladder rungs tried (1 = first try)
    model_choice: Optional[str] = None  # auto requests: model's pick
    predicted_s: Optional[float] = None  # model's time for the strategy run
    predictions: Optional[Dict[str, float]] = None  # full per-strategy model
    shared_wave_size: Optional[int] = None  # members of the shared pass
    #   that produced this result (None: the request ran solo); for a
    #   shared member, latency_s is the whole wave's wall time — the wave
    #   IS the unit of execution
    bytes_scanned: Optional[int] = None  # fact bytes the scan streamed at
    #   the columns' *encoded* widths (repro.sql.storage); for a shared
    #   member this is the whole wave's union-stream traffic
    bytes_scanned_plain: Optional[int] = None  # same streams at the
    #   nominal 4-byte width — the packed-vs-plain ratio is
    #   bytes_scanned_plain / bytes_scanned
    device_count: Optional[int] = None  # shards the execution ran over
    #   (None: the solo single-device path — no shard decomposition)
    shard_times_s: Optional[List[float]] = None  # per-shard wall times of
    #   a sharded execution (one entry for a whole shard_map launch); for
    #   a sharded shared wave, every member reports the wave's breakdown
    n_morsels: Optional[int] = None     # morsels the scan streamed over
    #   (1 = the in-memory degenerate case; >1 = out-of-core execution)
    peak_resident_bytes: Optional[int] = None  # largest encoded footprint
    #   of any two adjacent morsels — the double-buffer residency bound
    #   the morsel stream guarantees (<= 2 x the server's morsel budget)
    cache_hit: bool = False             # answered from the result cache
    #   (strategy == "cached": no scan, no kernel, no hash-table build)
    launch_config: Optional[Dict[str, Dict]] = None  # per-kernel-family
    #   launch configuration the execution actually used (tile, radix
    #   width, partition depth, and whether each came from an explicit
    #   tile argument, the tune store, or the shipped default) —
    #   compile.LAUNCH_CONFIG's snapshot; None for cached/ref answers
    #   (no kernel launched)
    subsumption_hit: bool = False       # the cache hit was a *narrower*
    #   query answered by masking a containing cached grid — implies
    #   cache_hit; benchmarks assert these answers against the oracle
    #   so cache correctness under pressure/eviction stays observable
    upload_bytes: Optional[int] = None  # host-to-device bytes the
    #   execution copied (``LAUNCH_STATS["upload_bytes"]``' change): plain
    #   columns, parameter arrays, dimension tables built on a miss; for a
    #   shared member the whole wave's; None for a result-cache answer


class QueryServer:
    """Batch query server over one resident ``Database``.

        server = QueryServer(db, mode="ref")
        rid = server.submit(plan)               # fused by default
        results = server.run()                  # Dict[rid, QueryResult]
    """

    # per-core accumulator budget for the shared-scan kernel: the
    # (Q_padded, n_groups) f32 scratch must stay a small slice of VMEM
    # (v5e: ~128MB/core, but the accumulator shares it with the tile
    # pipeline's double buffers).  2 MiB admits a full 16-member wave at
    # 32K groups; oversized waves split instead of assuming they fit —
    # the ROADMAP item this enforces.
    DEFAULT_ACC_BUDGET = 1 << 21

    def __init__(self, db: ssb.Database, mode: str = "ref",
                 tile: Optional[int] = None, max_batch: int = 8,
                 acc_budget_bytes: int = DEFAULT_ACC_BUDGET,
                 morsel_bytes: int = C.MS.DEFAULT_MORSEL_BYTES,
                 resident_budget_bytes: Optional[int] = None,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 1.0,
                 result_cache: Optional[RC.ResultCache] = None,
                 anchor_plans: Optional[List[Plan]] = None):
        self.db = db
        self.mode = mode
        # None = every kernel family launches at its tuned configuration
        # (repro.sql.tune; DEFAULT_TILE on a cold store); an explicit
        # tile pins every family — tests and A/B sweeps stay deterministic
        self.tile = tile
        self.max_batch = max_batch
        self.acc_budget_bytes = acc_budget_bytes
        # per-morsel byte budget every execution streams under; the
        # default keeps test-scale databases single-morsel (in-memory
        # fast path), a smaller budget bounds device residency at
        # 2 x morsel_bytes regardless of fact-table size.  The governor
        # owns the live value: memory pressure halves it (LANE floor)
        self.governor = RS.ResourceGovernor(
            morsel_bytes, budget_bytes=resident_budget_bytes)
        self.breakers = RS.BreakerBoard(threshold=breaker_threshold,
                                        cooldown_s=breaker_cooldown_s)
        self.cache = HashTableCache()
        # finished-aggregate-grid cache (repro.sql.result_cache): OFF by
        # default — batch benchmarks re-submit identical waves to time
        # execution, and a silently-on result cache would time lookups
        # instead.  The serving loop (repro.sql.serving) turns it on.
        self.result_cache = result_cache
        # footprint anchor (compile.shared_params): a serving loop that
        # knows its query pool pins every wave's lowered footprint to
        # the pool union, collapsing wave-composition churn onto one
        # executable per pow2 member bucket
        self.anchor_plans = list(anchor_plans) if anchor_plans else None
        self.queue: List[QueryRequest] = []
        self._next_rid = 0
        # defaultdict: unknown decided strategies tally instead of
        # KeyError-poisoning the request; non-counter entries seeded
        self.stats = defaultdict(int)
        self.stats["occupancy"] = []

    @property
    def morsel_bytes(self) -> int:
        return self.governor.morsel_bytes

    @morsel_bytes.setter
    def morsel_bytes(self, v: int) -> None:
        self.governor.morsel_bytes = int(v)

    def submit(self, plan: Plan, strategy: str = "fused",
               deadline_s: Optional[float] = None) -> int:
        """Enqueue one request.  Past the governor's high-water mark the
        server sheds load HERE — a typed :class:`~.resilience.
        MemoryPressure` at the door instead of a mid-query failure."""
        try:
            self.governor.admit()       # raises MemoryPressure when shedding
        except RS.MemoryPressure:
            self.stats["sheds"] += 1
            raise
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(QueryRequest(rid, plan, strategy, deadline_s))
        return rid

    def _wave_key(self, req: QueryRequest) -> Tuple:
        """Scan-compatibility bucketing: shareable plans requested as
        ``shared``/``auto`` group by the fact table they scan (one shared
        pass per wave); everything else buckets by requested strategy, as
        before.  A malformed plan buckets solo so ``_execute`` can report
        its error per-request."""
        if req.strategy in ("shared", "auto"):
            try:
                shareable = shareability(req.plan) is None
            except (ValueError, TypeError, KeyError, AttributeError):
                # malformed plan: route solo, _execute reports it typed
                shareable = False
            if shareable:
                return ("scan", req.plan.scan.table, req.strategy)
        return ("solo", req.strategy)

    @staticmethod
    def _member_key(req: QueryRequest) -> Tuple:
        """Dedup identity of a wave member; falls back to a per-request
        key (no dedup) when the plan cannot be fingerprinted."""
        try:
            return C.shared_member_key(req.plan)
        except (ValueError, TypeError, KeyError, AttributeError):
            # unfingerprintable plan: no dedup, keep its own wave slot
            return ("rid", req.rid)

    def _chunk_scan_bucket(self, rs: List[QueryRequest]
                           ) -> List[List[QueryRequest]]:
        """Chunk one scan-compatible bucket to waves that respect BOTH
        the batch size and the shared kernel's VMEM accumulator budget:
        the scratch is (Q_padded, max n_groups) f32, so wave size x
        group count is enforced here instead of assumed to fit.  BOTH
        limits count *unique* members (``_member_key``) — a duplicate
        occupies no stacked slot after ``_run_shared``'s dedup, so it
        never forces a split: N copies of one hot query stay one wave =
        one scan, whatever N.  A single member over budget still runs
        (a 1-wave cannot shrink); splits forced by the budget rather
        than max_batch are counted in ``stats["budget_splits"]``."""
        waves: List[List[QueryRequest]] = []
        cur: List[QueryRequest] = []
        cur_keys: set = set()
        cur_groups = 0
        for r in rs:
            k = self._member_key(r)
            is_dup = k in cur_keys
            ng = max(cur_groups, r.plan.n_groups)
            # padded *unique* slot count if r joins the current wave
            # (the pow2-bucket rule _run_shared pads the deduped wave to)
            q_pad = 1 << len(cur_keys).bit_length()
            over_budget = q_pad * ng * 4 > self.acc_budget_bytes
            if cur and not is_dup and (len(cur_keys) >= self.max_batch
                                       or over_budget):
                if over_budget and len(cur_keys) < self.max_batch:
                    self.stats["budget_splits"] += 1
                waves.append(cur)
                cur, cur_keys, cur_groups = [], set(), 0
            cur.append(r)
            cur_keys.add(k)
            cur_groups = max(cur_groups, r.plan.n_groups)
        if cur:
            waves.append(cur)
        return waves

    def _waves(self) -> List[Tuple[Tuple, List[QueryRequest]]]:
        """Bucket by scan-compatibility key, then chunk — scan buckets
        to batch size AND accumulator budget, everything else to batch
        size (a wave is homogeneous, like the LM server's length
        buckets)."""
        buckets: Dict[Tuple, List[QueryRequest]] = defaultdict(list)
        for r in self.queue:
            buckets[self._wave_key(r)].append(r)
        waves = []
        for key, rs in sorted(buckets.items()):
            if key[0] == "scan":
                waves.extend((key, chunk)
                             for chunk in self._chunk_scan_bucket(rs))
            else:
                for i in range(0, len(rs), self.max_batch):
                    waves.append((key, rs[i:i + self.max_batch]))
        return waves

    def run(self) -> Dict[int, QueryResult]:
        out: Dict[int, QueryResult] = {}
        for key, wave in self._waves():
            self.stats["waves"] += 1
            self.stats["occupancy"].append(len(wave) / self.max_batch)
            if key[0] == "scan":
                out.update(self._run_scan_wave(key, wave))
            else:
                for req in wave:
                    out[req.rid] = self._execute(req)
        self.queue.clear()
        return out

    # ------------------------------------------------------------------
    # result cache (finished aggregate grids; see repro.sql.result_cache)
    # ------------------------------------------------------------------

    def _from_result_cache(self, req: QueryRequest,
                           t0: float) -> Optional[QueryResult]:
        """Answer ``req`` from the result cache, or ``None``.  A cache
        malfunction is a miss, never a failed request."""
        if self.result_cache is None:
            return None
        try:
            hit = self.result_cache.lookup(self.db, req.plan)
        except Exception:
            return None
        if hit is None:
            return None
        grid, kind = hit
        self.stats["queries"] += 1
        self.stats["result_cache_hits"] += 1
        if kind == "subsume":
            self.stats["result_subsume_hits"] += 1
        if req.strategy == "auto":
            self.stats["auto"] += 1
        return QueryResult(
            rid=req.rid, name=req.plan.name, result=grid,
            strategy="cached", fallback_reason=None,
            latency_s=time.perf_counter() - t0,
            cache_hits=0, cache_misses=0,
            cache_hit=True, subsumption_hit=(kind == "subsume"))

    def _to_result_cache(self, plan: Plan, result) -> None:
        """Keep a finished aggregate grid; never fatal, never rows."""
        if (self.result_cache is None or result is None
                or plan.project is None or plan.group is None):
            return
        try:
            self.result_cache.insert(self.db, plan, np.asarray(result))
        except Exception:
            pass

    # ------------------------------------------------------------------
    # shared-scan wave path
    # ------------------------------------------------------------------

    def _run_scan_wave(self, key: Tuple,
                       wave: List[QueryRequest]) -> Dict[int, QueryResult]:
        """One scan-compatible wave.  ``shared`` requests always run the
        shared pass; ``auto`` waves run it only when the cost model says
        sharing beats the members' solo argmins (a 1-member wave never
        does — shared is fused plus wave overhead).

        On a resident *sharded* database the whole wave routes through
        sharded execution (``compile.execute_shared_sharded``): wave
        formation (PR 4) and decode-on-scan (PR 5) compose with the
        shard decomposition for free — each shard runs the wave's one
        multi-query pass, and only the stacked partial grids merge.
        ``auto`` waves arbitrate all three ways: solo argmins vs one
        shared pass vs the shared pass divided across shards
        (``model.predict_shared(..., n_shards=...)``)."""
        from repro.sql import shard as SH
        strategy = key[2]
        n_shards = SH.shard_count(self.db)
        sharded = n_shards > 1
        preds = None
        if strategy == "auto":
            from repro.sql import model as M
            run_shared = False
            if len(wave) > 1:
                try:
                    preds = M.predict_shared([r.plan for r in wave],
                                             self.db, n_shards=n_shards)
                    shared_t = min(preds["shared"],
                                   preds.get("shared_sharded",
                                             float("inf")))
                    run_shared = shared_t < preds["solo"]
                    sharded = (sharded and
                               preds.get("shared_sharded",
                                         float("inf")) < preds["shared"])
                except Exception:           # model failure, not fatal
                    run_shared = False      # falls back to solo execution
                    # observable: a broken shared-cost model must not be
                    # indistinguishable from "sharing does not pay"
                    self.stats["shared_arbitration_errors"] += 1
            if not run_shared:
                return {req.rid: self._execute(req) for req in wave}
        return self._run_shared(wave, model_predictions=preds,
                                sharded=sharded)

    def _run_shared(self, wave: List[QueryRequest],
                    model_predictions: Optional[Dict[str, float]] = None,
                    sharded: bool = False) -> Dict[int, QueryResult]:
        """Execute one wave as a single shared fused pass, with member
        fault isolation: a member whose join build sides fail to
        construct (the per-member failure surface — predicate/measure
        validation already passed at bucketing time) is excluded and
        re-enters the degradation ladder solo; the survivors still share
        one pass.  A fault inside the shared pass itself sends every
        survivor back through the ladder solo too — one poisoned launch
        must not kill a whole wave.

        ``sharded=True`` runs the wave once per fact shard and merges
        the stacked partial grids (``compile.execute_shared_sharded``);
        members then also report ``device_count``/``shard_times_s``."""
        rids = "-".join(str(r.rid) for r in wave)
        with SP.span(SP.WAVE, rids=rids):
            return self._shared_pass(wave, model_predictions, sharded)

    def _shared_pass(self, wave: List[QueryRequest],
                     model_predictions: Optional[Dict[str, float]],
                     sharded: bool) -> Dict[int, QueryResult]:
        from repro.sql import model as M
        from repro.sql import shard as SH
        out: Dict[int, QueryResult] = {}
        t0 = time.perf_counter()
        u0 = SP.LAUNCH_STATS["upload_bytes"]
        survivors: List[QueryRequest] = []
        deltas: Dict[int, Tuple[int, int]] = {}
        # built tables collected here ride into execute_shared as-is, so
        # the lowering never re-fetches from the cache — every hit/miss
        # the wave causes is attributed to exactly one member below
        prebuilt: Dict[Tuple, Tuple] = {}
        for req in wave:
            cached = self._from_result_cache(req, t0)
            if cached is not None:      # answered with no wave slot at
                out[req.rid] = cached   # all — the member leaves before
                continue                # its build sides are touched
            h0, m0 = self.cache.hits, self.cache.misses
            try:
                for j in req.plan.joins:
                    built = self.cache.get_or_build(self.db, j)
                    prebuilt[C.shared_join_key(j)] = built
            except Exception:       # build fault: member leaves the wave
                # ...and re-enters the ladder SOLO: a transient build
                # fault degrades this member (the survivors still share
                # one pass), a plan-contract violation surfaces as a
                # typed non-retryable error from its solo run
                self.stats["member_reentries"] += 1
                out[req.rid] = self._execute(req)
                continue
            deltas[req.rid] = (self.cache.hits - h0,
                               self.cache.misses - m0)
            survivors.append(req)
        if not survivors:
            return out

        # in-wave dedup: members with equal structural execution identity
        # (compile.shared_member_key) aggregate ONCE — the wave carries
        # one stacked slot per *unique* plan and duplicates fan the
        # result out (each its own copy); repeated queries at high
        # concurrency stop paying per-member VPU fan-out
        uniq_reqs: List[QueryRequest] = []
        slot_of: Dict[int, int] = {}
        slot_ix: Dict[Tuple, int] = {}
        for req in survivors:
            k = self._member_key(req)
            if k in slot_ix:
                self.stats["dedup_saved"] += 1
            else:
                slot_ix[k] = len(uniq_reqs)
                uniq_reqs.append(req)
            slot_of[req.rid] = slot_ix[k]

        try:
            fact = getattr(self.db, uniq_reqs[0].plan.scan.table)
            bytes_enc, bytes_plain = M.scanned_bytes_shared(
                [r.plan for r in uniq_reqs], fact)
        except Exception:                   # reporting only, never fatal
            bytes_enc = bytes_plain = None

        flavor = "shared_sharded" if sharded else "shared"
        dc = SH.shard_count(self.db) if sharded else None
        shard_times: Optional[List[float]] = None
        report: Optional[C.MS.MorselReport] = None
        wave_config: Optional[Dict[str, Dict]] = None

        def member_result(req, result, error, dt):
            self.stats["queries"] += 1
            if req.strategy == "auto":
                self.stats["auto"] += 1
            if error is None:
                self.stats["shared"] += 1
            else:
                self.stats["errors"] += 1
            hits, misses = deltas[req.rid]
            return QueryResult(
                rid=req.rid, name=req.plan.name, result=result,
                strategy="shared", fallback_reason=None, latency_s=dt,
                cache_hits=hits, cache_misses=misses, error=error,
                model_choice=flavor if req.strategy == "auto" else None,
                predicted_s=(None if model_predictions is None
                             else model_predictions.get(
                                 flavor, model_predictions["shared"])),
                predictions=model_predictions,
                shared_wave_size=len(survivors),
                bytes_scanned=bytes_enc, bytes_scanned_plain=bytes_plain,
                device_count=dc, shard_times_s=shard_times,
                n_morsels=None if report is None else report.n_morsels,
                peak_resident_bytes=(None if report is None
                                     else report.peak_resident_bytes),
                launch_config=wave_config, upload_bytes=uploaded)

        # pow2 member-count buckets (like the LM server's length buckets):
        # padded slots are inert but not free, so a small wave must not
        # pay for max_batch — while any member count still maps onto
        # O(log max_batch) cached executables per wave composition
        pad_to = 1 << max(len(uniq_reqs) - 1, 0).bit_length()
        try:
            if sharded:
                results, shard_times, report = C.execute_shared_sharded(
                    [r.plan for r in uniq_reqs], self.db, mode=self.mode,
                    tile=self.tile, cache=self.cache, pad_to=pad_to,
                    prebuilt=prebuilt, morsel_bytes=self.morsel_bytes,
                    anchor=self.anchor_plans)
            else:
                results, report = C.execute_shared_morsels(
                    [r.plan for r in uniq_reqs], self.db, mode=self.mode,
                    tile=self.tile, cache=self.cache, pad_to=pad_to,
                    prebuilt=prebuilt, morsel_bytes=self.morsel_bytes,
                    anchor=self.anchor_plans)
            wave_config = C.snapshot_launch_config()
        except Exception as e:          # wave fault: members retry solo
            err = RS.classify_error(e, during="execute")
            if isinstance(err, RS.MemoryPressure):
                self.governor.on_pressure(db=self.db, cache=self.cache,
                                          result_cache=self.result_cache)
            # the shared pass is one launch — a fault inside it says
            # nothing about which member is poisoned, so every survivor
            # re-enters the degradation ladder solo
            self.stats["wave_reentries"] += 1
            for req in survivors:
                out[req.rid] = self._execute(req)
            return out
        dt = time.perf_counter() - t0
        uploaded = SP.LAUNCH_STATS["upload_bytes"] - u0
        self.stats["shared_waves"] += 1
        if sharded:
            self.stats["sharded_waves"] += 1
        owned = set()
        for req in survivors:
            result = results[slot_of[req.rid]]
            if slot_of[req.rid] in owned:   # duplicate member: own copy
                result = result.copy()
            owned.add(slot_of[req.rid])
            self._to_result_cache(req.plan, result)
            out[req.rid] = member_result(req, result, None, dt)
        return out

    # ------------------------------------------------------------------
    # solo path
    # ------------------------------------------------------------------

    def _oracle_ok(self, plan: Plan) -> bool:
        """Whether the ``ref`` rung (pure-numpy oracle) can interpret
        this plan — aggregate SPJA plans only."""
        return plan.project is not None and plan.group is not None

    def _run_ref(self, plan: Plan) -> np.ndarray:
        """The ladder's rung of last resort: the host-side numpy oracle
        — no kernel dispatch, no device upload, no hash-table build.
        Pending ingest deltas are folded into a throwaway flushed copy
        so the oracle observes the same rows every engine path scans."""
        from dataclasses import replace as dc_replace

        from repro.sql import engine as E
        from repro.sql import shard as SH
        base = SH.base_of(self.db)
        fact = getattr(base, plan.scan.table)
        if ST.delta_rows(fact):
            base = dc_replace(base,
                              **{plan.scan.table: ST.flush_deltas(fact)})
        return np.asarray(E.run_query_oracle(base, plan))

    def _execute(self, req: QueryRequest) -> QueryResult:
        with SP.span(SP.QUERY, rid=req.rid):
            return self._ladder(req)

    def _ladder(self, req: QueryRequest) -> QueryResult:
        """One request through the retry/degradation ladder.

        Fault-isolated AND deadline-bounded: a non-retryable failure
        (bad plan, compile error) surfaces immediately as a typed
        :class:`~.resilience.ErrorInfo`; a retryable one (exec fault,
        memory pressure) walks the strategy ladder —
        ``resilience.ladder_for(req.strategy)`` — with capped
        exponential backoff, skipping rungs whose circuit breaker is
        open or whose cost-model prediction exceeds the remaining
        deadline budget.  Memory pressure additionally triggers the
        governor (smaller morsels, cache eviction) and retries the same
        rung once before degrading.  Every path terminates: success,
        typed error, or ``DeadlineExceeded``."""
        h0, m0 = self.cache.hits, self.cache.misses
        u0 = SP.LAUNCH_STATS["upload_bytes"]
        t0 = time.perf_counter()
        cached = self._from_result_cache(req, t0)
        if cached is not None:          # no scan, no ladder: the answer
            return cached               # was already computed and the
            # database has not changed since (the cache checks)
        deadline = RS.Deadline(req.deadline_s)
        attempts = 0

        def errored(err: RS.QueryError, strategy, fallback_reason=None):
            self.stats["queries"] += 1
            self.stats["errors"] += 1
            if req.strategy == "auto":
                self.stats["auto"] += 1
            if fallback_reason is not None:
                self.stats["fallbacks"] += 1
            return QueryResult(
                rid=req.rid, name=req.plan.name, result=None,
                strategy=strategy, fallback_reason=fallback_reason,
                latency_s=time.perf_counter() - t0,
                cache_hits=self.cache.hits - h0,
                cache_misses=self.cache.misses - m0,
                attempts=max(attempts, 1),
                error=RS.ErrorInfo.from_exception(
                    err, strategy=strategy, attempts=max(attempts, 1)),
                upload_bytes=SP.LAUNCH_STATS["upload_bytes"] - u0)

        def succeeded(result, ran, cq):
            dt = time.perf_counter() - t0
            self.stats["queries"] += 1
            self.stats[ran] += 1
            if req.strategy == "auto":
                self.stats["auto"] += 1
            fallback = None if cq is None else cq.fallback_reason
            if fallback is not None:
                self.stats["fallbacks"] += 1
            self.governor.on_success()
            self._to_result_cache(req.plan, result)
            try:
                from repro.sql import model as M
                bytes_enc, bytes_plain = M.scanned_bytes(
                    req.plan, getattr(self.db, req.plan.scan.table))
            except Exception:               # reporting only, never fatal
                bytes_enc = bytes_plain = None
            preds = None if cq is None else cq.predictions
            return QueryResult(
                rid=req.rid, name=req.plan.name, result=result,
                strategy=ran, fallback_reason=fallback,
                latency_s=dt, cache_hits=self.cache.hits - h0,
                cache_misses=self.cache.misses - m0,
                attempts=max(attempts, 1),
                model_choice=ran if req.strategy == "auto" else None,
                predicted_s=None if preds is None else preds.get(ran),
                predictions=preds,
                bytes_scanned=bytes_enc, bytes_scanned_plain=bytes_plain,
                device_count=None if cq is None else cq.device_count,
                shard_times_s=None if cq is None else cq.shard_times_s,
                n_morsels=None if cq is None else cq.n_morsels,
                peak_resident_bytes=(None if cq is None
                                     else cq.peak_resident_bytes),
                launch_config=(None if cq is None
                               else cq.launch_config),
                upload_bytes=SP.LAUNCH_STATS["upload_bytes"] - u0)

        ladder = RS.ladder_for(req.strategy)
        predictions: Optional[Dict[str, float]] = None
        last_err: Optional[RS.QueryError] = None
        pressure_retried: set = set()
        rung_i = 0
        while rung_i < len(ladder):
            rung = ladder[rung_i]
            if deadline.expired():
                break
            if rung == "ref" and not self._oracle_ok(req.plan):
                rung_i += 1
                continue
            breaker = self.breakers.get(rung, self.mode)
            if not breaker.allow():     # poisoned path: skip, don't probe
                self.stats["breaker_skips"] += 1
                rung_i += 1
                continue
            if req.deadline_s is not None and last_err is not None:
                # budget-aware rung skipping: don't start a strategy the
                # model already predicts will blow the remaining budget
                if predictions is None:
                    from repro.sql import model as M
                    from repro.sql import shard as SH
                    try:
                        predictions = M.predict(
                            req.plan, self.db,
                            n_shards=SH.shard_count(self.db),
                            morsel_bytes=self.morsel_bytes)
                    except Exception:   # no model, no skipping
                        predictions = {}
                if not RS.fit_in_budget(predictions, rung,
                                        deadline.remaining()):
                    self.stats["budget_skips"] += 1
                    rung_i += 1
                    continue
            attempts += 1
            cq = None
            try:
                if rung == "ref":
                    result = self._run_ref(req.plan)
                    ran = "ref"
                else:
                    # compilation is validation + a dataclass — cheap
                    try:
                        with SP.span(SP.PLAN):
                            cq = compile_plan(req.plan, rung)
                    except Exception as e:
                        raise RS.classify_error(e, during="compile") \
                            from e
                    result = cq.execute(
                        self.db, mode=self.mode, tile=self.tile,
                        cache=self.cache,
                        morsel_bytes=self.morsel_bytes)
                    # auto requests report the strategy the model
                    # actually dispatched, not the "auto" placeholder
                    ran = cq.decided or cq.strategy
            except Exception as e:
                err = RS.classify_error(e, during="execute")
                if err.retryable:
                    # plan/compile errors say nothing about the rung's
                    # health — only exec faults trip its breaker
                    breaker.record_failure()
                last_err = err
                if isinstance(err, RS.MemoryPressure):
                    # react, then retry the SAME rung once at the
                    # governor's reduced footprint before degrading
                    self.governor.on_pressure(
                        db=self.db, cache=self.cache,
                        result_cache=self.result_cache)
                    self.stats["pressure_events"] += 1
                    if err.retryable and rung not in pressure_retried:
                        pressure_retried.add(rung)
                        RS.sleep_backoff(attempts - 1, deadline)
                        continue
                if not err.retryable:
                    return errored(err, rung, None if cq is None
                                   else cq.fallback_reason)
                self.stats["retries"] += 1
                RS.sleep_backoff(attempts - 1, deadline)
                rung_i += 1
                continue
            breaker.record_success()
            return succeeded(result, ran, cq)

        if deadline.expired():
            err = RS.DeadlineExceeded(
                f"deadline {req.deadline_s}s exhausted after "
                f"{attempts} attempt(s), last rung "
                f"{ladder[min(rung_i, len(ladder) - 1)]!r}")
            if last_err is not None:
                err.__cause__ = last_err
            return errored(err, req.strategy)
        # ladder exhausted without success: surface the last typed error
        if last_err is None:
            last_err = RS.ExecError(
                f"no runnable rung in ladder {ladder} "
                "(circuit breakers open or rungs inapplicable)")
        return errored(last_err, req.strategy)
