"""Resilient serving spine (repro.sql.resilience + repro.sql.faults +
the server's retry/degradation ladder).

The tentpole claim under test: every request terminates with a result
or a *typed* error.  Under a seeded ``FaultPlan`` every SSB query
either returns a bit-identical-to-oracle result (degraded down the
ladder) or a structured ``ErrorInfo``; deadline-bounded requests finish
or return ``DeadlineExceeded``; circuit breakers open after K
consecutive faults and half-open probe back; the ``ResourceGovernor``
reacts to memory pressure by shrinking morsels / evicting soft caches
and sheds load at admission past the high-water mark.  Plus the
satellites: ingest atomicity under injected mid-staging faults, torn
calibration-cache recovery, and fault-plan determinism.
"""
import logging
import os
import time

import numpy as np
import pytest

from repro.cost import model as CM
from repro.sql import calibrate as CAL
from repro.sql import engine, faults, ssb
from repro.sql import plan as P
from repro.sql import resilience as RS
from repro.sql import storage as ST
from repro.sql.server import QueryServer

DB = ssb.generate(sf=0.005, seed=11)
QUERIES = engine.ssb_queries()
Q11 = QUERIES["q1.1"]           # no joins (selection only)
Q21 = QUERIES["q2.1"]           # 3 joins (build-side surface)


def oracle(plan):
    return np.asarray(engine.run_query_oracle(DB, plan))


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    """Fault plans must never leak across tests."""
    yield
    faults.install(None)


# ---------------------------------------------------------------------------
# taxonomy / classification
# ---------------------------------------------------------------------------


def test_taxonomy_hierarchy():
    assert issubclass(RS.PlanError, RS.QueryError)
    assert issubclass(RS.FaultInjected, RS.ExecError)
    assert issubclass(RS.InjectedOOM, RS.MemoryPressure)
    assert RS.ExecError("x").retryable
    assert RS.MemoryPressure("x").retryable
    assert not RS.PlanError("x").retryable
    assert not RS.CompileError("x").retryable
    assert RS.ExecError("x").kind == "ExecError"


def test_classify_wraps_and_chains_cause():
    orig = RuntimeError("kernel blew up")
    err = RS.classify_error(orig)
    assert isinstance(err, RS.ExecError)
    assert err.__cause__ is orig            # original traceback preserved
    # contract violations are plan errors on any rung
    assert isinstance(RS.classify_error(ValueError("negative payload")),
                      RS.PlanError)
    # allocation failures map to MemoryPressure whatever the phase
    oom = RS.classify_error(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert isinstance(oom, RS.MemoryPressure)
    # typed errors pass through unchanged
    e = RS.ExecError("already typed")
    assert RS.classify_error(e) is e
    # phase picks the class for plain exceptions
    assert isinstance(RS.classify_error(RuntimeError("x"), "compile"),
                      RS.CompileError)


def test_unlowerable_construct_surfaces_as_compile_error():
    """A construct the backend cannot lower (the TPU compiler raises
    NotImplementedError for a Pallas scatter-add) must not be retried
    down the ladder to the host oracle, where the device would vanish
    from view: it is a non-retryable CompileError in any phase."""
    exc = NotImplementedError("Unimplemented primitive in Pallas TPU "
                              "lowering for KernelType.TC: scatter-add")
    err = RS.classify_error(exc, during="execute")
    assert isinstance(err, RS.CompileError) and not err.retryable
    assert err.__cause__ is exc


def test_errorinfo_stringifies_and_supports_substring():
    err = RS.ExecError("boom at morsel 3")
    info = RS.ErrorInfo.from_exception(err, strategy="fused", attempts=2)
    assert info.error_kind == "ExecError"
    assert info.strategy == "fused" and info.attempts == 2
    assert str(info) == "ExecError: boom at morsel 3"
    assert "morsel 3" in info               # substring back-compat
    assert info.exception is err


# ---------------------------------------------------------------------------
# fault-plan determinism
# ---------------------------------------------------------------------------


def test_fault_plan_deterministic_per_site():
    def draw(seed, site, n):
        p = faults.FaultPlan(seed, {site: 0.3})
        return [p.should_fault(site) for _ in range(n)]

    assert draw(7, "kernel", 200) == draw(7, "kernel", 200)
    assert draw(7, "kernel", 200) != draw(8, "kernel", 200)
    # sites draw from independent streams: interleaving visits to one
    # site does not shift another's decisions
    p = faults.FaultPlan(7, {"kernel": 0.3, "build": 0.3})
    mixed = []
    for _ in range(200):
        p.should_fault("build")
        mixed.append(p.should_fault("kernel"))
    assert mixed == draw(7, "kernel", 200)


def test_fault_plan_rates_and_oom_every():
    p = faults.FaultPlan(3, {"kernel": 1.0}, oom_every=3)
    kinds = []
    for _ in range(6):
        with pytest.raises(RS.QueryError) as ei:
            p.fault("kernel")
        kinds.append(type(ei.value))
    assert kinds == [RS.FaultInjected, RS.FaultInjected, RS.InjectedOOM] * 2
    # rate 0 sites never fault; unlisted sites never fault
    q = faults.FaultPlan(3, {"kernel": 0.0})
    assert not any(q.should_fault("kernel") for _ in range(100))
    assert not any(q.should_fault("upload") for _ in range(100))


def test_maybe_fault_noop_without_plan():
    faults.install(None)
    faults.maybe_fault("kernel")            # must not raise


# ---------------------------------------------------------------------------
# deadline / backoff / breaker primitives
# ---------------------------------------------------------------------------


def test_deadline_remaining_and_unbounded():
    d = RS.Deadline(None)
    assert d.remaining() == float("inf") and not d.expired()
    d2 = RS.Deadline(0.0)
    assert d2.expired()


def test_backoff_capped_exponential():
    vals = [RS.backoff_s(i) for i in range(10)]
    assert vals[0] == RS.BACKOFF_BASE_S
    assert all(b >= a for a, b in zip(vals, vals[1:]))
    assert vals[-1] == RS.BACKOFF_CAP_S


def test_circuit_breaker_open_halfopen_close():
    br = RS.CircuitBreaker(threshold=3, cooldown_s=0.02)
    assert br.allow()
    for _ in range(3):
        br.record_failure()
    assert br.state == "open" and not br.allow()
    time.sleep(0.025)
    assert br.allow()                       # half-open: one probe
    assert not br.allow()                   # ...and only one
    br.record_failure()                     # failed probe re-opens
    assert br.state == "open"
    time.sleep(0.025)
    assert br.allow()
    br.record_success()                     # successful probe closes
    assert br.state == "closed" and br.allow()


def test_fit_in_budget():
    preds = {"fused": 0.5, "opat": 2.0}
    assert RS.fit_in_budget(preds, "fused", 1.0)
    assert not RS.fit_in_budget(preds, "opat", 1.0)
    assert RS.fit_in_budget(preds, "ref", 1.0)      # unknown always fits
    assert RS.fit_in_budget(None, "opat", 0.0)


# ---------------------------------------------------------------------------
# the ladder on the server
# ---------------------------------------------------------------------------


def test_ladder_degrades_to_typed_success():
    """Every device-touching site faults on every visit: the ladder
    walks all 13 SSB queries down to the host-side ``ref`` oracle, and
    every answer is bit-identical to running the oracle directly."""
    with faults.active(faults.FaultPlan(
            seed=2, rates={"kernel": 1.0, "build": 1.0, "upload": 1.0})):
        srv = QueryServer(DB, mode="ref")
        rids = {srv.submit(p, "auto"): p for p in QUERIES.values()}
        res = srv.run()
    for rid, plan in rids.items():
        r = res[rid]
        assert r.error is None, (plan.name, str(r.error))
        assert r.strategy == "ref"
        assert np.array_equal(r.result, oracle(plan)), plan.name
    # early requests walked the ladder; once the breakers opened, later
    # ones skipped the poisoned rungs and went straight to the oracle
    assert max(res[rid].attempts for rid in rids) > 1
    assert srv.stats["breaker_skips"] >= 1


def test_ladder_partial_degradation_prefers_early_rung():
    """Only the fused kernel faults: a no-join query lands on opat (its
    chain has no probe dispatch), not all the way down on ref."""
    with faults.active(faults.FaultPlan(seed=5, rates={"kernel": 1.0})):
        srv = QueryServer(DB, mode="ref")
        rid = srv.submit(Q11, "fused")
        r = srv.run()[rid]
    assert r.error is None
    assert r.strategy == "opat" and r.attempts == 2
    assert np.array_equal(r.result, oracle(Q11))


def test_plan_error_not_retried():
    """A contract violation fails identically on every rung — it must
    surface immediately as a typed PlanError, without ladder walking."""
    bad = (P.QueryBuilder("bad")
           .scan("lineorder")
           .hash_join("lo_suppkey", "supplier", "s_suppkey",
                      payload=P.AffineExpr("s_suppkey", 1, -999999))
           .measure("lo_revenue").group_by(1).build())
    srv = QueryServer(DB, mode="ref")
    rid = srv.submit(bad, "fused")
    r = srv.run()[rid]
    assert r.error is not None
    assert r.error.error_kind == "PlanError"
    assert "negative" in r.error
    assert r.attempts == 1
    assert r.error.exception.__cause__ is not None


def test_deadline_exceeded_is_typed_and_prompt():
    with faults.active(faults.FaultPlan(
            seed=4, rates={"kernel": 1.0, "build": 1.0})):
        srv = QueryServer(DB, mode="ref")
        rid = srv.submit(Q21, "fused", deadline_s=1e-6)
        t0 = time.monotonic()
        r = srv.run()[rid]
        dt = time.monotonic() - t0
    assert r.error is not None
    assert r.error.error_kind == "DeadlineExceeded"
    # bounded: deadline + one backoff step (+ a small first attempt)
    assert dt < 1e-6 + RS.BACKOFF_CAP_S + 2.0


def test_breaker_opens_and_skips_poisoned_strategy():
    with faults.active(faults.FaultPlan(seed=6, rates={"kernel": 1.0})):
        srv = QueryServer(DB, mode="ref", breaker_threshold=2,
                          breaker_cooldown_s=60.0)
        for _ in range(3):
            rid = srv.submit(Q11, "fused")
            r = srv.run()[rid]
            assert r.error is None          # degrades to opat every time
    # two consecutive fused faults opened the breaker; the third request
    # skipped the fused rung entirely
    assert srv.breakers.get("fused", "ref").state == "open"
    assert srv.stats["breaker_skips"] >= 1
    assert r.attempts == 1                  # went straight to opat


def test_wave_fault_reenters_members_solo():
    plans = [QUERIES["q2.1"], QUERIES["q2.2"], QUERIES["q2.3"]]
    with faults.active(faults.FaultPlan(seed=9, rates={"kernel": 1.0})):
        srv = QueryServer(DB, mode="ref")
        rids = {srv.submit(p, "shared"): p for p in plans}
        res = srv.run()
    assert srv.stats["wave_reentries"] >= 1
    for rid, plan in rids.items():
        r = res[rid]
        assert r.error is None, (plan.name, str(r.error))
        assert np.array_equal(r.result, oracle(plan)), plan.name


def test_no_cross_request_contamination_under_faults():
    """A faulted run must not leave a poisoned cache/plan behind: the
    same server serves a clean, bit-identical wave right after."""
    srv = QueryServer(DB, mode="ref")
    with faults.active(faults.FaultPlan(
            seed=2, rates={"kernel": 1.0, "build": 1.0})):
        rid = srv.submit(Q21, "fused")
        srv.run()
    rid2 = srv.submit(Q21, "fused")
    r2 = srv.run()[rid2]
    assert r2.error is None
    assert r2.strategy == "fused"
    assert np.array_equal(r2.result, oracle(Q21))


# ---------------------------------------------------------------------------
# resource governor
# ---------------------------------------------------------------------------


def test_governor_halves_morsels_with_lane_floor():
    g = RS.ResourceGovernor(1 << 20)
    sizes = []
    for _ in range(40):
        g.on_pressure()
        sizes.append(g.morsel_bytes)
    assert sizes[0] == (1 << 19)
    assert all(b % 32 == 0 for b in sizes)
    assert sizes[-1] == g._floor            # monotone down to the floor
    assert all(a >= b for a, b in zip(sizes, sizes[1:]))
    assert g._floor >= 32


def test_governor_evicts_cache_and_decode_memos():
    from repro.sql.hashtable import HashTableCache
    pdb = ST.pack_database(DB)
    cache = HashTableCache()
    for j in Q21.joins:
        cache.get_or_build(pdb, j)
    # pin a decode + device upload
    pdb.lineorder.columns["lo_revenue"].decode()
    n_entries = len(cache.tables)
    assert n_entries >= 3
    g = RS.ResourceGovernor(1 << 20)
    g.on_pressure(db=pdb, cache=cache)
    assert len(cache.tables) <= 2           # keep=2 most recent
    assert pdb.lineorder.columns["lo_revenue"]._decoded is None
    assert g.evictions > 0
    # evicted entries rebuild on demand (a miss, not an error)
    m0 = cache.misses
    cache.get_or_build(pdb, Q21.joins[0])
    assert cache.misses >= m0


def test_admission_shed_past_high_water():
    srv = QueryServer(DB, mode="ref")
    for _ in range(srv.governor.high_water):
        srv.governor.on_pressure()
    with pytest.raises(RS.MemoryPressure):
        srv.submit(Q11, "fused")
    assert srv.stats["sheds"] == 1
    # success resets the consecutive counter and admission reopens
    srv.governor.on_success()
    rid = srv.submit(Q11, "fused")
    r = srv.run()[rid]
    assert r.error is None


def test_injected_oom_triggers_governor_and_recovers():
    """InjectedOOM (a MemoryPressure) makes the server react — shrink
    morsels — and still answer via retry/degradation."""
    plan = faults.FaultPlan(seed=1, rates={"kernel": 1.0}, oom_every=1)
    mb0 = 1 << 20
    with faults.active(plan):
        srv = QueryServer(DB, mode="ref", morsel_bytes=mb0)
        rid = srv.submit(Q11, "fused")
        r = srv.run()[rid]
    assert r.error is None
    assert srv.stats["pressure_events"] >= 1
    assert srv.governor.morsel_bytes < mb0
    assert np.array_equal(r.result, oracle(Q11))


# ---------------------------------------------------------------------------
# ingest atomicity (storage satellite)
# ---------------------------------------------------------------------------


def _delta_rows_dict(table, n, seed):
    rng = np.random.default_rng(seed)
    return {c: rng.integers(1, 100, n).astype(np.int32)
            for c in table.columns}


def test_append_rows_atomic_under_injected_fault():
    pdb = ST.pack_database(ssb.generate(sf=0.005, seed=3))
    lo = pdb.lineorder
    rows = _delta_rows_dict(lo, 64, seed=0)
    ST.append_rows(lo, rows)                # one good batch
    before = ST.delta_batches(lo)
    assert len(before) == 1

    # deterministic mid-staging failure: the 3rd ingest-site visit
    class Fail3(faults.FaultPlan):
        def __init__(self):
            super().__init__(0, {"ingest": 1.0})
            self.n = 0

        def should_fault(self, site):
            self.n += 1
            return self.n == 3

    with faults.active(Fail3()):
        with pytest.raises(RS.QueryError):
            ST.append_rows(lo, _delta_rows_dict(lo, 64, seed=1))
    after = ST.delta_batches(lo)
    assert len(after) == 1                  # no half-ingested batch
    assert after[0] is before[0]
    assert ST.delta_rows(lo) == 64
    # and the table still ingests cleanly afterwards
    ST.append_rows(lo, _delta_rows_dict(lo, 32, seed=2))
    assert ST.delta_rows(lo) == 96


def test_flush_deltas_atomic_under_injected_fault():
    pdb = ST.pack_database(ssb.generate(sf=0.005, seed=3))
    lo = pdb.lineorder
    ST.append_rows(lo, _delta_rows_dict(lo, 64, seed=0))
    base_rows = lo.n_rows

    class FailLate(faults.FaultPlan):
        def __init__(self):
            super().__init__(0, {"ingest": 1.0})
            self.n = 0

        def should_fault(self, site):
            self.n += 1
            return self.n == 5              # fail mid-merge

    with faults.active(FailLate()):
        with pytest.raises(RS.QueryError):
            ST.flush_deltas(lo)
    # source table untouched: deltas intact, rows unchanged
    assert ST.delta_rows(lo) == 64
    assert lo.n_rows == base_rows
    # the retry succeeds and folds everything in
    flushed = ST.flush_deltas(lo)
    assert flushed.n_rows == base_rows + 64
    assert ST.delta_rows(flushed) == 0


def test_append_rows_validation_still_raises_plain():
    lo = ST.pack_database(ssb.generate(sf=0.005, seed=3)).lineorder
    with pytest.raises(ValueError, match="columns"):
        ST.append_rows(lo, {"nope": np.zeros(4, np.int32)})


# ---------------------------------------------------------------------------
# calibration torn-cache recovery (calibrate satellite)
# ---------------------------------------------------------------------------


def _fake_calib():
    return CAL.Calibration(backend="cpu", read_bw=1e10, write_bw=5e9,
                           cache_bw=2e10, launch_overhead_s=1e-5,
                           measured_at=0.0)


@pytest.mark.parametrize("torn", [
    "{\"backend\": \"cpu\", \"read_bw\": 1e10, \"wri",   # truncated
    "not json at all",
    "3",                                                 # wrong shape
    "{}",                                                # missing fields
])
def test_torn_calibration_cache_discarded_and_remeasured(
        tmp_path, monkeypatch, torn, caplog):
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(tmp_path))
    CAL._MEMO.clear()
    path = CAL.cache_path("cpu")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(torn)
    with caplog.at_level(logging.WARNING, logger="repro.sql.calibrate"):
        assert CAL.load_cached("cpu") is None
    assert "corrupt calibration cache" in caplog.text
    assert not os.path.exists(path)         # torn file removed
    # the calibrated-hardware path re-measures instead of raising
    CAL._MEMO.clear()
    monkeypatch.setattr(CAL, "measure", _fake_calib)
    with open(path, "w") as f:
        f.write(torn)
    hw = CAL.calibrated_hardware(CM.PAPER_CPU)
    assert hw.read_bw == 1e10               # the fresh measurement
    # and the re-measured cache round-trips
    CAL._MEMO.clear()
    loaded = CAL.load_cached("cpu")
    assert loaded is not None and loaded.read_bw == 1e10


def test_good_calibration_cache_still_loads(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(tmp_path))
    CAL._MEMO.clear()
    CAL.save(_fake_calib())
    CAL._MEMO.clear()
    loaded = CAL.load_cached("cpu")
    assert loaded is not None and loaded.read_bw == 1e10
