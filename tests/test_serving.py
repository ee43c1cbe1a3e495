"""Async serving loop (repro.sql.serving): admission queue, SLO-driven
wave formation, deadline accounting, and the pool-anchored executable.

The policy pieces are pure, so most of this file drives them without
threads: ``poisson_arrivals`` is deterministic under a fixed seed, the
``WaveFormer`` is exercised with a fake predictor and explicit clocks
(deadline-near dispatch, marginal economics, hold cap, unknown rate,
max-batch), and ``model.predict_marginal`` is sanity-checked against
the in-wave dedup invariant (a duplicate member costs nothing).  The
threaded ``ServingLoop`` is then tested end-to-end: every response —
executed, exact-cached, or subsumption-served — bit-identical to the
numpy oracle, drain-on-stop, admission shedding, queue-expired
deadlines, and the footprint anchor's membership-invariance.
"""
import math
import time

import numpy as np
import pytest

from repro.sql import compile as C
from repro.sql import engine, ssb
from repro.sql import model as M
from repro.sql import resilience as RS
from repro.sql import serving as SV
from repro.sql.result_cache import ResultCache

DB = ssb.generate(sf=0.005, seed=11)
QUERIES = engine.ssb_queries()
POOL = list(QUERIES.values())


def oracle(plan):
    return np.asarray(engine.run_query_oracle(DB, plan))


# ---------------------------------------------------------------------------
# poisson arrivals
# ---------------------------------------------------------------------------


def test_poisson_arrivals_deterministic_under_seed():
    a = SV.poisson_arrivals(50.0, 64, seed=7)
    b = SV.poisson_arrivals(50.0, 64, seed=7)
    np.testing.assert_array_equal(a, b)
    c = SV.poisson_arrivals(50.0, 64, seed=8)
    assert not np.array_equal(a, c)


def test_poisson_arrivals_shape_and_rate():
    sched = SV.poisson_arrivals(100.0, 2000, seed=3, start=5.0)
    assert sched.shape == (2000,)
    assert np.all(np.diff(sched) >= 0) and sched[0] >= 5.0
    # mean inter-arrival within 15% of 1/rate at n=2000
    assert abs(np.diff(sched).mean() - 0.01) < 0.0015


def test_poisson_arrivals_rejects_bad_rate():
    with pytest.raises(ValueError):
        SV.poisson_arrivals(0.0, 4, seed=1)


# ---------------------------------------------------------------------------
# wave former policy (fake predictor, explicit clock)
# ---------------------------------------------------------------------------


class FakePredictor:
    def __init__(self, shared_s=0.01, gain=1.0):
        self._shared = shared_s
        self._gain = gain

    def shared_s(self, plans):
        return self._shared

    def marginal_gain(self, plans):
        return self._gain


def ticket(rid, arrival, deadline_s=None):
    return SV.Ticket(rid, POOL[rid % len(POOL)], "auto", deadline_s,
                     arrival)


def test_former_holds_while_marginal_gain_pays():
    f = SV.WaveFormer(FakePredictor(shared_s=0.01, gain=10.0),
                      slo_s=10.0, max_batch=8, max_hold_s=60.0)
    f.add(ticket(0, arrival=0.0), now=0.0)
    f.add(ticket(1, arrival=0.1), now=0.1)
    # gain 10 > gap 0.05 * pool 2 and plenty of slack: keep holding
    assert f.decide(now=0.2, expected_gap=0.05) is None
    assert len(f.pending) == 2


def test_former_dispatches_on_economics():
    f = SV.WaveFormer(FakePredictor(shared_s=0.01, gain=0.001),
                      slo_s=10.0, max_batch=8, max_hold_s=60.0)
    f.add(ticket(0, arrival=0.0), now=0.0)
    f.add(ticket(1, arrival=0.1), now=0.1)
    wave = f.decide(now=0.2, expected_gap=0.05)
    assert wave is not None and len(wave) == 2
    assert f.dispatch_reasons == {"economics": 1}


def test_former_deadline_near_ticket_dispatches_alone():
    # remaining budget cannot cover the safety-padded wave time: the
    # single member leaves immediately instead of waiting for company
    f = SV.WaveFormer(FakePredictor(shared_s=0.2, gain=100.0),
                      slo_s=10.0, max_batch=8, safety=1.5,
                      max_hold_s=60.0)
    f.add(ticket(0, arrival=0.0, deadline_s=0.25), now=0.0)
    wave = f.decide(now=0.0, expected_gap=0.01)
    assert wave is not None and len(wave) == 1
    assert f.dispatch_reasons == {"deadline": 1}


def test_former_dispatches_when_slack_below_expected_gap():
    # holding means waiting ~one gap; a member that cannot afford that
    # wait forces dispatch even though its slack is still positive
    f = SV.WaveFormer(FakePredictor(shared_s=0.01, gain=100.0),
                      slo_s=0.5, max_batch=8, max_hold_s=60.0)
    f.add(ticket(0, arrival=0.0), now=0.0)
    assert f.decide(now=0.4, expected_gap=1.0) is not None
    assert f.dispatch_reasons == {"deadline": 1}


def test_former_full_wave_dispatches():
    f = SV.WaveFormer(FakePredictor(gain=100.0), slo_s=10.0, max_batch=4)
    for i in range(5):
        f.add(ticket(i, arrival=0.0), now=0.0)
    wave = f.decide(now=0.0, expected_gap=0.01)
    assert [t.rid for t in wave] == [0, 1, 2, 3]    # FIFO
    assert len(f.pending) == 1
    assert f.dispatch_reasons == {"full": 1}


def test_former_unknown_rate_never_holds():
    f = SV.WaveFormer(FakePredictor(gain=100.0), slo_s=10.0,
                      max_batch=8, max_hold_s=60.0)
    f.add(ticket(0, arrival=0.0), now=0.0)
    wave = f.decide(now=0.0, expected_gap=math.inf)
    assert wave is not None
    assert f.dispatch_reasons == {"unknown_rate": 1}


def test_former_hold_cap_expires():
    f = SV.WaveFormer(FakePredictor(shared_s=0.01, gain=100.0),
                      slo_s=10.0, max_batch=8, max_hold_s=0.2)
    f.add(ticket(0, arrival=0.0), now=0.0)
    assert f.decide(now=0.1, expected_gap=0.05) is None
    assert f.decide(now=0.21, expected_gap=0.05) is not None
    assert f.dispatch_reasons == {"hold_cap": 1}


def test_former_drain_flushes_everything():
    f = SV.WaveFormer(FakePredictor(gain=100.0), slo_s=10.0, max_batch=2)
    for i in range(3):
        f.add(ticket(i, arrival=0.0), now=0.0)
    waves = []
    while True:
        w = f.decide(now=0.0, expected_gap=0.01, draining=True)
        if not w:
            break
        waves.append(w)
    assert [len(w) for w in waves] == [2, 1] and not f.pending


def test_former_next_wakeup_tracks_hold_cap_and_slack():
    f = SV.WaveFormer(FakePredictor(shared_s=0.0, gain=100.0),
                      slo_s=10.0, max_batch=8, max_hold_s=0.25)
    assert f.next_wakeup(now=0.0) is None
    f.add(ticket(0, arrival=0.0), now=0.0)
    # hold cap (0.25s) binds before the 10s SLO slack does
    assert f.next_wakeup(now=0.0) == pytest.approx(0.25)
    f2 = SV.WaveFormer(FakePredictor(shared_s=0.0, gain=100.0),
                       slo_s=0.1, max_batch=8, max_hold_s=60.0)
    f2.add(ticket(0, arrival=0.0), now=0.0)
    assert f2.next_wakeup(now=0.0) == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# marginal cost model
# ---------------------------------------------------------------------------


def test_predict_marginal_duplicate_member_is_free():
    # in-wave dedup: a candidate identical to an existing member adds
    # no stacked slot, so its marginal cost is ~0 and the gain is ~its
    # entire solo cost
    plans = [QUERIES["q2.1"], QUERIES["q3.1"]]
    out = M.predict_marginal(plans, DB, candidate=QUERIES["q2.1"])
    assert out["marginal_cost"] == pytest.approx(0.0, abs=1e-9)
    assert out["gain"] == pytest.approx(out["solo"], rel=1e-6)


def test_predict_marginal_new_member_costs_less_than_solo():
    plans = [QUERIES["q2.1"], QUERIES["q2.2"]]
    out = M.predict_marginal(plans, DB, candidate=QUERIES["q3.1"])
    assert 0.0 < out["marginal_cost"] < out["solo"]
    assert out["gain"] == pytest.approx(out["solo"] - out["marginal_cost"])


def test_governor_pressure_clears_result_cache():
    # the PR 8 eviction bug: on_pressure dropped decode memos and cold
    # hash tables but left finished grids resident — the cheapest state
    # to rebuild survived while the expensive state died
    rc = ResultCache()
    assert rc.insert(DB, QUERIES["q2.1"], oracle(QUERIES["q2.1"]))
    gov = RS.ResourceGovernor(1 << 20)
    evicted_before = gov.evictions
    gov.on_pressure(result_cache=rc)
    assert len(rc) == 0
    assert gov.evictions > evicted_before


# ---------------------------------------------------------------------------
# serving loop end-to-end
# ---------------------------------------------------------------------------


def test_serving_loop_bit_identical_and_caches():
    q21, q31 = QUERIES["q2.1"], QUERIES["q3.1"]
    variants = engine.ssb_narrowed_variants(QUERIES)
    with SV.ServingLoop(DB, mode="ref", slo_s=5.0) as loop:
        first = [loop.submit(p) for p in (q21, q31)]
        for t, p in zip(first, (q21, q31)):
            r = t.wait(timeout=120)
            assert r.error is None
            np.testing.assert_array_equal(np.asarray(r.result), oracle(p))
            assert t.latency_s is not None and t.latency_s >= 0
        # exact repeat: answered from the result cache
        r = loop.submit(q21).wait(timeout=120)
        assert r.cache_hit and not r.subsumption_hit
        assert r.strategy == "cached" and r.error is None
        np.testing.assert_array_equal(np.asarray(r.result), oracle(q21))
        # narrowed variant of a cached parent: subsumption-served,
        # still bit-identical to its own oracle
        name, (parent, narrowed) = next(iter(variants.items()))
        pr = loop.submit(QUERIES[parent]).wait(timeout=120)
        assert pr.error is None
        r = loop.submit(narrowed).wait(timeout=120)
        assert r.subsumption_hit and r.cache_hit
        np.testing.assert_array_equal(np.asarray(r.result),
                                      oracle(narrowed))


def test_serving_loop_drains_on_stop():
    loop = SV.ServingLoop(DB, mode="ref", slo_s=5.0)
    loop.start()
    tickets = [loop.submit(p) for p in POOL[:6]]
    loop.stop()                         # drain: no ticket left hanging
    for t in tickets:
        assert t.done()
        assert t.result.error is None or t.result.error.error_kind
    with pytest.raises(RuntimeError):
        loop.submit(POOL[0])            # stopped loop rejects submits


def test_serving_loop_sheds_at_the_door():
    loop = SV.ServingLoop(DB, mode="ref")
    loop.start()
    try:
        gov = loop.server.governor
        gov.consecutive = gov.high_water        # sustained pressure
        with pytest.raises(RS.MemoryPressure):
            loop.submit(POOL[0])
        assert loop.server.stats["sheds"] >= 1
    finally:
        loop.server.governor.consecutive = 0
        loop.stop()


def test_serving_loop_queue_expired_deadline_is_typed():
    with SV.ServingLoop(DB, mode="ref", slo_s=5.0) as loop:
        t = loop.submit(QUERIES["q1.1"], deadline_s=1e-9)
        r = t.wait(timeout=120)
        assert r.error is not None
        assert r.error.error_kind == "DeadlineExceeded"
        assert r.result is None


# ---------------------------------------------------------------------------
# pool-anchored executables
# ---------------------------------------------------------------------------


def test_anchored_wave_bit_identical_any_membership():
    # the anchor widens the footprint with inert streams; results must
    # not change for any member subset, in any submission order
    for lo in (0, 3, 9):
        wave = POOL[lo:lo + 4]
        got, _ = C.execute_shared_morsels(wave, DB, mode="ref",
                                          pad_to=4, anchor=POOL)
        for r, p in zip(got, wave):
            np.testing.assert_array_equal(r, oracle(p))


def test_anchor_for_keeps_only_legal_members():
    assert C.anchor_for(POOL[:2], None) is None
    kept = C.anchor_for(POOL[:2], POOL)
    assert kept is not None and len(kept) == len(POOL)


def test_serving_loop_prewarm_counts_buckets():
    loop = SV.ServingLoop(DB, mode="ref", max_batch=4, warm_pool=POOL)
    assert loop.prewarm() == 3          # pow2 buckets 1, 2, 4
    # prewarm must not pre-answer traffic through the result cache
    assert len(loop.server.result_cache) == 0
    with loop:
        r = loop.submit(POOL[5]).wait(timeout=120)
        assert r.error is None
        np.testing.assert_array_equal(np.asarray(r.result),
                                      oracle(POOL[5]))


def test_serving_loop_submit_many_rides_one_wave():
    # a burst admitted as one arrival is routed whole before the former
    # decides, so it cannot be split by the worker waking mid-burst
    burst = POOL[:6]
    with SV.ServingLoop(DB, mode="ref", max_batch=8, slo_s=5.0) as loop:
        tickets = loop.submit_many(burst, strategy="shared")
        results = [t.wait(timeout=120) for t in tickets]
    assert [r.shared_wave_size for r in results] == [len(burst)] * 6
    for r, p in zip(results, burst):
        assert r.error is None and r.strategy == "shared"
        np.testing.assert_array_equal(np.asarray(r.result), oracle(p))
