"""The trace reduction: intervals, idle gaps charged to benchmark spans,
and a small trace recorded on one TPU v5e: q1.1 then q3.4 through
``QueryServer`` over 65,632 fact rows, under the benchmark's spans, with
``run._options()``."""
from pathlib import Path

import pytest

from chipbench import trace as TR

RECORDED = Path(__file__).parent / "data" / "tpu_v5e_small.xplane.pb"


def test_union_merges_overlaps_and_keeps_gaps():
    assert TR.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_gaps_cover_the_window_outside_busy_time():
    assert TR.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert TR.gaps([], 0, 10) == [(0, 10)]


def test_reduce_charges_gaps_to_the_innermost_span():
    device = {"/device:TPU:0": [("fusion.1", 10, 40), ("fusion.2", 60, 90),
                                ("fusion.1", 85, 95)]}
    spans = [("chipbench.window", 0, 100), ("chipbench.run", 5, 95),
             ("chipbench.submit", 40, 60)]
    r = TR.reduce(device, spans)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(65e-9)      # [10,40) + [60,95)
    assert r.devices == 1
    assert r.ops == [("fusion.1", pytest.approx(40e-9)),
                     ("fusion.2", pytest.approx(30e-9))]
    assert dict(r.idle_gaps) == {"chipbench.run": pytest.approx(10e-9),
                                 "chipbench.submit": pytest.approx(20e-9),
                                 "chipbench.window": pytest.approx(5e-9)}


def test_reduce_clips_to_the_window_and_averages_devices():
    device = {"/device:TPU:0": [("a", 0, 50)],
              "/device:TPU:1": [("a", 20, 40)],
              "/device:TPU:2": [("a", 200, 300)]}      # outside: unused
    r = TR.reduce(device, [("chipbench.window", 10, 60)])
    assert r.devices == 2
    assert r.busy_s == pytest.approx((40 + 20) / 2 * 1e-9)


def test_recorded_tpu_trace():
    r = TR.reduce_file(RECORDED)
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.014201118)
    assert r.busy_s == pytest.approx(0.008175235)
    assert r.ops[0] == ("jit__spja_xla:fusion.3", pytest.approx(0.000976942))
    assert 0 < r.busy_s <= r.window_s
    assert r.ops and all(s > 0 for _, s in r.ops)
    assert {n for n, _ in r.idle_gaps} <= {
        "chipbench.window", "chipbench.submit", "chipbench.run",
        TR.OUTSIDE}
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
