"""The control comes out as not correct against the exact reference at
a size a test run holds, on the queries of each cell: the reference
with its sums accumulated in float32, row by row."""
import pytest

from chipbench import data, reference, spec
from chipbench.tests.conftest import shrink

SEEDS = (2**33 + 1, 2**33 + 2, 2**33 + 3)


def control_steps(cfg, config, traffic, seed):
    tables = data.generate(cfg, seed)
    limit = spec.config(config)["limits"]["max_f32_steps"]
    queries = spec.traffic(traffic)["queries"]
    exact = reference.answers(tables, queries)
    ctl = reference.answers(tables, queries, "f32")
    return max(reference.gap(ctl[n], exact[n]) for n in queries), limit


@pytest.mark.parametrize("seed", SEEDS)
def test_f32_control_fails_flight1(tiny_cfg, seed):
    steps, limit = control_steps(shrink(tiny_cfg, 3_000_000), "ssb_sf20",
                                 "flight1", seed)
    assert steps > limit


@pytest.mark.parametrize("seed", SEEDS)
def test_f32_control_fails_joins(tiny_cfg, seed):
    steps, limit = control_steps(tiny_cfg, "ssb_sf10", "joins", seed)
    assert steps > limit


def test_limit_readings_on_the_timed_path(tiny_cfg):
    from chipbench import limits
    cell = spec.cell("ssb_sf20.flight1", spec.benchmark())
    r = limits.readings(cell, tiny_cfg, spec.traffic(cell.traffic),
                        SEEDS[0], controls=True)
    assert r["program"] == 0.0 and r["faults"] == 0
    assert set(r["f32_by_query"]) == set(spec.traffic("flight1")["queries"])
    assert r["f32"] > 1
