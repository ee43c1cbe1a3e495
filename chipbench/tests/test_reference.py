"""The benchmark's reference and the program's numpy oracle
(``engine.run_query_oracle``) agree on all 13 SSB queries.  The two are
written independently, so agreement checks both.  The data follow the
SSB rules, and the reference's sums are exact."""
import numpy as np
import pytest

from chipbench import data, reference
from chipbench.tests.conftest import shrink


@pytest.fixture(scope="module")
def db(tiny_cfg):
    tables = data.generate(shrink(tiny_cfg, 300_000), seed=2**35 + 1)
    return tables, data.to_program(tables, 0.05)


@pytest.mark.parametrize("name", list(reference.QUERIES))
def test_reference_matches_program_oracle(db, name):
    from repro.sql import engine
    tables, packed = db
    want = engine.run_query_oracle(packed, engine.ssb_queries()[name])
    got = reference.answer(tables, reference.QUERIES[name])
    assert got.dtype == np.int64 and got.shape == want.shape
    assert np.array_equal(want, got.astype(np.float32))
    assert reference.gap(want, got) == 0.0


def test_same_seed_same_tables(tiny_cfg):
    a = data.generate(tiny_cfg, 2**40 + 3)
    b = data.generate(tiny_cfg, 2**40 + 3)
    c = data.generate(tiny_cfg, 2**40 + 4)
    for t in a:
        for col in a[t]:
            assert np.array_equal(a[t][col], b[t][col])
    assert not np.array_equal(a["lineorder"]["lo_revenue"],
                              c["lineorder"]["lo_revenue"])


def test_tables_keep_configured_domains(tiny_cfg):
    t = data.generate(tiny_cfg, 9)
    assert set(t["lineorder"]) == set(tiny_cfg["lineorder"])
    assert len(t["lineorder"]) == 17
    for col, (lo, hi) in tiny_cfg["lineorder"].items():
        v = t["lineorder"][col]
        assert len(v) == tiny_cfg["rows"]["lineorder"]
        assert v.min() >= lo and v.max() < hi, col
    assert not t["lineorder"]["lo_revenue"].flags.writeable


def test_measures_follow_the_ssb_rules(tiny_cfg):
    lo = data.generate(shrink(tiny_cfg, 100_000), 2**36 + 7)["lineorder"]
    price = data.retail_price(10000).astype(np.int64)[lo["lo_partkey"]]
    ext = lo["lo_quantity"] * price
    assert np.array_equal(lo["lo_extendedprice"], ext)
    assert np.array_equal(lo["lo_revenue"],
                          ext * (100 - lo["lo_discount"]) // 100)
    assert np.array_equal(lo["lo_supplycost"], 6 * price // 10)
    # orders: consecutive lines numbered from 1, sharing the order's
    # date, customer and total; commit dates 30..90 days on
    key = lo["lo_orderkey"]
    starts = np.flatnonzero(np.diff(key, prepend=0))
    assert np.array_equal(key[starts], np.arange(1, len(starts) + 1))
    assert (lo["lo_linenumber"][starts] == 1).all()
    assert lo["lo_linenumber"].max() <= 7
    line = ext * (100 + lo["lo_tax"]) * (100 - lo["lo_discount"]) // 10000
    total = np.add.reduceat(line, starts)
    for col in ("lo_orderdate", "lo_custkey", "lo_ordtotalprice"):
        v = lo[col]
        assert (v == np.repeat(v[starts], np.diff(starts, append=len(v)))
                ).all(), col
    assert np.array_equal(lo["lo_ordtotalprice"][starts], total)
    wait = lo["lo_commitdate"] - lo["lo_orderdate"]
    assert wait.min() >= 30 and wait.max() <= 90


def test_chunked_packing_equals_pack_column(tiny_cfg, monkeypatch):
    from repro.sql import storage
    monkeypatch.setattr(data, "CHUNK_ROWS", 1 << 10)
    t = data.generate(shrink(tiny_cfg, 5_000), 3)
    db = data.to_program(t, 0.001)
    for name, cols in t.items():
        for c, v in cols.items():
            got = getattr(db, name).columns[c]
            want = storage.pack_column(v)
            assert got.encoding == want.encoding, c
            assert np.array_equal(got.words, want.words), c


def test_exact_sums_past_float64():
    m = np.full(1 << 20, (1 << 40) + 3, np.int64)
    got = reference.group_sums(np.zeros(len(m), np.int64), m, 1)
    assert int(got[0]) == len(m) * ((1 << 40) + 3)       # past 2^53
    neg = reference.group_sums(np.array([0, 0, 1]),
                               np.array([-5, 2, -(1 << 30)]), 2)
    assert neg.tolist() == [-3, -(1 << 30)]


def test_gap_counts_float32_steps():
    exact = np.array([(1 << 44) + 12345, 7, 0], np.int64)
    rounded = exact.astype(np.float32)
    assert reference.gap(rounded, exact) == 0.0
    up = rounded.copy()
    up[0] = np.nextafter(up[0], np.float32(np.inf))
    assert reference.gap(up, exact) == 1.0
    off = rounded.copy()
    off[1] = 8.0                      # 7 -> 8 is 2^21 float32 steps
    assert reference.gap(off, exact) == float(1 << 21)
    neg = rounded.copy()
    neg[2] = -np.float32(1e-45)
    assert reference.gap(neg, exact) == 1.0


def test_gap_reads_missing_and_misshapen_answers_as_infinite():
    want = np.array([1, 2], np.int64)
    assert reference.gap(None, want) == float("inf")
    assert reference.gap(np.zeros(3, np.float32), want) == float("inf")
    assert reference.gap(np.array([1.0, np.nan]), want) == float("inf")
    assert reference.gap(want.astype(np.float32), want) == 0.0
