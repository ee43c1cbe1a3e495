"""Least bytes per query from the configuration's domains, and the
peaks table."""
import pytest

from chipbench import roofline

# the SF-10 domains of the derived measures, in cents
EXTENDEDPRICE = 10_494_951 - 90_097         # 24 bits
SUPPLYCOST = 125_940 - 54_058               # 17 bits


def test_least_bytes_q1_1(tiny_cfg):
    # orderdate 2404 days -> 12 bits, discount 11 -> 4, quantity 50 -> 6,
    # extendedprice -> 24: 46 bits a row; the date dimension over the
    # order dates' key span of 2404 days at d_year's 7 values -> 3 bits
    assert roofline.bits(EXTENDEDPRICE) == 24
    n = tiny_cfg["rows"]["lineorder"]
    assert roofline.least_bytes(tiny_cfg, "q1.1") == -(-(n * 46 + 2404 * 3)
                                                        // 8)


def test_least_bytes_q4_1(tiny_cfg):
    # custkey 1500 -> 11 bits, suppkey 100 -> 7, partkey 10000 -> 14,
    # orderdate 12, revenue 24, supplycost 17: 85 bits a row; the
    # dimensions over their key spans: customer (c_region 3 bits,
    # c_nation 5), supplier (s_region 3), part (p_mfgr 3), date (d_year 3)
    assert roofline.bits(SUPPLYCOST) == 17
    n = tiny_cfg["rows"]["lineorder"]
    dims = 1500 * (3 + 5) + 100 * 3 + 10000 * 3 + 2404 * 3
    assert roofline.least_bytes(tiny_cfg, "q4.1") == -(-(n * 85 + dims)
                                                        // 8)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99 imaginary")


def test_v5e_peaks_from_the_published_table():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert "source" in p
