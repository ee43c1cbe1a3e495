"""Fixtures shared by the benchmark's own tests: configurations shrunk
to a size a CPU test run holds, with every width and domain kept."""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def shrink(cfg: dict, lineorder: int, supplier: int = 100,
           customer: int = 1500, part: int = 10000) -> dict:
    """``cfg`` at a small row count: the fact keys' domains follow the
    dimensions' sizes, every other domain stays as configured."""
    c = copy.deepcopy(cfg)
    c["rows"] = {"lineorder": lineorder, "supplier": supplier,
                 "customer": customer, "part": part}
    for col, n in (("lo_partkey", part), ("lo_suppkey", supplier),
                   ("lo_custkey", customer)):
        c["lineorder"][col] = [0, n]
    c["lineorder"]["lo_orderkey"] = [1, lineorder + 1]
    return c


@pytest.fixture(scope="session")
def tiny_cfg():
    from chipbench import spec
    return shrink(spec.config("ssb_sf10"), 200_000)
