"""The least bytes a query must read, and the chip's peaks.

``least_bytes`` counts, from the configuration alone, what any
implementation of a query has to move: each fact column the query
references at ceil(log2(domain)) bits per row, plus each dimension it
joins over its key span, at the bits of the dimension attributes the
query filters or groups on.  It never looks at the program's encodings,
so the same work reads the same number whatever implements it.
``query_roofline`` divides the time those bytes take at the chip's
peak bandwidth by the device time the queries took.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Set

from chipbench import data, reference

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def bits(n_values: int) -> int:
    return max(1, math.ceil(math.log2(n_values)))


def _dim_domains(cfg: dict) -> Dict[str, Dict[str, int]]:
    """Distinct values of each dimension attribute under ``cfg``."""
    d = cfg["dictionary"]
    nations = d["regions"] * d["nations_per_region"]
    cities = nations * d["cities_per_nation"]
    categories = d["mfgrs"] * d["categories_per_mfgr"]
    place = {"city": cities, "nation": nations, "region": d["regions"]}
    date = {c: len(set(v.tolist()))
            for c, v in data.date_table(cfg["calendar"]).items()}
    return {
        "date": date,
        "supplier": {"s_" + k: v for k, v in place.items()},
        "customer": {"c_" + k: v for k, v in place.items()},
        "part": {"p_brand1": categories * d["brands_per_category"],
                 "p_category": categories, "p_mfgr": d["mfgrs"]},
    }


def least_bytes(cfg: dict, name: str) -> int:
    """Bytes query ``name`` must read at least over ``cfg``'s tables."""
    q = reference.QUERIES[name]
    fact_cols: Set[str] = {p[0] for p in q.where}
    fact_cols |= {j.fact_col for j in q.joins}
    fact_cols |= {m for m in q.measure if m in cfg["lineorder"]}
    fact_bits = sum(bits(hi - lo) for c, (lo, hi) in cfg["lineorder"].items()
                    if c in fact_cols)
    total = cfg["rows"]["lineorder"] * fact_bits
    domains = _dim_domains(cfg)
    for j in q.joins:
        attrs = {p[0] for p in j.where} | {g.col for g in q.group
                                           if g.dim == j.dim}
        span = cfg["lineorder"][j.fact_col][1] - cfg["lineorder"][j.fact_col][0]
        total += span * sum(bits(domains[j.dim][a]) for a in attrs)
    return math.ceil(total / 8)
