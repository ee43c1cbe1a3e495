"""The plain reference: the 13 Star Schema Benchmark queries over plain
numpy columns.

Written from the SSB specification (O'Neil et al., rev. 3) in the
dictionary codes of the configuration files, independent of the program:
it imports nothing of ``repro`` and reads only the tables
``chipbench.data`` drew.  A join is an equi-join on the dimension's
unique key (a key -> row lookup); a dimension filter keeps the fact rows
whose dimension row passes; each group-by attribute maps to a dense
index, and the groups' indices combine major to minor in the order
listed, the layout the program's answers use.  Sums over the integer
measures are exact, in int64, as SQL's SUM is.

``answer(..., precision="f32")`` is the control: the same evaluation
with the sums accumulated in float32, row by row.  ``gap`` counts how
many float32 steps an answer lies from the exact sum rounded once to
float32.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

Pred = Tuple[str, str, tuple]         # (column, "between" | "in", values)


def between(col: str, lo: int, hi: int) -> Pred:
    return (col, "between", (lo, hi))


def eq(col: str, v: int) -> Pred:
    return (col, "in", (v,))


def isin(col: str, *vs: int) -> Pred:
    return (col, "in", tuple(vs))


@dataclass(frozen=True)
class Join:
    fact_col: str
    dim: str
    key: str
    where: Tuple[Pred, ...] = ()


@dataclass(frozen=True)
class GroupKey:
    """Index of a group-by attribute: ``value - base`` over ``size``
    values, or the position of the value in ``values``."""
    dim: str
    col: str
    base: int = 0
    size: int = 0
    values: Tuple[int, ...] = ()

    @property
    def n(self) -> int:
        return len(self.values) if self.values else self.size


@dataclass(frozen=True)
class Query:
    joins: Tuple[Join, ...]
    measure: Tuple[str, ...]          # ("lo_revenue",), or (op, m1, m2)
    where: Tuple[Pred, ...] = ()      # on fact columns
    group: Tuple[GroupKey, ...] = ()

    @property
    def n_groups(self) -> int:
        return int(np.prod([g.n for g in self.group], dtype=np.int64))


def _date(*where: Pred) -> Join:
    return Join("lo_orderdate", "date", "d_datekey", tuple(where))


def _supp(*where: Pred) -> Join:
    return Join("lo_suppkey", "supplier", "s_suppkey", tuple(where))


def _cust(*where: Pred) -> Join:
    return Join("lo_custkey", "customer", "c_custkey", tuple(where))


def _part(*where: Pred) -> Join:
    return Join("lo_partkey", "part", "p_partkey", tuple(where))


AMERICA, ASIA, EUROPE = 1, 2, 3
UNITED_STATES = 8
UKI1, UKI5 = 191, 195
YEAR = GroupKey("date", "d_year", base=1992, size=7)
YEAR_92_97 = GroupKey("date", "d_year", base=1992, size=6)
YEAR_97_98 = GroupKey("date", "d_year", base=1997, size=2)
PRICE_X_DISCOUNT = ("mul", "lo_extendedprice", "lo_discount")
PROFIT = ("sub", "lo_revenue", "lo_supplycost")
REVENUE = ("lo_revenue",)
UK_CITIES = (UKI1, UKI5)

QUERIES: Dict[str, Query] = {
    # flight 1: SUM(extendedprice * discount), no group-by
    "q1.1": Query((_date(eq("d_year", 1993)),), PRICE_X_DISCOUNT,
                  (between("lo_discount", 1, 3), between("lo_quantity", 1, 24))),
    "q1.2": Query((_date(eq("d_yearmonthnum", 199401)),), PRICE_X_DISCOUNT,
                  (between("lo_discount", 4, 6),
                   between("lo_quantity", 26, 35))),
    "q1.3": Query((_date(eq("d_weeknuminyear", 6), eq("d_year", 1994)),),
                  PRICE_X_DISCOUNT,
                  (between("lo_discount", 5, 7),
                   between("lo_quantity", 26, 35))),
    # flight 2: SUM(revenue) by d_year, p_brand1
    "q2.1": Query((_part(eq("p_category", 1)), _supp(eq("s_region", AMERICA)),
                   _date()), REVENUE,
                  group=(YEAR, GroupKey("part", "p_brand1", size=1000))),
    "q2.2": Query((_part(between("p_brand1", 260, 267)),
                   _supp(eq("s_region", ASIA)), _date()), REVENUE,
                  group=(YEAR, GroupKey("part", "p_brand1", size=1000))),
    "q2.3": Query((_part(eq("p_brand1", 260)), _supp(eq("s_region", EUROPE)),
                   _date()), REVENUE,
                  group=(YEAR, GroupKey("part", "p_brand1", size=1000))),
    # flight 3: SUM(revenue) by customer place, supplier place, d_year
    "q3.1": Query((_cust(eq("c_region", ASIA)), _supp(eq("s_region", ASIA)),
                   _date(between("d_year", 1992, 1997))), REVENUE,
                  group=(GroupKey("customer", "c_nation", base=10, size=5),
                         GroupKey("supplier", "s_nation", base=10, size=5),
                         YEAR_92_97)),
    "q3.2": Query((_cust(eq("c_nation", UNITED_STATES)),
                   _supp(eq("s_nation", UNITED_STATES)),
                   _date(between("d_year", 1992, 1997))), REVENUE,
                  group=(GroupKey("customer", "c_city", base=80, size=10),
                         GroupKey("supplier", "s_city", base=80, size=10),
                         YEAR_92_97)),
    "q3.3": Query((_cust(isin("c_city", *UK_CITIES)),
                   _supp(isin("s_city", *UK_CITIES)),
                   _date(between("d_year", 1992, 1997))), REVENUE,
                  group=(GroupKey("customer", "c_city", values=UK_CITIES),
                         GroupKey("supplier", "s_city", values=UK_CITIES),
                         YEAR_92_97)),
    "q3.4": Query((_cust(isin("c_city", *UK_CITIES)),
                   _supp(isin("s_city", *UK_CITIES)),
                   _date(eq("d_yearmonthnum", 199712))), REVENUE,
                  group=(GroupKey("customer", "c_city", values=UK_CITIES),
                         GroupKey("supplier", "s_city", values=UK_CITIES),
                         YEAR_92_97)),
    # flight 4: SUM(revenue - supplycost)
    "q4.1": Query((_cust(eq("c_region", AMERICA)),
                   _supp(eq("s_region", AMERICA)),
                   _part(between("p_mfgr", 0, 1)), _date()), PROFIT,
                  group=(GroupKey("customer", "c_nation", base=5, size=5),
                         YEAR)),
    "q4.2": Query((_cust(eq("c_region", AMERICA)),
                   _supp(eq("s_region", AMERICA)),
                   _part(between("p_mfgr", 0, 1)),
                   _date(isin("d_year", 1997, 1998))), PROFIT,
                  group=(YEAR_97_98,
                         GroupKey("supplier", "s_nation", base=5, size=5),
                         GroupKey("part", "p_category", size=10))),
    "q4.3": Query((_cust(eq("c_region", AMERICA)),
                   _supp(eq("s_nation", UNITED_STATES)),
                   _part(eq("p_category", 3)),
                   _date(isin("d_year", 1997, 1998))), PROFIT,
                  group=(YEAR_97_98,
                         GroupKey("supplier", "s_city", base=80, size=10),
                         GroupKey("part", "p_brand1", base=120, size=40))),
}

PRECISIONS = ("exact", "f32")


def _holds(values: np.ndarray, pred: Pred) -> np.ndarray:
    _, kind, args = pred
    if kind == "between":
        return (values >= args[0]) & (values <= args[1])
    return np.isin(values, args)


def _key_rows(keys: np.ndarray) -> np.ndarray:
    """Lookup key -> dimension row (-1: no such key)."""
    rows = np.full(int(keys.max()) + 1, -1, np.int64)
    rows[keys] = np.arange(len(keys))
    return rows


def _lookup(rows: np.ndarray, fk: np.ndarray) -> np.ndarray:
    """Dimension row of each foreign key, -1 where no key matches."""
    inside = (fk >= 0) & (fk < len(rows))
    return np.where(inside, rows[np.clip(fk, 0, len(rows) - 1)], -1)


def answer(tables, q: Query, precision: str = "exact") -> np.ndarray:
    """(n_groups,) group sums of the query over ``tables``: int64 and
    exact, or float32 accumulated in float32 for ``precision="f32"``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    fact = tables["lineorder"]
    sel: Optional[np.ndarray] = None          # surviving fact rows

    def column(col: str) -> np.ndarray:
        return fact[col] if sel is None else fact[col][sel]

    def narrow(keep: np.ndarray) -> None:
        nonlocal sel
        sel = np.flatnonzero(keep) if sel is None else sel[keep]

    for pred in q.where:
        narrow(_holds(column(pred[0]), pred))
    key_rows = {}
    for j in q.joins:
        dim = tables[j.dim]
        key_rows[j.dim] = (_key_rows(dim[j.key]), j.fact_col)
        passes = np.ones(len(dim[j.key]), bool)
        for pred in j.where:
            passes &= _holds(dim[pred[0]], pred)
        row = _lookup(key_rows[j.dim][0], column(j.fact_col))
        narrow((row >= 0) & passes[np.maximum(row, 0)])
    if len(q.measure) == 1:
        measure = column(q.measure[0]).astype(np.int64)
    else:
        op, m1, m2 = q.measure
        a, b = column(m1).astype(np.int64), column(m2).astype(np.int64)
        measure = a * b if op == "mul" else a - b
    group = np.zeros(len(measure), np.int64)
    for g in q.group:
        rows, fact_col = key_rows[g.dim]
        v = tables[g.dim][g.col][_lookup(rows, column(fact_col))]
        ix = (np.searchsorted(g.values, v) if g.values
              else v.astype(np.int64) - g.base)
        group = group * g.n + ix
    return group_sums(group, measure, q.n_groups, precision)


LOW_BITS = 24


def group_sums(group: np.ndarray, measure: np.ndarray, n_groups: int,
               precision: str = "exact") -> np.ndarray:
    """Per-group sums of int64 ``measure``.  Exact: the high and low
    ``LOW_BITS`` of each value summed apart, each in float64 with no
    rounding while a part's sum stays below 2^53, then joined in int64."""
    if precision == "f32":
        out = np.zeros(n_groups, np.float32)
        np.add.at(out, group, measure.astype(np.float32))   # in row order
        return out
    hi, lo = measure >> LOW_BITS, measure & ((1 << LOW_BITS) - 1)
    for part, top in ((hi, int(np.abs(hi).max(initial=0))),
                      (lo, (1 << LOW_BITS) - 1)):
        if len(part) * top >= 2 ** 53:
            raise OverflowError("a part's float64 sum would round")
    sums = [np.bincount(group, weights=part.astype(np.float64),
                        minlength=n_groups).astype(np.int64)
            for part in (hi, lo)]
    return (sums[0] << LOW_BITS) + sums[1]


def answers(tables, names: Sequence[str], precision: str = "exact",
            threads: int = 4) -> Dict[str, np.ndarray]:
    """Each named query's answer, a few queries at a time (numpy's
    loops release the interpreter lock)."""
    from concurrent.futures import ThreadPoolExecutor
    names = list(dict.fromkeys(names))
    with ThreadPoolExecutor(max(1, min(threads, len(names)))) as pool:
        out = pool.map(lambda n: answer(tables, QUERIES[n], precision),
                       names)
        return dict(zip(names, out))


def _ordered(x: np.ndarray) -> np.ndarray:
    """float32 values as int64 keys in their order, one apart for
    neighbours (+0 and -0 alike)."""
    i = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
    return np.where(i < 0, -(i & 0x7FFFFFFF), i)


def gap(got, want: np.ndarray) -> float:
    """Widest distance, in float32 steps, of an answer's group from the
    exact sum ``want`` rounded once to float32: 0 where every group is
    that rounding, 1 for a neighbour of it, inf for a missing, misshapen
    or not-a-number answer."""
    if got is None:
        return float("inf")
    got = np.asarray(got)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    steps = np.abs(_ordered(got.astype(np.float32))
                   - _ordered(want.astype(np.float32)))
    return float(steps.max(initial=0))
