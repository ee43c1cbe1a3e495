"""The benchmark's own SSB data, drawn from ``--seed`` by a configuration.

The rules are the Star Schema Benchmark's (O'Neil et al., rev. 3, after
TPC-H's dbgen), in the dictionary codes the program's plans use:

* all 17 lineorder columns.  Orders of 1 to 7 lines (uniform); an
  order's lines share its order key, date, customer, priorities and
  total price; the table has exactly ``rows["lineorder"]`` rows (the
  last order is cut there).
* prices in integer cents: a part's retail price is
  ``90000 + (K // 10) % 20001 + 100 * (K % 1000)`` for its 1-based key
  ``K``; ``lo_extendedprice = lo_quantity * price``, ``lo_revenue =
  lo_extendedprice * (100 - lo_discount) // 100``, ``lo_supplycost =
  6 * price // 10``, and ``lo_ordtotalprice`` sums
  ``lo_extendedprice * (100 + lo_tax) * (100 - lo_discount) // 10000``
  over the order's lines;
* order dates uniform over the calendar less its last 151 days, commit
  dates 30 to 90 days later; every other drawn column uniform over the
  configuration's domain; dimension keys dense from 0.

``generate`` returns plain numpy tables, which stay with the benchmark
for the reference; ``load`` is every caller's set-up: it draws them,
hands the program its database in the configuration's ``layout``, and
places it on the device by ``fact_morsel_bytes``:

    layout             "packed" (the default): each column encoded as
                       ``storage.pack_database`` encodes it;
                       "plain": every column one int32 word a row, as
                       Crystal stores them, sharing the drawn buffers
    fact_morsel_bytes  absent (the default): every table resident on
                       the device, the fact table streamed as one
                       morsel; a positive integer: the dimensions
                       resident, the fact table held on the host and
                       streamed in morsels of that many bytes

The draws go in chunks of rows, each from a stream of its own spawned
from the seed, on threads (numpy releases the interpreter lock): the
same seed gives the same tables on any number of threads.
"""
from __future__ import annotations

import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Tuple

import numpy as np

Tables = Dict[str, Dict[str, np.ndarray]]

CHUNK_ROWS = 1 << 22            # a multiple of 32: chunks pack on words
LAST_ORDER_DAYS = 151           # orders end this many days before the end
COMMIT_DAYS = (30, 90)
MAX_LINES = 7

DIMENSIONS = ("date", "supplier", "customer", "part")
LAYOUTS = ("packed", "plain")
# every key a configuration file may hold; the loader refuses others, so
# a misspelt key cannot fall back to a default unseen
KEYS = frozenset({
    "name", "source", "sf", "reduced", "rows", "lineorder", "derived",
    "calendar", "dictionary", "storage", "guarantee", "deployment",
    "assumed", "limits", "layout", "fact_morsel_bytes"})


def date_table(cal: dict) -> Dict[str, np.ndarray]:
    n = cal["years"] * cal["days_per_year"]
    dk = np.arange(n, dtype=np.int32)
    year = cal["first_year"] + dk // cal["days_per_year"]
    day = dk % cal["days_per_year"]
    return {
        "d_datekey": dk,
        "d_year": year.astype(np.int32),
        "d_yearmonthnum": (year * 100 + day // cal["days_per_month"]
                           + 1).astype(np.int32),
        "d_weeknuminyear": (day // cal["days_per_week"] + 1).astype(np.int32),
    }


def retail_price(n_parts: int) -> np.ndarray:
    """Each part's retail price in cents, by 0-based part key."""
    k = np.arange(1, n_parts + 1, dtype=np.int64)
    return (90000 + (k // 10) % 20001 + 100 * (k % 1000)).astype(np.int32)


def _pool():
    return ThreadPoolExecutor(os.cpu_count() or 1)


def _orders(rng, rows: int):
    """Line counts of the orders that make up ``rows`` lines, the last
    one cut so that they sum to ``rows``."""
    counts = rng.integers(1, MAX_LINES + 1, rows // 4 + 64, dtype=np.int32)
    while counts.sum(dtype=np.int64) < rows:
        counts = np.concatenate([counts, rng.integers(
            1, MAX_LINES + 1, rows // 64 + 64, dtype=np.int32)])
    ends = np.cumsum(counts, dtype=np.int64)
    n = int(np.searchsorted(ends, rows)) + 1
    counts = counts[:n].copy()
    counts[-1] -= int(ends[n - 1] - rows)
    return counts


def generate(cfg: dict, seed: int) -> Tables:
    """All five tables of ``cfg`` from ``seed``: the same seed gives the
    same tables, and every seed the same sizes and domains."""
    rows, d, lo = cfg["rows"], cfg["dictionary"], cfg["lineorder"]
    n = rows["lineorder"]
    n_chunks = -(-n // CHUNK_ROWS)
    dim_seq, order_seq, *chunk_seqs = np.random.SeedSequence(seed).spawn(
        2 + n_chunks)
    rng = np.random.default_rng(dim_seq)
    n_cities = d["regions"] * d["nations_per_region"] * d["cities_per_nation"]
    n_brands = d["mfgrs"] * d["categories_per_mfgr"] * d["brands_per_category"]

    def city_dim(key: str, p: str, size: int) -> Dict[str, np.ndarray]:
        city = rng.integers(0, n_cities, size, dtype=np.int32)
        nation = city // d["cities_per_nation"]
        return {key: np.arange(size, dtype=np.int32), p + "city": city,
                p + "nation": nation,
                p + "region": nation // d["nations_per_region"]}

    supplier = city_dim("s_suppkey", "s_", rows["supplier"])
    customer = city_dim("c_custkey", "c_", rows["customer"])
    brand = rng.integers(0, n_brands, rows["part"], dtype=np.int32)
    category = brand // d["brands_per_category"]
    part = {"p_partkey": np.arange(rows["part"], dtype=np.int32),
            "p_brand1": brand, "p_category": category,
            "p_mfgr": category // d["categories_per_mfgr"]}
    price = retail_price(rows["part"])
    supplycost = (6 * price.astype(np.int64) // 10).astype(np.int32)

    # orders: each of its lines carries these
    orng = np.random.default_rng(order_seq)
    counts = _orders(orng, n)
    n_orders = len(counts)
    days = cfg["calendar"]["years"] * cfg["calendar"]["days_per_year"]
    order = {
        "lo_orderdate": orng.integers(0, days - LAST_ORDER_DAYS, n_orders,
                                      dtype=np.int32),
        "lo_custkey": orng.integers(*lo["lo_custkey"], n_orders,
                                    dtype=np.int32),
        "lo_orderpriority": orng.integers(*lo["lo_orderpriority"], n_orders,
                                          dtype=np.int32),
        "lo_shippriority": orng.integers(*lo["lo_shippriority"], n_orders,
                                         dtype=np.int32),
    }
    starts = np.concatenate([[0], np.cumsum(counts[:-1], dtype=np.int64)])
    of_row = np.repeat(np.arange(n_orders, dtype=np.int32), counts)

    cols = {c: np.empty(n, np.int32) for c in lo}
    line_total = np.empty(n, np.int32)

    def chunk(i: int) -> None:
        a, b = i * CHUNK_ROWS, min(n, (i + 1) * CHUNK_ROWS)
        r = np.random.default_rng(chunk_seqs[i])
        m = b - a

        def draw(c):
            return r.integers(*lo[c], m, dtype=np.int32)

        o = of_row[a:b]
        out = {c: v[o] for c, v in order.items()}
        out["lo_orderkey"] = o + 1
        out["lo_linenumber"] = (np.arange(a, b) - starts[o] + 1).astype(
            np.int32)
        for c in ("lo_partkey", "lo_suppkey", "lo_quantity", "lo_discount",
                  "lo_tax", "lo_shipmode"):
            out[c] = draw(c)
        out["lo_commitdate"] = out["lo_orderdate"] + r.integers(
            COMMIT_DAYS[0], COMMIT_DAYS[1] + 1, m, dtype=np.int32)
        pk = out["lo_partkey"]
        ext = out["lo_quantity"] * price[pk]
        out["lo_extendedprice"] = ext
        keep = 100 - out["lo_discount"]
        out["lo_revenue"] = ext * keep // 100
        out["lo_supplycost"] = supplycost[pk]
        line_total[a:b] = (ext.astype(np.int64) * (100 + out["lo_tax"])
                           * keep // 10000)
        for c, v in out.items():
            cols[c][a:b] = v

    with _pool() as pool:
        list(pool.map(chunk, range(n_chunks)))
    total = np.add.reduceat(line_total, starts, dtype=np.int64)
    cols["lo_ordtotalprice"] = total.astype(np.int32)[of_row]
    del of_row, line_total
    lineorder = {c: cols[c] for c in lo}
    tables = {"lineorder": lineorder, "date": date_table(cfg["calendar"]),
              "supplier": supplier, "customer": customer, "part": part}
    for t in tables.values():
        for arr in t.values():
            arr.flags.writeable = False   # the reference's copy stays as drawn
    return tables


def pack(values: np.ndarray, pool):
    """``values`` as ``storage.pack_column`` packs them: the encoding
    ``choose_encoding`` picks, the words ``pack_words`` makes, a chunk
    of rows at a time on ``pool``.  A column left plain shares the
    benchmark's read-only buffer."""
    from repro.sql import storage
    enc = storage.choose_encoding(values)
    if enc.kind == "plain":
        return storage.PackedColumn(enc, values)
    parts = pool.map(lambda a: storage.pack_words(
        values[a:a + CHUNK_ROWS], enc.width, enc.ref),
        range(0, len(values), CHUNK_ROWS))
    return storage.PackedColumn(enc, np.concatenate(list(parts)))


def plain(values: np.ndarray):
    """``values`` as one int32 word a row, Crystal's layout: the drawn
    buffer itself, not a copy."""
    from repro.sql import storage
    return storage.PackedColumn(
        storage.ColumnEncoding("plain", 32, 32, 0, len(values)), values)


def to_program(tables: Tables, sf: float, layout: str = "packed"):
    """The program's database: every table bit-packed as
    ``storage.pack_database`` packs it, or with ``layout="plain"`` every
    column plain."""
    from repro.sql import ssb, storage
    with _pool() as pool:
        column = plain if layout == "plain" else (lambda v: pack(v, pool))
        return ssb.Database(**{
            name: storage.PackedTable(name, {c: column(v)
                                             for c, v in cols.items()})
            for name, cols in tables.items()}, sf=sf)


def make_resident(db, names=("lineorder",) + DIMENSIONS) -> int:
    """Upload every column of the tables ``names`` to the device, as a
    database resident on the chip holds it, and wait for the copies;
    returns the bytes uploaded."""
    import jax
    arrays = [col.words_jax() for name in names
              for col in getattr(db, name).columns.values()]
    jax.block_until_ready(arrays)
    return sum(int(a.nbytes) for a in arrays)


def placement(cfg: dict) -> Tuple[str, Optional[int]]:
    """``cfg``'s ``layout`` and ``fact_morsel_bytes`` (None when
    absent); a key the loader does not know, or a value outside these,
    raises ``ValueError`` naming the key."""
    unknown = sorted(set(cfg) - KEYS)
    if unknown:
        raise ValueError(f"unknown configuration keys {unknown}; "
                         f"known: {sorted(KEYS)}")
    layout = cfg.get("layout", "packed")
    if layout not in LAYOUTS:
        raise ValueError(f"layout must be one of {LAYOUTS}, got {layout!r}")
    morsel = cfg.get("fact_morsel_bytes")
    if "fact_morsel_bytes" in cfg and (type(morsel) is not int
                                       or morsel <= 0):
        raise ValueError(f"fact_morsel_bytes must be a positive integer, "
                         f"got {morsel!r}")
    return layout, morsel


def load(cfg: dict, seed: int) -> Tuple[Tables, dict]:
    """One run's set-up: the tables drawn from ``seed``, and the keyword
    arguments of the ``QueryServer`` that serves the program's database,
    built by ``cfg``'s ``layout`` and placed by its ``fact_morsel_bytes``.
    The uploads are waited for."""
    layout, morsel_bytes = placement(cfg)
    t0 = time.perf_counter()

    def log(what: str) -> None:
        print(f"{what} s={time.perf_counter() - t0:.3f}", file=sys.stderr,
              flush=True)

    tables = generate(cfg, seed)
    log(f"generated seed={seed}")
    db = to_program(tables, cfg["sf"], layout)
    log(f"{layout} fact_bytes={db.lineorder.nbytes}")
    if morsel_bytes is None:        # the whole fact table is one morsel
        resident = make_resident(db)
        morsel_bytes = db.lineorder.nbytes
    else:                           # a morsel is a fresh slice: upload none
        resident = make_resident(db, DIMENSIONS)
    log(f"resident bytes={resident} morsel_bytes={morsel_bytes}")
    return tables, {"db": db, "mode": "auto", "morsel_bytes": morsel_bytes}
