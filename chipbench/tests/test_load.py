"""``data.load``, the set-up of every run: by default the tables packed
as ``storage.pack_database`` packs them, all resident, the fact table
one morsel; ``layout: "plain"`` every column one int32 word a row on the
drawn buffer; ``fact_morsel_bytes`` the fact table held on the host and
streamed in morsels.  Each on the tiny configuration, through the
queries of both mixes, answered as the reference answers them."""
import numpy as np
import pytest

from chipbench import data, reference, run, spec

MIXES = ("flight1", "joins")
SEED = 2**33 + 21


def serve(placed, mix):
    """Each distinct query of ``mix`` once through the timed path; their
    results by name."""
    from repro.sql import engine
    from repro.sql.server import QueryServer
    server = QueryServer(**placed)
    plans, strategy = engine.ssb_queries(), spec.traffic(mix)["strategy"]
    out = {}
    for name in dict.fromkeys(spec.traffic(mix)["queries"]):
        server.submit(plans[name], strategy=strategy)
        (r,) = server.run().values()
        assert run.faults(r, strategy) == [], (name, r)
        out[name] = r
    return out


def gaps(tables, results):
    want = reference.answers(tables, list(results))
    return {n: reference.gap(r.result, want[n]) for n, r in results.items()}


def columns(db):
    for name in ("lineorder",) + data.DIMENSIONS:
        for c, col in getattr(db, name).columns.items():
            yield name, c, col


@pytest.fixture(scope="module")
def loaded(tiny_cfg):
    """The tiny configuration loaded under each placement, once."""
    cache = {}

    def get(**keys):
        k = tuple(sorted(keys.items()))
        if k not in cache:
            cache[k] = data.load({**tiny_cfg, **keys}, SEED)
        return cache[k]
    return get


@pytest.mark.parametrize("mix", MIXES)
def test_default_packs_as_pack_database_resident_in_one_morsel(loaded,
                                                               mix):
    from repro.sql import ssb, storage
    tables, placed = loaded()
    db = placed["db"]
    want = storage.pack_database(ssb.Database(
        **{n: ssb.Table(n, dict(cols)) for n, cols in tables.items()},
        sf=db.sf))
    for name, c, col in columns(db):
        other = getattr(want, name).columns[c]
        assert col.encoding == other.encoding, (name, c)
        assert np.array_equal(col.words, other.words), (name, c)
        assert col.resident, (name, c)
    assert placed["mode"] == "auto"
    results = serve(placed, mix)
    assert {r.n_morsels for r in results.values()} == {1}
    assert set(gaps(tables, results).values()) == {0.0}


@pytest.mark.parametrize("mix", MIXES)
def test_plain_layout_holds_the_drawn_words(loaded, mix):
    from repro.sql.storage import ColumnEncoding
    tables, placed = loaded(layout="plain")
    db = placed["db"]
    for name, c, col in columns(db):
        v = tables[name][c]
        assert col.encoding == ColumnEncoding("plain", 32, 32, 0, len(v))
        assert np.shares_memory(col.words, v), (name, c)
        assert col.resident, (name, c)
    assert db.lineorder.nbytes == 4 * len(tables["lineorder"]) * len(
        tables["lineorder"]["lo_orderkey"])
    results = serve(placed, mix)
    assert {r.n_morsels for r in results.values()} == {1}
    assert set(gaps(tables, results).values()) == {0.0}


@pytest.mark.parametrize("mix", MIXES)
def test_fact_morsel_bytes_streams_the_fact_table_from_the_host(
        loaded, tiny_cfg, mix):
    tables, placed = loaded(fact_morsel_bytes=1 << 18)
    db = placed["db"]
    assert placed["morsel_bytes"] == 1 << 18
    results = serve(placed, mix)
    assert min(r.n_morsels for r in results.values()) > 1
    for name, c, col in columns(db):
        assert col.resident is (name != "lineorder"), (name, c)
    limit = tiny_cfg["limits"]["max_f32_steps"]
    assert max(gaps(tables, results).values()) <= limit
