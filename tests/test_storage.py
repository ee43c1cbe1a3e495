"""Compressed column store (repro.sql.storage) + decode-on-scan kernels.

* encode/decode roundtrip: parametrized widths 1-32 + hypothesis sweep
  (random widths, frames of reference incl. negative, value patterns)
* encoding choice from column stats: bitpack / for / plain
* the decode primitives agree: numpy oracle == ops.unpack (ref + kernel)
  == gather_decode
* packed-aware kernels (select_scan_packed, spja, multi_spja) against
  their plain counterparts, ref AND interpret-kernel modes
* packed-vs-plain BIT-identical equivalence for every strategy
  (fused/opat/part/part_loop/shared) on the 13 SSB queries
* encoded-domain predicate rewrite, encoded-bytes cost model, packed
  database through the QueryServer (bytes_scanned reporting, fingerprint
  compatibility with the plain original)
* plain columns of a packed table served from their resident device
  buffer: counted, dropped with a morsel cut, never stale after ingest
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops
from repro.sql import compile as C
from repro.sql import engine, ssb
from repro.sql import model as M
from repro.sql import morsel as MS
from repro.sql import spans as SP
from repro.sql import storage as ST
from repro.sql.compile import compile_plan
from repro.sql.plan import ColExpr, QueryBuilder
from repro.sql.server import QueryServer

DB = ssb.generate(sf=0.005, seed=3)
PDB = ST.pack_database(DB)
QUERIES = engine.ssb_queries()


# ---------------------------------------------------------------------------
# encode / decode roundtrip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", list(range(1, 33)))
def test_roundtrip_all_widths(width):
    rng = np.random.default_rng(width)
    hi = (1 << width) - 1 if width < 32 else (1 << 31) - 1
    vals = rng.integers(0, hi + 1 if width < 32 else hi, 257,
                        dtype=np.int64).astype(np.int32)
    words = ST.pack_words(vals, width)
    np.testing.assert_array_equal(
        ST.unpack_words(words, len(vals), width), vals)


@pytest.mark.parametrize("ref", [-5000, -1, 0, 7, 1 << 20])
def test_roundtrip_frame_of_reference(ref):
    rng = np.random.default_rng(abs(ref) + 1)
    vals = (rng.integers(0, 1000, 100, dtype=np.int64)
            + ref).astype(np.int32)
    words = ST.pack_words(vals, 10, ref)
    np.testing.assert_array_equal(
        ST.unpack_words(words, len(vals), 10, ref), vals)


def test_pack_rejects_out_of_range():
    with pytest.raises(ValueError, match="out of range"):
        ST.pack_words(np.array([16], np.int32), width=4)
    with pytest.raises(ValueError, match="out of range"):
        ST.pack_words(np.array([-1], np.int32), width=4, ref=0)


def test_empty_column():
    col = ST.pack_column(np.zeros(0, np.int32))
    assert col.encoding.kind == "plain" and len(col) == 0
    assert col.decode().shape == (0,)


def test_hypothesis_roundtrip_sweep():
    hyp = pytest.importorskip(
        "hypothesis", reason="hypothesis not installed "
        "(see requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(st.integers(1, 32), st.integers(-(1 << 30), 1 << 30),
           st.integers(0, 300), st.integers(0, 2 ** 32 - 1))
    def roundtrip(width, ref, n, seed):
        rng = np.random.default_rng(seed)
        span = min((1 << width) - 1, (1 << 31) - 1 - max(ref, 0))
        if ref < 0:
            span = min(span, (1 << 31) - 1 + ref + 1)
        hyp.assume(span >= 0)
        enc = rng.integers(0, span + 1, n, dtype=np.int64)
        vals = (enc + ref).astype(np.int32)
        words = ST.pack_words(vals, width, ref)
        np.testing.assert_array_equal(
            ST.unpack_words(words, n, width, ref), vals)
        # the device decodes agree with the numpy oracle
        col = ST.pack_column(vals)
        e = col.encoding
        got = np.asarray(ops.unpack(col.words_jax(), len(vals), e.phys,
                                    e.ref, mode="ref"))
        np.testing.assert_array_equal(got, vals)

    roundtrip()


# ---------------------------------------------------------------------------
# encoding choice
# ---------------------------------------------------------------------------


def test_choose_encoding_kinds():
    bp = ST.choose_encoding(np.array([0, 3, 10], np.int32))
    assert (bp.kind, bp.width, bp.phys, bp.ref) == ("bitpack", 4, 4, 0)
    fo = ST.choose_encoding(np.array([100000, 100010], np.int32))
    assert fo.kind == "for" and fo.ref == 100000 and fo.phys == 4
    neg = ST.choose_encoding(np.array([-5, 5], np.int32))
    assert neg.kind == "for" and neg.ref == -5
    pl = ST.choose_encoding(
        np.array([-(1 << 30), 1 << 30], np.int32))
    assert pl.kind == "plain" and pl.bytes_per_row == 4.0
    # same phys either way -> prefer the ref-free bitpack
    both = ST.choose_encoding(np.array([1, 50], np.int32))
    assert both.kind == "bitpack" and both.ref == 0


def test_encoded_nbytes():
    enc = ST.choose_encoding(np.arange(1000, dtype=np.int32))  # 10 -> 16 bit
    assert enc.phys == 16
    assert enc.nbytes == 4 * 500
    assert ST.pack_column(np.arange(1000, dtype=np.int32)).words.nbytes \
        == enc.nbytes


def test_ssb_fact_compression_ratio():
    """The acceptance floor: >=1.5x bytes-moved reduction on the fact
    table (the SSB domains land ~2.5x at lane-aligned widths)."""
    lo = PDB.lineorder
    assert lo.plain_nbytes / lo.nbytes >= 1.5
    for c in lo.columns:
        assert lo.encoding(c).kind != "plain"


# ---------------------------------------------------------------------------
# decode primitives: unpack kernel, gather_decode, take
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["ref", "kernel"])
def test_unpack_modes_match_numpy(mode):
    rng = np.random.default_rng(0)
    vals = (rng.integers(0, 3000, 777, dtype=np.int64)
            - 1500).astype(np.int32)
    col = ST.pack_column(vals)
    e = col.encoding
    assert e.kind == "for" and e.ref == int(vals.min())
    got = np.asarray(ops.unpack(col.words_jax(), len(vals), e.phys, e.ref,
                                mode=mode, tile=256))
    np.testing.assert_array_equal(got, vals)


def test_take_gather_decode():
    rng = np.random.default_rng(1)
    vals = rng.integers(0, 50, 500).astype(np.int32)
    table = ST.pack_table(ssb.Table("t", {"x": vals}))
    idx = jnp.asarray(rng.integers(0, 500, 200).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(ST.take(table, "x", idx)),
                                  vals[np.asarray(idx)])
    # plain passthrough path
    plain = ssb.Table("t", {"x": vals})
    np.testing.assert_array_equal(np.asarray(ST.take(plain, "x", idx)),
                                  vals[np.asarray(idx)])


@pytest.mark.parametrize("mode", ["ref", "kernel"])
def test_select_scan_packed_modes(mode):
    rng = np.random.default_rng(2)
    x = (rng.integers(0, 200, 3000, dtype=np.int64) + 7000).astype(np.int32)
    col = ST.pack_column(x)
    e = col.encoding
    assert e.kind == "for"
    lo, hi = 7050, 7100
    lo2, hi2 = ST.encoded_bounds(e, lo, hi)
    y = jnp.arange(len(x), dtype=jnp.int32)
    out, cnt = ops.select_scan_packed(col.words_jax(), y, lo2, hi2,
                                      e.phys, mode=mode, tile=256)
    got = np.asarray(out)[:int(cnt)]
    np.testing.assert_array_equal(got,
                                  np.flatnonzero((x >= lo) & (x <= hi)))


def test_encoded_bounds_rewrite():
    enc = ST.ColumnEncoding("for", 8, 8, 100, 10)
    assert ST.encoded_bounds(enc, 110, 150) == (10, 50)
    # all-pass int32 bounds clamp instead of wrapping, and stay all-pass
    # in the encoded domain (encoded values are in [0, 2^width))
    lo, hi = ST.encoded_bounds(enc, -(1 << 31), (1 << 31) - 1)
    assert lo == -(1 << 31) and hi >= (1 << enc.width) - 1
    assert ST.encoded_bounds(None, 3, 5) == (3, 5)


# ---------------------------------------------------------------------------
# strategy equivalence: packed bit-identical to plain, all lowerings
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(QUERIES))
@pytest.mark.parametrize("strategy", ["fused", "opat", "part", "part_loop"])
def test_packed_bit_identical_all_strategies(name, strategy):
    plan = QUERIES[name]
    plain = compile_plan(plan, strategy).execute(DB, mode="ref")
    packed = compile_plan(plan, strategy).execute(PDB, mode="ref")
    assert np.array_equal(plain, packed), (name, strategy)
    np.testing.assert_allclose(
        packed, engine.run_query_oracle(PDB, plan), rtol=1e-5, atol=1e-3)


def test_packed_shared_wave_bit_identical():
    plans = list(QUERIES.values())
    plain = C.execute_shared(plans, DB, mode="ref", pad_to=16)
    packed = C.execute_shared(plans, PDB, mode="ref", pad_to=16)
    for plan, a, b in zip(plans, plain, packed):
        assert np.array_equal(a, b), plan.name


@pytest.mark.parametrize("name", ["q1.1", "q2.1", "q4.2"])
def test_packed_kernel_paths(name):
    """The Pallas decode-on-scan kernels (interpret on CPU) match the
    jitted jnp path on the packed database."""
    plan = QUERIES[name]
    ref = compile_plan(plan, "fused").execute(PDB, mode="ref")
    ker = compile_plan(plan, "fused").execute(PDB, mode="kernel", tile=512)
    np.testing.assert_allclose(ker, ref, rtol=1e-5, atol=1e-3)


def test_packed_shared_kernel_path():
    plans = [QUERIES[n] for n in ("q1.1", "q2.1", "q4.2")]
    ref = C.execute_shared(plans, PDB, mode="ref", pad_to=4)
    ker = C.execute_shared(plans, PDB, mode="kernel", tile=512, pad_to=4)
    for plan, a, b in zip(plans, ref, ker):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-3,
                                   err_msg=plan.name)


def test_for_encoded_fk_join():
    """A frame-of-reference FK column (offset key domain) still probes
    correctly: decode adds the reference before the hash lookup."""
    rng = np.random.default_rng(5)
    base = 1 << 20
    n_dim, n_fact = 64, 4096

    class _Shim:
        pass

    def mkdb(pack):
        db = _Shim()
        dim = ssb.Table("dim", {
            "d_key": (np.arange(n_dim, dtype=np.int64)
                      + base).astype(np.int32),
            "d_pay": np.arange(n_dim, dtype=np.int32)})
        lo = ssb.Table("lineorder", {
            "lo_fk": (rng.integers(0, n_dim, n_fact, dtype=np.int64)
                      + base).astype(np.int32),
            "lo_rev": rng.integers(1, 100, n_fact, dtype=np.int32)})
        db.lineorder = ST.pack_table(lo) if pack else lo
        db.dim = dim
        return db

    rng = np.random.default_rng(5)
    db_plain = mkdb(False)
    rng = np.random.default_rng(5)
    db_packed = mkdb(True)
    assert db_packed.lineorder.encoding("lo_fk").kind == "for"
    plan = (QueryBuilder("forfk").scan("lineorder")
            .hash_join("lo_fk", "dim", "d_key",
                       payload=ColExpr("d_pay"), mult=1)
            .measure("lo_rev").group_by(n_dim).build())
    expect = engine.run_query_oracle(db_plain, plan)
    for strategy in ("fused", "opat", "part"):
        got = compile_plan(plan, strategy).execute(db_packed, mode="ref")
        np.testing.assert_allclose(got, expect, err_msg=strategy)


# ---------------------------------------------------------------------------
# cost model: encoded bytes
# ---------------------------------------------------------------------------


def test_model_prices_encoded_bytes():
    plan = QUERIES["q1.1"]
    enc, plain = M.scanned_bytes(plan, PDB.lineorder)
    enc2, plain2 = M.scanned_bytes(plan, DB.lineorder)
    assert plain == plain2 == enc2            # plain table: nominal W
    assert enc < plain and plain / enc >= 1.5
    # predictions follow: every strategy's scan term shrinks
    p_packed = M.predict(plan, PDB)
    p_plain = M.predict(plan, DB)
    for s in p_packed:
        assert p_packed[s] < p_plain[s], s


def test_predict_shared_encoded_bytes():
    plans = [QUERIES[n] for n in ("q2.1", "q2.2", "q2.3")]
    shared_packed = M.predict_shared(plans, PDB)["shared"]
    shared_plain = M.predict_shared(plans, DB)["shared"]
    assert shared_packed < shared_plain


# ---------------------------------------------------------------------------
# server: packed database served transparently
# ---------------------------------------------------------------------------


def test_server_packed_transparent_and_reports_bytes():
    server = QueryServer(PDB, mode="ref", max_batch=16)
    rids = {n: server.submit(QUERIES[n], strategy="shared")
            for n in ("q1.1", "q2.1", "q4.2")}
    solo = server.submit(QUERIES["q1.1"], strategy="fused")
    results = server.run()
    for n, rid in rids.items():
        r = results[rid]
        assert r.error is None
        np.testing.assert_allclose(
            r.result, engine.run_query_oracle(DB, QUERIES[n]),
            rtol=1e-5, atol=1e-3)
        assert r.bytes_scanned < r.bytes_scanned_plain
    rs = results[solo]
    assert rs.error is None
    assert rs.bytes_scanned_plain / rs.bytes_scanned >= 1.5


def test_packed_fingerprint_matches_plain():
    """A packed database decodes to the same logical data, so a cache
    warmed on the plain original rebinds to it instead of raising."""
    from repro.sql.hashtable import HashTableCache, db_fingerprint
    assert db_fingerprint(PDB, ("supplier",)) == \
        db_fingerprint(DB, ("supplier",))
    cache = HashTableCache()
    plan = QUERIES["q2.1"]
    compile_plan(plan, "fused").execute(DB, mode="ref", cache=cache)
    misses = cache.misses
    out = compile_plan(plan, "fused").execute(PDB, mode="ref", cache=cache)
    assert cache.misses == misses             # warm entries served
    assert np.array_equal(
        out, compile_plan(plan, "fused").execute(DB, mode="ref"))


def test_first_op_with_m2_column_ignored():
    """An m2 on an op="first" projection is ignored, never loaded — the
    measure stream count follows the op, matching the kernels (packed
    and plain, ref and interpret-kernel modes; regression: the packed
    lowering used to size widths off m2's presence and misalign the
    kernel's measure refs)."""
    plan = (QueryBuilder("first_m2").scan("lineorder")
            .where_range("lo_discount", 1, 3)
            .measure("lo_revenue", "lo_discount")     # op defaults "first"
            .group_by(1).build())
    baseline = (QueryBuilder("first_only").scan("lineorder")
                .where_range("lo_discount", 1, 3)
                .measure("lo_revenue").group_by(1).build())
    expect = engine.run_query_oracle(DB, baseline)
    for db in (DB, PDB):
        for mode, tile in (("ref", 2048), ("kernel", 512)):
            got = compile_plan(plan, "fused").execute(db, mode=mode,
                                                      tile=tile)
            np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-3,
                                       err_msg=f"{mode}")


def test_ops_spja_dispatch_guards():
    """Dispatch-surface robustness: an m2 with op="first" is accepted
    and ignored (as before the packed extension), and a packed measure
    without an explicit row count raises instead of silently scanning a
    fraction of the rows (the word count is not the row count)."""
    import jax.numpy as jnp2
    n = 100
    m1 = jnp2.arange(n, dtype=jnp2.float32)
    m2 = jnp2.ones((n,), jnp2.float32)
    out = ops.spja([], np.zeros((0, 2), np.int32), [], [],
                   jnp2.zeros((0,), jnp2.int32), m1, m2,
                   measure_op="first", mode="ref")
    np.testing.assert_allclose(np.asarray(out), [n * (n - 1) / 2])
    col = ST.pack_column(np.arange(n, dtype=np.int32))
    with pytest.raises(ValueError, match="n_rows"):
        ops.spja([], np.zeros((0, 2), np.int32), [], [],
                 jnp2.zeros((0,), jnp2.int32), col.words_jax(), None,
                 measure_op="first", mode="ref",
                 m_widths=(col.encoding.phys,))


# ---------------------------------------------------------------------------
# plain columns served from their resident device buffer
# ---------------------------------------------------------------------------

# the measures at a width past 16 bits, so they stay plain as at the
# SSB's own widths (``lo_extendedprice`` is 24 bits there)
PLAIN_MEASURES = ("lo_extendedprice", "lo_revenue", "lo_supplycost")


def _wide_measures_db() -> ssb.Database:
    base = ssb.generate(sf=0.005, seed=3)
    for c in PLAIN_MEASURES:
        base.lineorder.columns[c] = base.lineorder.columns[c] * 1000
    return base


WIDE = _wide_measures_db()


def _packed_wide() -> ssb.Database:
    pdb = ST.pack_database(WIDE)
    assert all(pdb.lineorder.encoding(c).kind == "plain"
               for c in PLAIN_MEASURES)
    return pdb


def _stats_delta(before):
    return {k: SP.LAUNCH_STATS[k] - before[k]
            for k in ("streams", "streams_resident", "uploads",
                      "upload_bytes")}


def test_plain_column_of_packed_table_streams_from_resident_buffer():
    fact = _packed_wide().lineorder
    col = "lo_extendedprice"
    before = dict(SP.LAUNCH_STATS)
    first, phys, ref = ST.column_stream(fact, col)
    assert (phys, ref) == (32, 0)
    np.testing.assert_array_equal(np.asarray(first), fact[col])
    assert _stats_delta(before) == {"streams": 1, "streams_resident": 0,
                                    "uploads": 1,
                                    "upload_bytes": 4 * fact.n_rows}
    before = dict(SP.LAUNCH_STATS)
    again, _, _ = ST.column_stream(fact, col)
    assert again is first
    assert _stats_delta(before) == {"streams": 1, "streams_resident": 1,
                                    "uploads": 0, "upload_bytes": 0}


def test_plain_table_uploads_on_every_stream():
    fact = WIDE.lineorder
    col = "lo_extendedprice"
    arrays = []
    for _ in range(2):
        before = dict(SP.LAUNCH_STATS)
        arr, phys, ref = ST.column_stream(fact, col)
        arrays.append(arr)
        assert (phys, ref) == (32, 0)
        assert _stats_delta(before) == {"streams": 1, "streams_resident": 0,
                                        "uploads": 1,
                                        "upload_bytes": 4 * fact.n_rows}
    assert arrays[0] is not arrays[1]


def test_morsel_release_drops_a_cuts_plain_buffer_and_keeps_the_bases():
    fact = _packed_wide().lineorder
    col = "lo_extendedprice"
    stream = MS.MorselStream(fact, morsel_bytes=fact.nbytes // 4)
    cut = list(stream.morsels())[1]
    assert cut.table is not fact
    stream._prefetch(cut)
    assert cut.table.columns[col].resident
    before = dict(SP.LAUNCH_STATS)
    ST.column_stream(cut.table, col)
    assert _stats_delta(before)["streams_resident"] == 1
    stream._release(cut, keep=None)
    assert not cut.table.columns[col].resident

    whole = MS.MorselStream(fact)               # one morsel: the table
    (m,) = whole.morsels()
    assert m.table is fact
    whole._prefetch(m)
    whole._release(m, keep=None)
    assert fact.columns[col].resident


def test_ingested_rows_are_read_not_a_stale_buffer():
    pdb = _packed_wide()
    plan = QUERIES["q1.1"]                      # reads the plain price
    cq = compile_plan(plan, "fused")
    before = np.asarray(cq.execute(pdb, mode="ref"))
    assert pdb.lineorder.columns["lo_extendedprice"].resident
    rng = np.random.default_rng(7)
    idx = rng.integers(0, WIDE.lineorder.n_rows, 512)
    rows = {c: np.asarray(WIDE.lineorder[c])[idx]
            for c in WIDE.lineorder.columns}
    ST.append_rows(pdb.lineorder, rows)
    merged = dataclasses.replace(WIDE, lineorder=ssb.Table(
        "lineorder", {c: np.concatenate([np.asarray(WIDE.lineorder[c]),
                                         rows[c]])
                      for c in WIDE.lineorder.columns}))
    want = engine.run_query_oracle(merged, plan)
    appended = np.asarray(cq.execute(pdb, mode="ref"))
    assert not np.array_equal(appended, before)
    np.testing.assert_allclose(appended, want, rtol=1e-5, atol=1e-3)
    flushed = dataclasses.replace(
        pdb, lineorder=ST.flush_deltas(pdb.lineorder))
    assert not flushed.lineorder.columns["lo_extendedprice"].resident
    got = np.asarray(cq.execute(flushed, mode="ref"))
    np.testing.assert_array_equal(got, appended)


@pytest.mark.parametrize("strategy", ["fused", "shared"])
def test_repeat_runs_on_resident_plain_columns_match_oracle(strategy):
    """Every SSB query twice on one packed database: the second pass
    reads every column from its resident buffer, and both passes equal
    the plain database's answers (which upload anew) and the oracle."""
    pdb = _packed_wide()
    plans = list(QUERIES.values())

    def run(db):
        if strategy == "shared":
            return C.execute_shared(plans, db, mode="ref", pad_to=16)
        return [compile_plan(p, "fused").execute(db, mode="ref")
                for p in plans]

    plain = run(WIDE)
    for rep in range(2):
        before = dict(SP.LAUNCH_STATS)
        got = run(pdb)
        moved = _stats_delta(before)
        if rep:
            assert moved["streams_resident"] == moved["streams"] > 0
        for plan, a, b in zip(plans, got, plain):
            assert np.array_equal(a, b), (plan.name, rep)
            np.testing.assert_allclose(
                a, engine.run_query_oracle(WIDE, plan), rtol=1e-5,
                atol=1e-3, err_msg=f"{plan.name} rep {rep}")
