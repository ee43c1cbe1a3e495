"""Host spans and the upload counter of the query path: the ``sql.*``
spans nest inside their request or wave and carry its ids, and
``QueryResult.upload_bytes`` counts what each execution copies to the
device, as ``LAUNCH_STATS`` does."""
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.sql import engine, ssb
from repro.sql import spans as SP
from repro.sql import storage as ST
from repro.sql.server import QueryServer

QUERIES = engine.ssb_queries()
PLAIN = "lo_extendedprice"


@pytest.fixture(scope="module")
def db():
    """A tiny packed database whose ``lo_extendedprice`` needs more than
    16 bits, so it stays plain as at the SSB's widths, with every packed
    column resident on the device."""
    base = ssb.generate(sf=0.005, seed=11)
    base.lineorder.columns[PLAIN] = base.lineorder.columns[PLAIN] * 1000
    packed = ST.pack_database(base)
    assert packed.lineorder.encoding(PLAIN).kind == "plain"
    for name in ("lineorder", "date", "supplier", "customer", "part"):
        for col in getattr(packed, name).columns.values():
            if col.encoding.kind != "plain":
                col.words_jax()
    return packed


def fact_cols(plan):
    proj = plan.project
    cols = [c for c, _, _ in plan.preds] + [j.fact_col for j in plan.joins]
    return cols + ([proj.m1] if proj.op not in ("mul", "sub")
                   else [proj.m1, proj.m2])


def solo_upload_bytes(plan, db, plain_resident: bool) -> int:
    """The fused step's parameter arrays: (lo, hi) per predicate, a
    frame of reference and a multiplier per join, a frame of reference
    per measure stream; and, unless they are already on the device,
    the plain fact columns at 4 bytes a row."""
    fact = db.lineorder
    plain = 0 if plain_resident else sum(
        4 * fact.n_rows for c in fact_cols(plan)
        if fact.encoding(c).kind == "plain")
    n_meas = 2 if plan.project.op in ("mul", "sub") else 1
    return plain + 4 * (2 * len(plan.preds) + 2 * len(plan.joins) + n_meas)


def sql_spans(trace_dir):
    """``(name, start, end, stats)`` of every ``sql.`` host span."""
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("sql.")]


def test_spans_nest_inside_their_request_and_carry_its_ids(db, tmp_path):
    server = QueryServer(db, mode="ref")
    server.submit(QUERIES["q2.1"], strategy="fused")     # compile first
    server.run()
    jax.profiler.start_trace(str(tmp_path))
    solo = server.submit(QUERIES["q2.1"], strategy="fused")
    server.run()
    wave = [server.submit(QUERIES[n], strategy="shared")
            for n in ("q1.1", "q1.2")]
    out = server.run()
    jax.profiler.stop_trace()
    assert out[wave[0]].shared_wave_size == 2

    spans = sql_spans(tmp_path)
    roots = [s for s in spans if s[0] in (SP.QUERY, SP.WAVE)]
    assert [(n, st) for n, _, _, st in roots] == [
        (SP.QUERY, {"rid": solo}),
        (SP.WAVE, {"rids": f"{wave[0]}-{wave[1]}"})]
    children = {}
    for name, a, b, _ in spans:
        if name in (SP.QUERY, SP.WAVE):
            continue
        (root,) = [r[0] for r in roots if r[1] <= a and b <= r[2]]
        children.setdefault(root, set()).add(name)
    assert children[SP.QUERY] == {SP.PLAN, SP.HASHTABLE, SP.UPLOAD,
                                  SP.DISPATCH, SP.PULL}
    assert children[SP.WAVE] == {SP.UPLOAD, SP.DISPATCH, SP.PULL}


@pytest.mark.parametrize("name", ["q1.1", "q2.1"])
def test_upload_bytes_count_each_repeat_of_a_query(db, name):
    """q1.1's first run uploads its plain price column, which then stays
    on the device; q2.1 reads packed columns only, resident since
    set-up, and its hash tables are cached after the first run.  Each
    repeat of either uploads its parameters alone."""
    db.lineorder.columns[PLAIN].release(device=True)
    server = QueryServer(db, mode="ref")
    plan = QUERIES[name]
    server.submit(plan, strategy="fused")
    first = server.run()                            # builds hash tables
    if not plan.joins:
        (r,) = first.values()
        assert r.upload_bytes == solo_upload_bytes(plan, db, False)
    for _ in range(3):
        before = SP.LAUNCH_STATS["upload_bytes"]
        rid = server.submit(plan, strategy="fused")
        r = server.run()[rid]
        assert r.strategy == "fused" and r.error is None
        assert r.upload_bytes == solo_upload_bytes(plan, db, True)
        assert SP.LAUNCH_STATS["upload_bytes"] - before == r.upload_bytes


def test_shared_members_report_the_whole_waves_uploads(db):
    """The first wave moves the plain price column once for both
    members; it then stays on the device, and the second wave moves
    its parameters alone."""
    db.lineorder.columns[PLAIN].release(device=True)
    server = QueryServer(db, mode="ref")
    n = db.lineorder.n_rows
    for wave, (lo, hi) in enumerate([(4 * n, 8 * n), (0, 4 * n)]):
        before = SP.LAUNCH_STATS["upload_bytes"]
        rids = [server.submit(QUERIES[q], strategy="shared")
                for q in ("q1.1", "q1.2")]
        out = server.run()
        moved = SP.LAUNCH_STATS["upload_bytes"] - before
        assert [out[r].upload_bytes for r in rids] == [moved, moved]
        assert lo <= moved < hi, (wave, moved)


def test_upload_passes_device_arrays_through_uncounted():
    host = np.arange(1000, dtype=np.int32)
    before = dict(SP.LAUNCH_STATS)
    dev = ST.upload(host)
    assert SP.LAUNCH_STATS["uploads"] == before["uploads"] + 1
    assert SP.LAUNCH_STATS["upload_bytes"] == (before["upload_bytes"]
                                               + host.nbytes)
    assert ST.upload(dev) is dev
    assert SP.LAUNCH_STATS["uploads"] == before["uploads"] + 1
