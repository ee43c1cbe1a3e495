"""The reduction with the program's spans and scopes
(``chipbench.span_trace``): idle gaps charged to the benchmark's and the
program's spans, span and scope self times, op scopes; on the two small
traces recorded on one TPU v5e (q1.1 then q3.4 through ``QueryServer``
over 65,632 fact rows; the first before the program had spans and
scopes, the second with them, by ``record_trace.py``) it reads the
window, busy time and op sums ``chipbench.trace`` reads; and
``split.py`` on the CPU."""
from pathlib import Path

import jax
import pytest

from chipbench import span_trace as ST
from chipbench import spec, split
from chipbench import trace as TR

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "tpu_v5e_small.xplane.pb"
WITH_SPANS = DATA / "tpu_v5e_spans.xplane.pb"
CELLS = ("ssb_sf20.flight1", "ssb_sf10.joins")


def test_gaps_charge_to_the_innermost_of_benchmark_and_program_spans():
    device = {"/device:TPU:0": [("f", 30, 40), ("f", 70, 80)]}
    spans = [("chipbench.window", 0, 100), ("chipbench.run", 10, 95),
             ("sql.query", 12, 88), ("sql.upload", 15, 25),
             ("sql.pull", 40, 60), ("sql.upload", 62, 64)]
    r = ST.reduce(device, spans)
    assert dict(r.idle_gaps) == {"chipbench.run": pytest.approx(20e-9),
                                 "sql.upload": pytest.approx(30e-9),
                                 "sql.pull": pytest.approx(30e-9)}


def test_spans_that_start_together_charge_the_inner_one():
    spans = [("chipbench.window", 0, 100), ("chipbench.run", 10, 90),
             ("sql.query", 10, 80)]
    r = ST.reduce({"/device:TPU:0": [("f", 0, 10), ("f", 80, 100)]}, spans)
    assert dict(r.idle_gaps) == {"sql.query": pytest.approx(70e-9)}


def test_host_span_names_are_cut_at_their_metadata():
    events = [("sql.query#rid=7#", 0, 9), ("sql.wave#rids=3-4#", 10, 19),
              ("chipbench.run", 0, 20), ("PjitFunction(_spja_xla)", 1, 2),
              ("np.asarray(jax.Array)", 3, 4)]
    assert ST.host_spans(events) == [("sql.query", 0, 9),
                                     ("sql.wave", 10, 19),
                                     ("chipbench.run", 0, 20)]


def test_span_self_time_is_its_length_less_its_children():
    spans = [("chipbench.window", 0, 100), ("sql.query", 10, 90),
             ("sql.upload", 20, 40), ("sql.hashtable", 50, 70),
             ("sql.upload", 55, 60), ("sql.query", 92, 120)]
    r = ST.reduce({}, spans)
    assert r.spans["sql.query"] == (2, pytest.approx(88e-9),
                                    pytest.approx(48e-9))
    assert r.spans["sql.upload"] == (2, pytest.approx(25e-9),
                                     pytest.approx(25e-9))
    assert r.spans["sql.hashtable"].self_s == pytest.approx(15e-9)
    assert r.spans["chipbench.window"] == (1, pytest.approx(100e-9),
                                           pytest.approx(12e-9))


def test_scope_self_time_counts_a_nested_op_once():
    device = {"/device:TPU:0": [
        ("jit_f:while.0", 0, 100),
        ("jit_f:spja.probe:while.1", 10, 60),
        ("jit_f:spja.probe:fusion.2", 20, 30),
        ("jit_f:spja.filter:fusion.4", 35, 45),
        ("jit_f:spja.aggregate:fusion.3", 70, 90)]}
    r = ST.reduce(device, [("chipbench.window", 0, 100)])
    assert r.busy_s == pytest.approx(100e-9)
    assert r.scopes == {"spja.probe": pytest.approx(40e-9),
                        "spja.filter": pytest.approx(10e-9),
                        "spja.aggregate": pytest.approx(20e-9)}
    assert dict(r.ops)["jit_f:spja.probe:while.1"] == pytest.approx(50e-9)
    assert dict(r.ops)["jit_f:while.0"] == pytest.approx(100e-9)


def test_an_op_without_a_scope_takes_the_scope_of_nearly_all_nested():
    """A probe loop whose body holds a sliver of key decode takes the
    probe's scope; the fold over row blocks, which nests the probe and
    the aggregate, takes none."""
    modules = [("jit_f(7)", 0, 2000)]
    ops = [("%while.1 = fold", 0, 2000), ("%while.2 = probe", 10, 1010),
           ("%fusion.3 = gather", 12, 1000),
           ("%reshape.6 = key", 1000, 1005),
           ("%copy.4 = c", 1005, 1008), ("%fusion.5 = scatter", 1100, 1900)]
    scopes = {("7", "%fusion.3 = gather"): "spja.probe",
              ("7", "%reshape.6 = key"): "spja.decode",
              ("7", "%fusion.5 = scatter"): "spja.aggregate"}
    names = [n for n, _, _ in ST._op_names(ops, modules, scopes)]
    assert names == ["jit_f:while.1", "jit_f:spja.probe:while.2",
                     "jit_f:spja.probe:fusion.3",
                     "jit_f:spja.decode:reshape.6", "jit_f:copy.4",
                     "jit_f:spja.aggregate:fusion.5"]


def unscoped(name: str) -> str:
    parts = name.split(":")
    return f"{parts[0]}:{parts[-1]}"


@pytest.mark.parametrize("path", [RECORDED, WITH_SPANS],
                         ids=["before_spans", "with_spans"])
def test_recorded_trace_reads_as_the_benchmark_reads_it(path):
    """Window, busy time and the op sums (scopes taken out of the names)
    are those of ``chipbench.trace``; the idle gaps sum to the same
    idle time; the old fixture keeps its test's numbers."""
    plain, split_ = TR.reduce_file(path, top=1000), ST.reduce_file(
        path, top=1000)
    assert (split_.window_s, split_.busy_s, split_.devices) == (
        plain.window_s, plain.busy_s, plain.devices)
    assert len(plain.ops) > 50
    merged = {}
    for n, s in split_.ops:
        merged[unscoped(n)] = merged.get(unscoped(n), 0) + s
    assert merged == {n: pytest.approx(s, rel=1e-12) for n, s in plain.ops}
    assert sum(s for _, s in split_.idle_gaps) == pytest.approx(
        sum(s for _, s in plain.idle_gaps), rel=1e-9)
    if path == RECORDED:
        assert split_.window_s == pytest.approx(0.014201118)
        assert split_.busy_s == pytest.approx(0.008175235)
        assert split_.ops[0] == ("jit__spja_xla:fusion.3",
                                 pytest.approx(0.000976942))
        assert dict(split_.idle_gaps) == {
            n: pytest.approx(s) for n, s in plain.idle_gaps}
        assert split_.scopes == {}


def test_recorded_trace_with_program_spans_and_scopes():
    r = ST.reduce_file(WITH_SPANS, top=1000)
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.013705359)
    assert r.busy_s == pytest.approx(0.007247168)
    assert {"sql.query", "sql.plan", "sql.hashtable", "sql.upload",
            "sql.dispatch", "sql.pull"} <= set(r.spans)
    assert r.spans["sql.query"].count == 2          # q1.1, q3.4
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    assert {n for n, _ in r.idle_gaps} <= set(r.spans)
    # q3.4 alone joins: its probe loops take their scope from their body
    assert 0 < r.scopes["spja.probe"] / r.busy_s < 1
    assert r.ops[0][0].startswith("jit__spja_xla:spja.probe:while.")
    assert sum(r.scopes.values()) <= r.busy_s * (1 + 1e-9)


@pytest.mark.parametrize("name", CELLS)
def test_split_reads_the_programs_spans_and_uploads(tiny_cfg, name):
    """On the CPU a trace holds the host spans but no device ops: the
    window is charged to the program's spans, each query's uploads are
    counted (its parameters alone), no scope is found, and the harness is
    left as it was."""
    from chipbench import run
    faults, trace = run.faults, run.TR
    cell = spec.cell(name, spec.benchmark())
    out, found = split.split(cell, tiny_cfg, spec.traffic(cell.traffic),
                             seed=2**33 + 11, seconds=0.2,
                             devices=jax.devices()[:1],
                             peaks={"hbm_bytes_per_s": 819e9}, t0=0.0)
    assert (run.faults, run.TR) == (faults, trace)
    assert out["correct"], out["checks"]
    assert found["queries"] == out["attempted"] >= 1
    # every column is served from its resident buffer: a query copies
    # only its parameters, under 1 KB
    assert 0 <= found["upload_bytes_per_query"] < 1024
    per = found["per_query"]
    assert per["sql.query"]["count"] == 1
    assert per["sql.upload"]["total_s"] > 0
    assert 0 < per["sql.query"]["self_s"] < per["sql.query"]["total_s"]
    assert found["scopes"] == {}
    assert any(n.startswith("sql.") for n, _ in out["breakdown"]["idle_gaps"])
