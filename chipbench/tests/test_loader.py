"""Every cell resolves from its own files by name, with no registry."""
import json

import pytest

from chipbench import data, loadgen, reference, spec

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_every_name_is_plain():
    names = [w[k] for w in BENCH["workloads"]
             for k in ("name", "config", "traffic")]
    names += [c["name"] for c in BENCH["configs"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert spec.check_name(n) == n
    with pytest.raises(ValueError):
        spec.check_name("a/b")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_files(name):
    cell = spec.cell(name, BENCH)
    cfg = spec.config(cell.config)
    assert cfg["name"] == cell.config
    mix = loadgen.validate(spec.traffic(cell.traffic))
    assert set(mix["queries"]) <= set(reference.QUERIES)
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert spec.metric_path(m["name"]).is_file()
        assert callable(spec.reader(m["name"]))


def test_configs_match_their_entries():
    for c in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["limits"] == {"max_f32_steps": 1, "failed_queries": 0}


def test_moves_names_an_end_to_end_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", ()):
            assert m["moves"] in {x["name"] for x in
                                  spec.cell(w, BENCH).end_to_end}


def test_round_robin_sends_the_same_sequence_for_every_seed():
    mix = spec.traffic("joins")
    seq = loadgen.sequence(mix)
    first = [next(seq) for _ in range(25)]
    assert first[:10] == mix["queries"] and first[10:20] == mix["queries"]


def test_split_metric_reads_with_its_base_reader():
    assert spec.metric_path("device_idle_share.scan").name == \
        "device_idle_share.py"
    assert spec.metric_path("device_idle_share").name == \
        "device_idle_share.py"


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_config_key_is_one_the_loader_knows(entry):
    cfg = json.loads((spec.ROOT / entry["file"]).read_text())
    assert set(cfg) <= data.KEYS
    assert data.placement(cfg) == (cfg.get("layout", "packed"),
                                   cfg.get("fact_morsel_bytes"))


@pytest.mark.parametrize("key, value", [
    ("layout", "int32"), ("layout", "Plain"), ("layout", None),
    ("fact_morsel_bytes", 0), ("fact_morsel_bytes", -64),
    ("fact_morsel_bytes", 1.5), ("fact_morsel_bytes", "67108864"),
    ("fact_morsel_bytes", True), ("fact_morsel_bytes", None),
    ("fact_morsel_byte", 1 << 26)])
def test_a_bad_placement_raises_naming_its_key(tiny_cfg, key, value):
    with pytest.raises(ValueError, match=key):
        data.load({**tiny_cfg, key: value}, seed=1)
