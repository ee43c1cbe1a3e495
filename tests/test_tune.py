"""Autotuner invariance + tune-store persistence.

Two halves:

* Property tests that the knobs the tuner sweeps are answer-preserving
  for EVERY kernel family (select_scan, unpack, spja, multi_spja,
  part_probe, radix_sort, partition_multi), packed and plain, across
  legal tile sizes and radix widths — so the tuner can only ever change
  speed, never results.
* TuneStore mechanics: fingerprinted cache filename, save/load
  round-trip, torn-file recovery, width-bucket fallback, the tie-keeps-
  default pick rule, cold-store fallback to DEFAULT_TILE (byte-for-byte
  vs an explicit default-tile run), tuned-store pickup in compile, and
  the part-budget feedback into the cost model.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.common import DEFAULT_TILE
from repro.sql import calibrate, engine, ssb
from repro.sql import model as M
from repro.sql import storage as ST
from repro.sql import tune as TN
from repro.sql.compile import compile_plan
from repro.sql.hashtable import build_dim_partitions, next_pow2, np_build

KEY = jax.random.PRNGKey(11)
TILES = (32, 128, 512)          # legal: any pow2 >= 32 (word alignment)
N = 2048


def randi(shape, lo, hi, k=0):
    return jax.random.randint(jax.random.fold_in(KEY, k), shape, lo, hi,
                              jnp.int32)


# ---------------------------------------------------------------------------
# invariance: every swept knob is answer-preserving, per family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tile", TILES)
def test_select_scan_tile_invariant(tile):
    x = randi((N,), 0, 1000, 1)
    y = jnp.arange(N, dtype=jnp.int32)
    out_k, cnt_k = ops.select_scan(x, y, 100, 900, mode="kernel",
                                   tile=tile)
    out_r, cnt_r = ref.select_scan(x, y, 100, 900)
    assert int(cnt_k) == int(cnt_r)
    np.testing.assert_array_equal(np.asarray(out_k)[:int(cnt_k)],
                                  np.asarray(out_r)[:int(cnt_r)])


@pytest.mark.parametrize("tile", TILES)
def test_select_scan_packed_tile_invariant(tile):
    """The packed-width bucket: same scan off the 16-bit word stream."""
    vals = np.asarray(randi((N,), 0, 1000, 2))
    y = jnp.arange(N, dtype=jnp.int32)
    words = jnp.asarray(ST.pack_words(vals, 16))
    out_k, cnt_k = ops.select_scan_packed(words, y, 100, 900, 16,
                                          mode="kernel", tile=tile)
    mask = (vals >= 100) & (vals <= 900)
    assert int(cnt_k) == int(mask.sum())
    np.testing.assert_array_equal(np.asarray(out_k)[:int(cnt_k)],
                                  np.arange(N)[mask])


@pytest.mark.parametrize("tile", TILES)
def test_unpack_tile_invariant(tile):
    vals = np.asarray(randi((N,), 0, 200, 3))      # 8-bit domain
    words = jnp.asarray(ST.pack_words(vals, 8))
    got = ops.unpack(words, N, 8, mode="kernel", tile=tile)
    np.testing.assert_array_equal(np.asarray(got), vals)


def _join_fixture(k=4):
    n_dim = 512
    x = randi((N,), 0, 1000, k)
    fk = randi((N,), 0, n_dim, k + 1)
    m = randi((N,), 0, 100, k + 2).astype(jnp.float32)
    dimk = np.arange(n_dim, dtype=np.int32)
    dimv = (dimk % 16).astype(np.int32)
    htk, htv = np_build(dimk, dimv, next_pow2(n_dim))
    return x, fk, m, dimv, jnp.asarray(htk), jnp.asarray(htv)


@pytest.mark.parametrize("tile", TILES)
def test_spja_tile_invariant(tile):
    x, fk, m, dimv, htk, htv = _join_fixture(4)
    bounds = jnp.array([[100, 900]], jnp.int32)
    mults = jnp.array([1], jnp.int32)
    out_k = ops.spja([x], bounds, [fk], [htk, htv], mults, m, None,
                     measure_op="first", n_groups=16, mode="kernel",
                     tile=tile)
    out_r = ref.spja([x], bounds, [fk], [htk, htv], mults, m, None,
                     measure_op="first", n_groups=16)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("tile", TILES)
def test_multi_spja_tile_invariant(tile):
    x, fk, m, dimv, htk, htv = _join_fixture(8)
    b = jnp.array([[[100, 900]], [[200, 800]]], jnp.int32)   # (Q=2, C=1, 2)
    ones2 = jnp.ones((2, 1), jnp.int32)
    q_valid = jnp.ones((2,), jnp.int32)
    msel = jnp.zeros((2, 3), jnp.int32)
    out_k = ops.multi_spja([x], b, [fk], [htk, htv], ones2, ones2,
                           q_valid, [m], msel, n_groups=16, mode="kernel",
                           tile=tile)
    out_r = ref.multi_spja([x], b, [fk], [htk, htv], ones2, ones2,
                           q_valid, [m], msel, n_groups=16)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("tile", (128, 512))
@pytest.mark.parametrize("bits", (1, 2, 3))
def test_part_probe_bits_tile_invariant(bits, tile):
    """The partitioned-probe family across radix depths AND tiles:
    output order is partition-major (depth-dependent) so compare the
    (rowid, group) pairs sorted by rowid — the only order downstream
    aggregation relies on."""
    n_build = 512
    fk = np.asarray(randi((N,), 0, n_build, 12))
    dimk = np.arange(n_build, dtype=np.int32)
    dimv = (dimk % 7).astype(np.int32)
    parts = build_dim_partitions(None, None, bits, side=(dimk, dimv),
                                 packed=True)
    outr, outg, cnt = ops.part_join(
        jnp.asarray(fk), jnp.arange(N, dtype=jnp.int32),
        jnp.zeros(N, jnp.int32), parts.htk, parts.htv, 1, bits,
        mode="kernel", tile=tile, digit=2)
    cnt = int(cnt)
    assert cnt == N                         # dense dim: every key hits
    order = np.argsort(np.asarray(outr[:cnt]), kind="stable")
    np.testing.assert_array_equal(np.asarray(outr[:cnt])[order],
                                  np.arange(N))
    np.testing.assert_array_equal(np.asarray(outg[:cnt])[order],
                                  dimv[fk])


@pytest.mark.parametrize("tile", (128, 512))
@pytest.mark.parametrize("r", (4, 8, 16))
def test_radix_sort_tile_and_r_invariant(r, tile):
    keys = randi((N,), 0, 1 << 30, 20)
    vals = jnp.arange(N, dtype=jnp.int32)
    sk, sv = ops.radix_sort(keys, vals, mode="kernel", r=r, tile=tile)
    rk, rv = ref.radix_sort(keys, vals)
    np.testing.assert_array_equal(sk, rk)
    np.testing.assert_array_equal(sv, rv)


@pytest.mark.parametrize("digit", (1, 2, 3, 4))
def test_lsb_shuffle_digit_invariant(digit):
    """The host LSD shuffle's swept pass width — including digit=3,
    which does not divide bits=8 (passes of 3, 3, 2 bits)."""
    bits = 8
    keys = randi((N,), 0, 1 << 19, 30)
    v1 = jnp.arange(N, dtype=jnp.int32)
    v2 = randi((N,), 0, 64, 31)
    ok, (o1, o2) = ops._lsb_partition_multi(keys, (v1, v2), bits, digit)
    rk, (r1, r2) = ref.partition_multi(keys, (v1, v2), 0, bits)
    np.testing.assert_array_equal(ok, rk)
    np.testing.assert_array_equal(o1, r1)
    np.testing.assert_array_equal(o2, r2)


# ---------------------------------------------------------------------------
# tune store: persistence, recovery, lookup, pick rule
# ---------------------------------------------------------------------------


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Point the cache at a private tempdir so store tests neither see
    nor pollute the session-wide (conftest) cache dir."""
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(tmp_path))
    yield str(tmp_path)


def _mk_tunings(**configs):
    return TN.Tunings(backend=jax.default_backend(),
                      fingerprint=calibrate.backend_fingerprint(),
                      measured_at=0.0, configs=configs)


def test_cache_filename_fingerprinted(tune_dir):
    base = os.path.basename(TN.cache_path())
    assert base.startswith("tunings-")
    assert jax.default_backend() in base
    assert f"jax{jax.__version__}" in base
    assert TN.cache_path() == os.path.join(tune_dir, base)
    # calibration shares the fingerprint discipline (same upgrade-
    # invalidation story)
    assert f"jax{jax.__version__}" in os.path.basename(
        calibrate.cache_path())


def test_store_roundtrip(tune_dir):
    t = _mk_tunings(**{
        "spja/w32": TN.TunedConfig("spja", 32, tile=512, best_us=10.0,
                                   default_us=15.0),
        "radix_sort/w32": TN.TunedConfig("radix_sort", 32, tile=1024,
                                         r=4, best_us=5.0,
                                         default_us=5.0)})
    path = TN.save(t)
    assert os.path.exists(path)
    TN._MEMO.clear()
    loaded = TN.load_cached()
    assert loaded is not None
    assert loaded.configs["spja/w32"].tile == 512
    assert loaded.configs["spja/w32"].speedup == pytest.approx(1.5)
    assert loaded.configs["radix_sort/w32"].r == 4
    # memo: second load must not re-read disk
    os.remove(path)
    assert TN.load_cached() is loaded


def test_torn_file_recovery(tune_dir):
    path = TN.cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write('{"backend": "cpu", "configs": {"x"')     # torn write
    TN._MEMO.clear()
    assert TN.load_cached() is None
    assert not os.path.exists(path)         # removed for a later retune
    assert TN.load_cached() is None         # absence is memoized too
    # schema drift (valid JSON, wrong shape) is also survived
    with open(path, "w") as f:
        json.dump({"backend": "cpu", "configs": {"spja/w32": 7}}, f)
    TN._MEMO.clear()
    assert TN.load_cached() is None
    assert not os.path.exists(path)


def test_width_bucket_fallback(tune_dir):
    st = TN.TuneStore(_mk_tunings(**{
        "select_scan/w32": TN.TunedConfig("select_scan", 32, tile=4096)}))
    assert st.tile("select_scan") == 4096
    # missing packed bucket falls back to the plain winner
    assert st.tile("select_scan", 16) == 4096
    # unknown family falls back to the shipped default
    assert st.tile("group_sum") == DEFAULT_TILE
    assert st.r() == TN.DEFAULT_R
    assert st.digit() == TN.DEFAULT_DIGIT
    assert st.part_budget_bytes() is None


def test_pick_tie_keeps_default():
    dflt = {"tile": DEFAULT_TILE}
    # within noise: default survives even though a candidate is faster
    cfg, best, d = TN._pick([({"tile": 512}, 0.98), (dflt, 1.0)], dflt)
    assert cfg == dflt and best == d == 1.0
    # beyond the margin: the candidate displaces it
    cfg, best, d = TN._pick([({"tile": 512}, 0.5), (dflt, 1.0)], dflt)
    assert cfg == {"tile": 512} and best == 0.5 and d == 1.0
    # stored speedup is structurally >= 1.0 either way
    assert TN.TunedConfig("x", 32, best_us=best * 1e6,
                          default_us=d * 1e6).speedup >= 1.0


def test_assert_identical_refuses_wrong_answers():
    with pytest.raises(AssertionError, match="never change answers"):
        TN._assert_identical("spja", {"tile": 64},
                             (np.arange(4),), (np.arange(4) + 1,))


# ---------------------------------------------------------------------------
# launch threading: cold-store fallback, tuned pickup, explicit wins
# ---------------------------------------------------------------------------

DB = ssb.generate(sf=0.002, seed=5)
QUERIES = engine.ssb_queries()


def test_cold_store_launches_default_byte_for_byte(tune_dir):
    """No tuning cache: tile=None must resolve to DEFAULT_TILE and the
    result must be byte-identical to an explicit default-tile run."""
    TN._MEMO.clear()
    assert TN.cached_store() is None
    assert TN.tuned_tile("spja") == DEFAULT_TILE
    assert TN.tuned_r() == TN.DEFAULT_R
    cq = compile_plan(QUERIES["q2.1"], "fused")
    got = cq.execute(DB, mode="ref")
    assert cq.launch_config["spja"] == {
        "tile": DEFAULT_TILE, "width": 32, "source": "default",
        "impl": "xla"}
    cq2 = compile_plan(QUERIES["q2.1"], "fused")
    explicit = cq2.execute(DB, mode="ref", tile=DEFAULT_TILE)
    assert cq2.launch_config["spja"]["source"] == "explicit"
    np.testing.assert_array_equal(np.asarray(got), np.asarray(explicit))


def test_tuned_store_drives_launch_and_preserves_answers(tune_dir):
    TN.save(_mk_tunings(**{
        "spja/w32": TN.TunedConfig("spja", 32, tile=512, best_us=1.0,
                                   default_us=2.0)}))
    cq = compile_plan(QUERIES["q2.1"], "fused")
    got = cq.execute(DB, mode="ref")
    assert cq.launch_config["spja"] == {
        "tile": 512, "width": 32, "source": "tuned", "impl": "xla"}
    # explicit tile still wins over the store
    cq2 = compile_plan(QUERIES["q2.1"], "fused")
    exp = cq2.execute(DB, mode="ref", tile=DEFAULT_TILE)
    assert cq2.launch_config["spja"] == {
        "tile": DEFAULT_TILE, "width": 32, "source": "explicit",
        "impl": "xla"}
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_part_launch_reports_bits_and_digit(tune_dir):
    TN._MEMO.clear()
    cq = compile_plan(QUERIES["q2.1"], "part")
    cq.execute(DB, mode="ref")
    lc = cq.launch_config["part_probe"]
    assert lc["source"] == "default" and lc["tile"] == DEFAULT_TILE
    assert lc["bits"] >= 1 and lc["digit"] == TN.DEFAULT_DIGIT


# ---------------------------------------------------------------------------
# cost-model feedback
# ---------------------------------------------------------------------------


def test_part_budget_feedback_reproduces_best_bits():
    """The budget the sweep stores must make model.part_bits reproduce
    the measured best depth at the calibration build size — for every
    depth the grid can pick."""
    n_build = 1 << 19
    for best_bits in (1, 2, 3, 4, 5, 6, 8):
        budget = int(M.ht_bytes(n_build) * 2 / (3 << (best_bits - 1)))
        hw = dataclasses.replace(M.HOST, part_budget_bytes=budget)
        assert M.part_bits(n_build, hw=hw) == best_bits, best_bits


def test_apply_hardware_folds_tuned_feedback():
    st = TN.TuneStore(_mk_tunings(**{
        "part_probe/w32": TN.TunedConfig(
            "part_probe", 32, part_bits=2, part_budget_bytes=123456),
        "select_scan/w32": TN.TunedConfig(
            "select_scan", 32, tile=4096, eff_bw=12.5e9)}))
    hw = TN.apply_hardware(st, M.HOST)
    assert hw.name == M.HOST.name + "-tuned"
    assert hw.part_budget_bytes == 123456
    assert hw.read_bw == 12.5e9
    # nothing to fold -> base returned untouched
    assert TN.apply_hardware(TN.TuneStore(_mk_tunings()), M.HOST) is M.HOST


def test_tuned_hardware_cold_is_base(tune_dir):
    TN._MEMO.clear()
    assert TN.tuned_hardware(M.HOST) is M.HOST
