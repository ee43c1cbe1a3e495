"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
sweeping shapes, dtypes and tile sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def randi(shape, lo, hi, k=0, dtype=jnp.int32):
    return jax.random.randint(jax.random.fold_in(KEY, k), shape, lo, hi,
                              dtype)


@pytest.mark.parametrize("n", [64, 1000, 4096])
@pytest.mark.parametrize("tile", [128, 512])
def test_select_scan_shapes(n, tile):
    x = randi((n,), 0, 100, 1)
    y = randi((n,), 0, 1000, 2)
    out_k, cnt_k = ops.select_scan(x, y, 20, 70, mode="kernel", tile=tile)
    out_r, cnt_r = ref.select_scan(x, y, 20, 70)
    assert int(cnt_k) == int(cnt_r)
    np.testing.assert_array_equal(np.asarray(out_k)[:int(cnt_k)],
                                  np.asarray(out_r)[:int(cnt_r)])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_select_scan_dtypes(dtype):
    n = 2000
    if dtype == jnp.float32:
        x = jax.random.uniform(KEY, (n,), dtype) * 100
        y = jax.random.normal(jax.random.fold_in(KEY, 1), (n,), dtype)
    else:
        x = randi((n,), 0, 100, 1, dtype)
        y = randi((n,), 0, 100, 2, dtype)
    out_k, cnt_k = ops.select_scan(x, y, 10, 60, mode="kernel", tile=256)
    out_r, cnt_r = ref.select_scan(x, y, 10, 60)
    assert int(cnt_k) == int(cnt_r)
    np.testing.assert_allclose(np.asarray(out_k)[:int(cnt_k)],
                               np.asarray(out_r)[:int(cnt_r)])


def test_select_scan_extremes():
    n = 1024
    x = randi((n,), 0, 100, 1)
    y = randi((n,), 0, 100, 2)
    # selectivity 0 and 1
    for lo, hi in ((1000, 2000), (0, 100)):
        out_k, cnt_k = ops.select_scan(x, y, lo, hi, mode="kernel", tile=256)
        _, cnt_r = ref.select_scan(x, y, lo, hi)
        assert int(cnt_k) == int(cnt_r)


@pytest.mark.parametrize("sigmoid", [False, True])
@pytest.mark.parametrize("n", [100, 5000])
def test_project(sigmoid, n):
    x1 = jax.random.normal(KEY, (n,), jnp.float32)
    x2 = jax.random.normal(jax.random.fold_in(KEY, 1), (n,), jnp.float32)
    out_k = ops.project(x1, x2, 1.5, -0.5, sigmoid=sigmoid, mode="kernel",
                        tile=256)
    out_r = ref.project(x1, x2, 1.5, -0.5, sigmoid=sigmoid)
    np.testing.assert_allclose(out_k, out_r, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n_build,n_slots", [(100, 256), (500, 2048)])
def test_hash_build_probe(n_build, n_slots):
    bk = jax.random.permutation(KEY, jnp.arange(5 * n_build,
                                                dtype=jnp.int32))[:n_build]
    bv = randi((n_build,), 0, 100, 3)
    htk, htv = ops.build_hash_table(bk, bv, n_slots, mode="kernel", tile=128)
    htk_r, htv_r = ref.build(bk, bv, n_slots)
    n = 3000
    probe = randi((n,), 0, 5 * n_build, 4)
    vals = randi((n,), 0, 100, 5)
    agg_k = ops.probe_agg(probe, vals, htk, htv, mode="kernel", tile=512)
    agg_r = ref.probe_agg(probe, vals, htk_r, htv_r)
    assert int(agg_k) == int(agg_r)
    pj_k = ops.probe_join(probe, vals, htk, htv, mode="kernel", tile=512)
    pj_r = ref.probe_join(probe, vals, htk_r, htv_r)
    assert int(pj_k[2]) == int(pj_r[2])
    c = int(pj_k[2])
    np.testing.assert_array_equal(np.asarray(pj_k[0])[:c],
                                  np.asarray(pj_r[0])[:c])
    np.testing.assert_array_equal(np.asarray(pj_k[1])[:c],
                                  np.asarray(pj_r[1])[:c])


@pytest.mark.parametrize("r", [4, 8])
def test_radix_partition(r):
    n = 3000
    keys = randi((n,), 0, 2**31 - 1, 6)
    vals = jnp.arange(n, dtype=jnp.int32)
    pk, pv = ops.radix_partition(keys, vals, 8, r, mode="kernel", tile=512)
    rk, rv = ref.partition(keys, vals, 8, r)
    np.testing.assert_array_equal(pk, rk)
    np.testing.assert_array_equal(pv, rv)


def test_radix_sort_full():
    n = 4000
    keys = randi((n,), 0, 2**31 - 1, 7)
    vals = jnp.arange(n, dtype=jnp.int32)
    sk, sv = ops.radix_sort(keys, vals, mode="kernel", tile=512)
    rk, rv = ref.radix_sort(keys, vals)
    np.testing.assert_array_equal(sk, rk)
    np.testing.assert_array_equal(sv, rv)


def test_agg():
    n = 3000
    x = randi((n,), 0, 100, 8)
    g = randi((n,), 0, 13, 9)
    assert int(ops.reduce_sum(x, mode="kernel", tile=256)) == \
        int(ref.reduce_sum(x))
    np.testing.assert_array_equal(
        ops.group_sum(g, x, 13, mode="kernel", tile=256),
        ref.group_sum(g, x, 13))


def test_spja_fused():
    n = 4000
    x = randi((n,), 0, 100, 10)
    fk = randi((n,), 0, 500, 11)
    m1 = randi((n,), 1, 50, 12).astype(jnp.float32)
    m2 = randi((n,), 1, 10, 13).astype(jnp.float32)
    bk = jax.random.permutation(KEY, jnp.arange(500, dtype=jnp.int32))[:200]
    bv = randi((200,), 0, 9, 14)
    htk, htv = ref.build(bk, bv, 1024)
    pb = jnp.array([[20, 80]], jnp.int32)
    mults = jnp.array([1], jnp.int32)
    for mop, mm2 in (("first", None), ("mul", m2), ("sub", m2)):
        out_k = ops.spja([x], pb, [fk], [htk, htv], mults, m1, mm2,
                         measure_op=mop, n_groups=9, mode="kernel", tile=512)
        out_r = ref.spja([x], pb, [fk], [htk, htv], mults, m1, mm2,
                         measure_op=mop, n_groups=9)
        np.testing.assert_allclose(out_k, out_r, rtol=1e-5)


def test_integer_group_sums_exact_past_f32_and_int32():
    """Integer measures sum exactly on the XLA path: totals far past
    2^24 (and 2^31, where an int32 accumulator alone wraps) come out as
    the f32 rounding of the exact integer total, negative ones too."""
    rng = np.random.default_rng(5)
    n = 200_000
    g = rng.integers(0, 3, n).astype(np.int32)
    v = rng.integers(0, 100_000, n).astype(np.int32)
    v[g == 2] *= -1                       # a negative total
    exact = np.array([v[g == k].astype(np.int64).sum() for k in range(3)])
    assert np.abs(exact).min() > 2 ** 31
    got = ref.group_sum(jnp.asarray(g), jnp.asarray(v), 3)
    np.testing.assert_array_equal(np.asarray(got), exact.astype(np.float32))
    # the same sums through the blocked SPJA fold (a block size that
    # leaves a tail), single group and grouped
    ones = jnp.ones((n,), jnp.int32)
    bounds = jnp.array([[1, 1]], jnp.int32)
    old = ops.XLA_BLOCK_ROWS
    try:
        ops.XLA_BLOCK_ROWS = 4096
        got1 = ops.spja([ones], bounds, [], [], jnp.zeros((0,), jnp.int32),
                        jnp.asarray(v), mode="ref", n_groups=1)
    finally:
        ops.XLA_BLOCK_ROWS = old
    np.testing.assert_array_equal(np.asarray(got1),
                                  np.float32(exact.sum()))


def test_blocked_prefix_sum_and_compaction(monkeypatch):
    """``prefix_sum``/``compact`` split large inputs into pieces; shrunk
    piece sizes must give numpy's cumsum and stable compaction."""
    monkeypatch.setattr(ref, "_SCAN_WINDOW", 8)
    monkeypatch.setattr(ref, "_SCATTER_BLOCK", 64)
    rng = np.random.default_rng(6)
    for n in (7, 64, 65, 1000):
        x = rng.integers(0, 5, n).astype(np.int32)
        np.testing.assert_array_equal(ref.prefix_sum(jnp.asarray(x)),
                                      np.cumsum(x))
        out, cnt = ref.select_scan(jnp.asarray(x), jnp.arange(n), 2, 3)
        keep = np.flatnonzero((x >= 2) & (x <= 3))
        want = np.zeros(n, np.int32)
        want[:len(keep)] = keep
        assert int(cnt) == len(keep)
        np.testing.assert_array_equal(out, want)
