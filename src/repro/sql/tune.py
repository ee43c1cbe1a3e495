"""Per-backend empirical kernel autotuner (measured, not modeled).

The kernels ship with launch constants hand-tuned for one device —
``DEFAULT_TILE = 2048`` items per block (the paper's V100 best, §3.3 /
Fig. 9) and fixed radix widths — but the optimum is hardware-specific:
the paper's own items-per-thread sweep moves the knee per device, and
the follow-up literature (arXiv 2302.00734, 2508.04701) attributes large
cross-system gaps to exactly these per-device launch choices.  This
module closes the gap empirically: per (kernel family, backend,
packed-width bucket) it sweeps the launch-configuration space on
synthetic data shaped like the calibration microbenchmarks
(``repro.sql.calibrate``), asserts every swept configuration is
bit-identical to the numpy oracle BEFORE timing it, and persists the
winners next to the calibration cache.

Swept knobs per family:

  tile         — items per block, word-alignment-legal powers of two
                 (``common.words_per_block`` requires
                 ``tile % (32/phys) == 0``; every pow2 tile >= 32
                 satisfies all physical widths).  On the jnp host path
                 the tile is only a jit cache key, so the sweep ties and
                 the default survives (see the tie rule below) — on a
                 kernel backend it is the paper's Fig. 9 sweep.
  r / digit    — radix pass width: ``radix_sort``'s digit bits, and the
                 host LSD shuffle's pass width for ``partition_multi``
                 (``ops._lsb_partition_multi``: a d-bit pass costs 2^d
                 cumsums but only ONE scatter per d bits — the
                 scatter/scan trade is hardware-specific and measurably
                 so on CPU).
  part_bits    — the partitioned-probe family's radix depth.  Each bit
                 is one more full shuffle pass over the probe side; the
                 win (cache-resident partition tables) is real on
                 devices with a steep cache/memory cliff and absent on
                 the jnp host path, so the static
                 ``model.PART_BUDGET_BYTES`` formula can be badly off.
                 The winner is fed back as an equivalent per-partition
                 byte budget (``TunedConfig.part_budget_bytes``) so
                 ``model.part_bits`` — used by BOTH the execute path and
                 the cost model — reproduces the measured best depth at
                 the calibration shape and scales it by table size.

Tie rule: a candidate replaces the default configuration only when it
is faster beyond measurement noise (``WIN_MARGIN``).  Inert knobs
therefore keep the default — the tuner can make launches faster, never
slower, and never changes answers (bit-identity is asserted per swept
configuration, and ``tests/test_tune.py`` property-tests invariance
independently).

Results persist in ``tunings-{backend}-jax{ver}-{devkind}.json`` in the
same cache directory as the calibration (``REPRO_CALIB_CACHE``
override), with the same in-process memo and torn-file recovery; the
jax version + device kind in the filename means a driver upgrade
re-measures instead of silently serving stale winners.

    PYTHONPATH=src python -m repro.sql.tune              # show (tune if cold)
    PYTHONPATH=src python -m repro.sql.tune --retune     # re-measure
    PYTHONPATH=src python -m repro.sql.tune --smoke      # reduced grid (CI)
    PYTHONPATH=src python -m repro.sql.tune --json out   # + TUNINGS.json
"""
from __future__ import annotations

import dataclasses
import functools
import json
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.common import DEFAULT_TILE
from repro.sql import calibrate
from repro.sql import storage as ST
from repro.sql.hashtable import build_dim_partitions, next_pow2, np_build

FAMILIES = ("select_scan", "unpack", "spja", "multi_spja", "part_probe",
            "radix_sort", "partition_multi")

DEFAULT_R = 8                   # radix_sort's shipped digit width
DEFAULT_DIGIT = 1               # host LSD shuffle's shipped pass width
WIN_MARGIN = 0.03               # a winner must beat default by > 3%

# sweep grids: every tile is a power of two >= 32, so it satisfies the
# word-alignment constraint tile % (32/phys) == 0 for every physical
# width storage can pack
FULL_GRID = dict(tiles=(512, 1024, 2048, 4096, 8192),
                 rs=(4, 8, 16), digits=(1, 2, 4),
                 bits=(1, 2, 3, 4, 5, 6, 8),
                 n=1 << 21, n_build=1 << 19, warmup=1, iters=3)
# smoke build side 2^17: big enough that the static formula defaults to
# bits=3, so the part_bits sweep exercises a real decision even on CI
SMOKE_GRID = dict(tiles=(1024, 2048, 4096),
                  rs=(8, 16), digits=(1, 2),
                  bits=(1, 3, 5),
                  n=1 << 18, n_build=1 << 17, warmup=1, iters=2)


@dataclass(frozen=True)
class TunedConfig:
    """Winner of one (family, width-bucket) sweep.  ``r`` doubles as the
    host shuffle's digit width for the partition families; ``part_bits``
    / ``part_budget_bytes`` are set for ``part_probe`` only.  ``eff_bw``
    is the measured effective scan bandwidth (bytes touched / best
    seconds) where the family streams a known byte count — what
    ``apply_hardware`` feeds back into the cost model."""
    family: str
    width: int                  # packed-width bucket (32 = plain int32)
    tile: int = DEFAULT_TILE
    r: Optional[int] = None
    part_bits: Optional[int] = None
    part_budget_bytes: Optional[int] = None
    best_us: float = 0.0
    default_us: float = 0.0
    eff_bw: Optional[float] = None

    @property
    def speedup(self) -> float:
        """Measured default-config / best-config time (1.0 when the
        default itself won the sweep)."""
        if self.best_us <= 0 or self.default_us <= 0:
            return 1.0
        return self.default_us / self.best_us


@dataclass(frozen=True)
class Tunings:
    """One backend's persisted sweep results."""
    backend: str
    fingerprint: str            # calibrate.backend_fingerprint()
    measured_at: float
    configs: Dict[str, TunedConfig] = field(default_factory=dict)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: dict) -> "Tunings":
        fields_ = {f.name for f in dataclasses.fields(Tunings)}
        d = {k: v for k, v in d.items() if k in fields_}
        cfg_fields = {f.name for f in dataclasses.fields(TunedConfig)}
        d["configs"] = {
            k: TunedConfig(**{kk: vv for kk, vv in v.items()
                              if kk in cfg_fields})
            for k, v in dict(d.get("configs") or {}).items()}
        return Tunings(**d)


def _key(family: str, width: int = 32) -> str:
    return f"{family}/w{width}"


class TuneStore:
    """Lookup view over a :class:`Tunings` record — the object
    ``sql/compile.py`` consults per launch.  Unknown families and
    width buckets fall back to the shipped defaults, so a store can
    never make a launch illegal; a missing packed bucket falls back to
    the plain (w32) winner of the same family."""

    def __init__(self, tunings: Tunings):
        self.tunings = tunings

    def get(self, family: str, width: int = 32) -> Optional[TunedConfig]:
        cfg = self.tunings.configs.get(_key(family, width))
        if cfg is None and width != 32:
            cfg = self.tunings.configs.get(_key(family, 32))
        return cfg

    def tile(self, family: str, width: int = 32,
             default: int = DEFAULT_TILE) -> int:
        cfg = self.get(family, width)
        return cfg.tile if cfg is not None else default

    def r(self, family: str = "radix_sort",
          default: int = DEFAULT_R) -> int:
        cfg = self.get(family)
        return cfg.r if cfg is not None and cfg.r else default

    def digit(self, default: int = DEFAULT_DIGIT) -> int:
        cfg = self.get("partition_multi")
        return cfg.r if cfg is not None and cfg.r else default

    def part_budget_bytes(self) -> Optional[int]:
        cfg = self.get("part_probe")
        return cfg.part_budget_bytes if cfg is not None else None

    def eff_read_bw(self) -> Optional[float]:
        cfg = self.get("select_scan")
        return cfg.eff_bw if cfg is not None else None


# ---------------------------------------------------------------------------
# disk cache (same directory, memo and torn-file discipline as calibrate)
# ---------------------------------------------------------------------------


def cache_path(backend: Optional[str] = None) -> str:
    """Per-(backend, jax version, device kind) tuning cache file, next
    to the calibration cache (``REPRO_CALIB_CACHE`` override)."""
    fp = calibrate.backend_fingerprint(backend)
    return os.path.join(calibrate.cache_dir(), f"tunings-{fp}.json")


# memoizes even absence (None) — compile.py consults the store per
# launch, so a cold cache must cost one os.path lookup total, not one
# per query
_MEMO: dict = {}


def save(tunings: Tunings) -> str:
    path = cache_path(tunings.backend)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(tunings.to_json(), f, indent=1)
    _MEMO[path] = tunings
    return path


def load_cached(backend: Optional[str] = None) -> Optional[Tunings]:
    """Load the persisted sweep results, or None.  A corrupted cache
    (torn write, schema drift, junk bytes) is logged, removed from disk
    and reported as no-cache — the engine then simply launches with the
    shipped defaults and a later ``--retune`` writes a fresh file."""
    path = cache_path(backend)
    if path in _MEMO:
        return _MEMO[path]
    tunings = None
    if os.path.exists(path):
        try:
            with open(path) as f:
                tunings = Tunings.from_json(json.load(f))
        except (ValueError, TypeError, KeyError, AttributeError,
                OSError) as e:
            logging.getLogger(__name__).warning(
                "discarding corrupt tuning cache %s (%s: %s); "
                "launching with defaults until --retune", path,
                type(e).__name__, e)
            tunings = None
            try:
                os.remove(path)
            except OSError:
                pass
    _MEMO[path] = tunings
    return tunings


def cached_store(backend: Optional[str] = None) -> Optional[TuneStore]:
    """Non-measuring store lookup for the launch paths: the TuneStore
    iff sweep results are on disk, else None (defaults)."""
    tunings = load_cached(backend)
    return None if tunings is None else TuneStore(tunings)


# module-level conveniences for the per-launch call sites --------------------


def tuned_tile(family: str, width: int = 32,
               default: int = DEFAULT_TILE) -> int:
    st = cached_store()
    return st.tile(family, width, default) if st is not None else default


def tuned_r(family: str = "radix_sort", default: int = DEFAULT_R) -> int:
    st = cached_store()
    return st.r(family, default) if st is not None else default


def tuned_digit(default: int = DEFAULT_DIGIT) -> int:
    st = cached_store()
    return st.digit(default) if st is not None else default


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def _bench(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _pick(timed: List[Tuple[dict, float]], default_cfg: dict
          ) -> Tuple[dict, float, float]:
    """(winner config, winner seconds, default seconds).  The default
    configuration must be in ``timed``; a candidate only displaces it
    when faster by more than WIN_MARGIN — on paths where the knob is
    inert the sweep ties within noise and the default survives, so a
    tuned launch is never slower than an untuned one."""
    default_s = next(s for c, s in timed if c == default_cfg)
    best_cfg, best_s = default_cfg, default_s
    for cfg, s in timed:
        if s < best_s * (1.0 - 1e-12) and s < default_s * (1 - WIN_MARGIN):
            best_cfg, best_s = cfg, s
    return best_cfg, best_s, default_s


def _assert_identical(family: str, cfg: dict, got, want) -> None:
    got = [np.asarray(g) for g in got]
    want = [np.asarray(w) for w in want]
    for g, w in zip(got, want):
        if g.shape != w.shape or not np.array_equal(g, w):
            raise AssertionError(
                f"tuner sweep {family} {cfg}: result differs from the "
                "oracle — refusing to time (a tuned config must never "
                "change answers)")


def _sweep_select_scan(g: dict, rng) -> List[TunedConfig]:
    n = g["n"]
    x = rng.integers(0, 1000, n).astype(np.int32)
    y = np.arange(n, dtype=np.int32)
    lo, hi = 100, 900
    mask = (x >= lo) & (x <= hi)
    want_out, want_cnt = y[mask], int(mask.sum())
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    out: List[TunedConfig] = []

    timed = []
    for t in g["tiles"]:
        sel, cnt = ops.select_scan(xj, yj, lo, hi, tile=t)
        _assert_identical("select_scan", {"tile": t},
                          (sel[:int(cnt)], int(cnt)), (want_out, want_cnt))
        timed.append(({"tile": t},
                      _bench(lambda tt=t: ops.select_scan(xj, yj, lo, hi,
                                                          tile=tt),
                             warmup=g["warmup"], iters=g["iters"])))
    cfg, best, dflt = _pick(timed, {"tile": DEFAULT_TILE})
    out.append(TunedConfig("select_scan", 32, tile=cfg["tile"],
                           best_us=best * 1e6, default_us=dflt * 1e6,
                           eff_bw=2.0 * 4 * n / best))

    # packed bucket: the same scan off the bit-packed word stream
    pc = ST.pack_column(x)
    if pc.encoding.kind != "plain":
        phys = pc.encoding.phys
        lo2, hi2 = ST.encoded_bounds(pc.encoding, lo, hi)
        words = pc.words_jax()
        timed = []
        for t in g["tiles"]:
            sel, cnt = ops.select_scan_packed(words, yj, lo2, hi2, phys,
                                              tile=t)
            _assert_identical("select_scan_packed", {"tile": t},
                              (sel[:int(cnt)], int(cnt)),
                              (want_out, want_cnt))
            timed.append(({"tile": t},
                          _bench(lambda tt=t: ops.select_scan_packed(
                              words, yj, lo2, hi2, phys, tile=tt),
                              warmup=g["warmup"], iters=g["iters"])))
        cfg, best, dflt = _pick(timed, {"tile": DEFAULT_TILE})
        out.append(TunedConfig("select_scan", phys, tile=cfg["tile"],
                               best_us=best * 1e6, default_us=dflt * 1e6,
                               eff_bw=(4 * n + phys * n / 8) / best))
    return out


def _sweep_unpack(g: dict, rng) -> List[TunedConfig]:
    n = g["n"]
    vals = rng.integers(0, 200, n).astype(np.int32)     # 8-bit domain
    phys = 8
    words = jnp.asarray(ST.pack_words(vals, phys))
    timed = []
    for t in g["tiles"]:
        got = ops.unpack(words, n, phys, tile=t)
        _assert_identical("unpack", {"tile": t}, (got,), (vals,))
        timed.append(({"tile": t},
                      _bench(lambda tt=t: ops.unpack(words, n, phys,
                                                     tile=tt),
                             warmup=g["warmup"], iters=g["iters"])))
    cfg, best, dflt = _pick(timed, {"tile": DEFAULT_TILE})
    return [TunedConfig("unpack", phys, tile=cfg["tile"],
                        best_us=best * 1e6, default_us=dflt * 1e6,
                        eff_bw=(phys * n / 8 + 4 * n) / best)]


def _spja_fixture(g: dict, rng):
    """Shared single-join SPJA microbenchmark: one range predicate, one
    FK join against a 64-group dim payload, one integer-valued measure
    (so f32 partial sums are exact and the numpy oracle is bit-exact)."""
    n, n_dim = g["n"], 1 << 16
    x = rng.integers(0, 1000, n).astype(np.int32)
    fk = rng.integers(0, n_dim, n).astype(np.int32)
    m = rng.integers(0, 100, n).astype(np.int32)
    dimk = np.arange(n_dim, dtype=np.int32)
    dimv = (dimk % 64).astype(np.int32)
    htk, htv = np_build(dimk, dimv, next_pow2(n_dim))
    return x, fk, m, dimv, jnp.asarray(htk), jnp.asarray(htv)


def _sweep_spja(g: dict, rng) -> List[TunedConfig]:
    x, fk, m, dimv, htk, htv = _spja_fixture(g, rng)
    n = g["n"]
    lo, hi = 100, 900
    mask = (x >= lo) & (x <= hi)
    grp = dimv[fk]
    want = np.bincount(grp[mask], weights=m[mask],
                       minlength=64).astype(np.float32)
    xj, fkj = jnp.asarray(x), jnp.asarray(fk)
    mj = jnp.asarray(m).astype(jnp.float32)
    bounds = jnp.asarray(np.array([[lo, hi]], np.int32))
    mults = jnp.asarray(np.array([1], np.int32))

    def run(t):
        return ops.spja([xj], bounds, [fkj], [htk, htv], mults, mj,
                        measure_op="first", n_groups=64, tile=t)

    timed = []
    for t in g["tiles"]:
        _assert_identical("spja", {"tile": t}, (run(t),), (want,))
        timed.append(({"tile": t}, _bench(functools.partial(run, t),
                                          warmup=g["warmup"],
                                          iters=g["iters"])))
    cfg, best, dflt = _pick(timed, {"tile": DEFAULT_TILE})
    return [TunedConfig("spja", 32, tile=cfg["tile"], best_us=best * 1e6,
                        default_us=dflt * 1e6, eff_bw=3.0 * 4 * n / best)]


def _sweep_multi_spja(g: dict, rng) -> List[TunedConfig]:
    x, fk, m, dimv, htk, htv = _spja_fixture(g, rng)
    n = g["n"]
    b = np.array([[[100, 900]], [[200, 800]]], np.int32)    # (Q=2, C=1, 2)
    grp = dimv[fk]
    want = np.stack([
        np.bincount(grp[(x >= lo) & (x <= hi)],
                    weights=m[(x >= lo) & (x <= hi)],
                    minlength=64).astype(np.float32)
        for (lo, hi) in b[:, 0]])
    xj, fkj = jnp.asarray(x), jnp.asarray(fk)
    mj = jnp.asarray(m).astype(jnp.float32)
    ones2 = jnp.ones((2, 1), jnp.int32)
    q_valid = jnp.ones((2,), jnp.int32)
    msel = jnp.zeros((2, 3), jnp.int32)

    def run(t):
        return ops.multi_spja([xj], jnp.asarray(b), [fkj], [htk, htv],
                              ones2, ones2, q_valid, [mj], msel,
                              n_groups=64, tile=t)

    timed = []
    for t in g["tiles"]:
        _assert_identical("multi_spja", {"tile": t}, (run(t),), (want,))
        timed.append(({"tile": t}, _bench(functools.partial(run, t),
                                          warmup=g["warmup"],
                                          iters=g["iters"])))
    cfg, best, dflt = _pick(timed, {"tile": DEFAULT_TILE})
    return [TunedConfig("multi_spja", 32, tile=cfg["tile"],
                        best_us=best * 1e6, default_us=dflt * 1e6,
                        eff_bw=3.0 * 4 * n / best)]


def _sweep_radix_sort(g: dict, rng) -> List[TunedConfig]:
    n = g["n"]
    keys = rng.integers(0, 1 << 30, n).astype(np.int32)
    vals = np.arange(n, dtype=np.int32)
    order = np.argsort(keys, kind="stable")
    want = (keys[order], vals[order])
    kj, vj = jnp.asarray(keys), jnp.asarray(vals)
    timed = []
    for t in g["tiles"]:
        for r in g["rs"]:
            cfg = {"tile": t, "r": r}
            _assert_identical("radix_sort", cfg,
                              ops.radix_sort(kj, vj, r=r, tile=t), want)
            timed.append((cfg,
                          _bench(lambda tt=t, rr=r: ops.radix_sort(
                              kj, vj, r=rr, tile=tt),
                              warmup=g["warmup"], iters=g["iters"])))
    cfg, best, dflt = _pick(timed, {"tile": DEFAULT_TILE, "r": DEFAULT_R})
    return [TunedConfig("radix_sort", 32, tile=cfg["tile"], r=cfg["r"],
                        best_us=best * 1e6, default_us=dflt * 1e6)]


@functools.partial(jax.jit, static_argnames=("bits", "digit"))
def _shuffle_jit(keys, vals, *, bits: int, digit: int):
    return ops._lsb_partition_multi(keys, vals, bits, digit)


def _sweep_partition_multi(g: dict, rng) -> List[TunedConfig]:
    """The partitioned join's stable low-bit shuffle: sweep the LSD pass
    width at the deepest radix depth the engine uses (8 bits — the
    per-pass trade is width-independent, and deeper amplifies it)."""
    n = g["n"]
    bits = 8
    keys = rng.integers(0, 1 << 19, n).astype(np.int32)
    v1 = np.arange(n, dtype=np.int32)
    v2 = rng.integers(0, 64, n).astype(np.int32)
    order = np.argsort(keys & ((1 << bits) - 1), kind="stable")
    want = (keys[order], v1[order], v2[order])
    kj = jnp.asarray(keys)
    vj = (jnp.asarray(v1), jnp.asarray(v2))
    timed = []
    for d in g["digits"]:
        ok, (o1, o2) = _shuffle_jit(kj, vj, bits=bits, digit=d)
        _assert_identical("partition_multi", {"digit": d},
                          (ok, o1, o2), want)
        timed.append(({"digit": d},
                      _bench(lambda dd=d: _shuffle_jit(kj, vj, bits=bits,
                                                       digit=dd),
                             warmup=g["warmup"], iters=g["iters"])))
    cfg, best, dflt = _pick(timed, {"digit": DEFAULT_DIGIT})
    return [TunedConfig("partition_multi", 32, tile=DEFAULT_TILE,
                        r=cfg["digit"], best_us=best * 1e6,
                        default_us=dflt * 1e6)]


def _part_default_bits(n_build: int) -> int:
    """The UNTUNED radix depth for ``n_build`` — the static formula with
    the shipped budget, deliberately bypassing any tuned hardware so the
    sweep's baseline is what the engine would do without this module."""
    from repro.sql import model as M
    return M.part_bits(n_build, hw=M.base_hardware())


def _sweep_part_probe(g: dict, rng, digit: int) -> List[TunedConfig]:
    """Sweep the partitioned-probe family's radix depth at the
    calibration shape, then express the winner as a per-partition byte
    budget: ``model.part_bits`` with that budget reproduces the measured
    best depth for this build size and scales it with table size (a 2x
    bigger table gets one more bit).  ``digit`` is the already-tuned
    shuffle pass width, so the sweep times the composed launch the
    engine will actually run."""
    from repro.sql import model as M
    n, n_build = g["n"], g["n_build"]
    fk = rng.integers(0, n_build, n).astype(np.int32)
    dimk = np.arange(n_build, dtype=np.int32)
    dimv = (dimk % 64).astype(np.int32)
    col = jnp.asarray(fk)
    rowids = jnp.arange(n, dtype=jnp.int32)
    groups = jnp.zeros(n, jnp.int32)
    # oracle: every key hits (dense dim domain); output order is
    # partition-major and therefore depth-dependent, so compare the
    # (rowid, group) multiset sorted by rowid — the only order the
    # engine relies on downstream (aggregation is order-insensitive)
    want_r = np.arange(n, dtype=np.int32)
    want_g = dimv[fk]

    default_bits = _part_default_bits(n_build)
    bits_grid = sorted(set(g["bits"]) | {default_bits})
    timed = []
    for b in bits_grid:
        parts = build_dim_partitions(None, None, b, side=(dimk, dimv),
                                     packed=True)

        def run(bb=b, p=parts):
            return ops.part_join(col, rowids, groups, p.htk, p.htv, 1,
                                 bits=bb, digit=digit)

        outr, outg, cnt = run()
        cnt = int(cnt)
        order = np.argsort(np.asarray(outr[:cnt]), kind="stable")
        _assert_identical("part_probe", {"bits": b},
                          (np.asarray(outr[:cnt])[order],
                           np.asarray(outg[:cnt])[order]),
                          (want_r, want_g))
        timed.append(({"bits": b}, _bench(run, warmup=g["warmup"],
                                          iters=g["iters"])))
    cfg, best, dflt = _pick(timed, {"bits": default_bits})
    best_bits = cfg["bits"]
    # budget such that ceil(log2(ht_bytes / budget)) == best_bits at the
    # calibration build size: 2/3 of ht/2^(bits-1) sits strictly inside
    # the half-open interval that maps there
    budget = int(M.ht_bytes(n_build) * 2 / (3 << (best_bits - 1)))
    return [TunedConfig("part_probe", 32, tile=DEFAULT_TILE,
                        part_bits=best_bits, part_budget_bytes=budget,
                        best_us=best * 1e6, default_us=dflt * 1e6)]


def measure(grid: Optional[dict] = None, seed: int = 0) -> Tunings:
    """Run every family sweep on the current backend and return the
    winners (not yet persisted — callers decide via :func:`save`)."""
    g = dict(FULL_GRID if grid is None else grid)
    rng = np.random.default_rng(seed)
    configs: Dict[str, TunedConfig] = {}

    def put(cfgs: List[TunedConfig]) -> None:
        for c in cfgs:
            configs[_key(c.family, c.width)] = c

    put(_sweep_select_scan(g, rng))
    put(_sweep_unpack(g, rng))
    put(_sweep_spja(g, rng))
    put(_sweep_multi_spja(g, rng))
    put(_sweep_radix_sort(g, rng))
    put(_sweep_partition_multi(g, rng))
    digit = configs[_key("partition_multi")].r or DEFAULT_DIGIT
    put(_sweep_part_probe(g, rng, digit))
    return Tunings(backend=jax.default_backend(),
                   fingerprint=calibrate.backend_fingerprint(),
                   measured_at=time.time(), configs=configs)


def tuned_store(refresh: bool = False,
                grid: Optional[dict] = None) -> TuneStore:
    """Measure (or load the cached sweep) and return the lookup store —
    the measuring analogue of :func:`cached_store`."""
    tunings = None if refresh else load_cached()
    if tunings is None:
        tunings = measure(grid=grid)
        save(tunings)
    return TuneStore(tunings)


# ---------------------------------------------------------------------------
# Hardware integration (cost model feedback)
# ---------------------------------------------------------------------------


def apply_hardware(store: TuneStore, base):
    """``base`` with the tuner's feedback folded in: the partitioned
    join's per-partition byte budget (so ``model.part_bits`` — shared by
    the execute path and the cost model — reproduces the measured best
    depth), and the effective scan bandwidth at the best tile (so
    strategies are priced off what a tuned scan kernel actually moves,
    not the generic triad number)."""
    kw = {}
    budget = store.part_budget_bytes()
    if budget:
        kw["part_budget_bytes"] = budget
    eff = store.eff_read_bw()
    if eff:
        kw["read_bw"] = eff
    if not kw:
        return base
    kw["name"] = base.name + "-tuned"
    return dataclasses.replace(base, **kw)


def tuned_hardware(base):
    """Non-measuring variant for ``model.default_hardware()``: ``base``
    with tuned feedback iff sweep results are cached, else ``base``
    unchanged — importing the model never triggers a sweep."""
    store = cached_store()
    return base if store is None else apply_hardware(store, base)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="empirical per-backend kernel autotuner; winners "
                    "cached next to the calibration")
    ap.add_argument("--retune", action="store_true",
                    help="re-measure even if a tuning cache exists")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweep grid (CI smoke)")
    ap.add_argument("--json", metavar="OUTDIR",
                    help="also write OUTDIR/TUNINGS.json")
    args = ap.parse_args(argv)
    grid = SMOKE_GRID if args.smoke else None
    tunings = None if args.retune else load_cached()
    source = "cached"
    if tunings is None:
        tunings = measure(grid=grid)
        save(tunings)
        source = "measured"
    print(f"backend={tunings.backend} fingerprint={tunings.fingerprint} "
          f"({source}; cache={cache_path()})")
    for key in sorted(tunings.configs):
        c = tunings.configs[key]
        knobs = [f"tile={c.tile}"]
        if c.r is not None:
            knobs.append(f"r={c.r}")
        if c.part_bits is not None:
            knobs.append(f"bits={c.part_bits} "
                         f"budget={c.part_budget_bytes}B")
        eff = f" eff_bw={c.eff_bw / 1e9:.2f}GB/s" if c.eff_bw else ""
        print(f"{key:24s} {' '.join(knobs):32s} "
              f"{c.best_us:10.1f}us  ({c.speedup:.2f}x default{eff})")
    if args.json:
        os.makedirs(args.json, exist_ok=True)
        out = os.path.join(args.json, "TUNINGS.json")
        with open(out, "w") as f:
            json.dump(tunings.to_json(), f, indent=1)
        print(f"# wrote {out}")


if __name__ == "__main__":
    main()
