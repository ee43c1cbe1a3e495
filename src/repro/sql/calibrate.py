"""Measured-bandwidth calibration for the cost model.

``sql/model.py`` used to predict with hard-coded ``HOST`` constants — a
guess at whatever machine the container runs on.  The measured-vs-modeled
gaps that matter for strategy selection come from exactly that
mis-calibration plus unpriced dispatch overheads, so this module measures
the four quantities the model actually consumes, *on the current
backend*, with the paper's own microbenchmark shapes:

  read_bw   — streaming reduction over a DRAM-resident array (the
              paper's scan bound: one pass, read-only)
  write_bw  — streaming triad ``a + 2b -> y`` with the read time
              subtracted at the measured ``read_bw``
  cache_bw  — random gather against a cache-resident table, priced per
              line like the model's probe term (§4.3 step function)
  launch_overhead_s — one tiny jitted dispatch, timed round-trip: the
              per-launch cost that multiplies by 2^bits in a
              partition-at-a-time probe loop
  interconnect_bw — a ``psum`` all-reduce over every visible device
              (ring volume: ``2(D-1)/D`` of the payload per hop, per
              device), the rate ``model._shard_reduce_time`` prices
              sharded tree-reduction at; None on single-device hosts

Results are cached to disk (JSON, keyed by backend) so calibration runs
once per machine, not per process: ``model.default_hardware()`` picks the
cached calibration up for free, and ``benchmarks/run.py fig8`` /
``python -m repro.sql.calibrate`` refresh it explicitly.

    PYTHONPATH=src python -m repro.sql.calibrate            # print
    PYTHONPATH=src python -m repro.sql.calibrate --json out # + artifact
    PYTHONPATH=src python -m repro.sql.calibrate --refresh  # re-measure
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.cost.model import Hardware

# sizes chosen so the host run finishes in ~a second: the stream array
# dwarfs any L3 (model-relevant regime), the gather table sits well
# inside it
STREAM_ELEMS = 1 << 24          # 64 MB of f32 — DRAM-resident
GATHER_TABLE_ELEMS = 1 << 14    # 64 KB — cache-resident
GATHER_PROBES = 1 << 21


@dataclass(frozen=True)
class Calibration:
    backend: str
    read_bw: float              # B/s
    write_bw: float
    cache_bw: float
    launch_overhead_s: float
    measured_at: float          # unix time
    interconnect_bw: Optional[float] = None     # B/s; None if 1 device

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: dict) -> "Calibration":
        fields = {f.name for f in dataclasses.fields(Calibration)}
        return Calibration(**{k: v for k, v in d.items() if k in fields})


def _bench(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median-free best-effort seconds/call (min over iters: bandwidth
    microbenchmarks want the unperturbed run, not the scheduler noise)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def measure(stream_elems: int = STREAM_ELEMS,
            table_elems: int = GATHER_TABLE_ELEMS,
            probes: int = GATHER_PROBES,
            line_bytes: int = 64) -> Calibration:
    """Run the microbenchmarks on the current jax backend."""
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (stream_elems,), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1),
                          (stream_elems,), jnp.float32)
    w = 4

    # read: one streaming pass, scalar out (no write traffic to speak of)
    t_read = _bench(jax.jit(jnp.sum), a)
    read_bw = w * stream_elems / t_read

    # triad: reads 2 columns, writes 1 -> solve for write_bw given read_bw.
    # Proportional floor on the residual: if the read-time estimate
    # swallows the whole triad (read_bw underestimated by the reduction
    # benchmark), write_bw saturates at ~10x the triad rate instead of
    # exploding to a nonsense value that would zero the model's write
    # terms.
    t_triad = _bench(jax.jit(lambda x, y: x + 2.0 * y), a, b)
    write_s = max(t_triad - 2 * w * stream_elems / read_bw, t_triad * 0.1)
    write_bw = w * stream_elems / write_s

    # random gather against a cache-resident table, priced per line like
    # the model's probe term
    table = jnp.arange(table_elems, dtype=jnp.int32)
    idx = jax.random.randint(jax.random.fold_in(key, 2), (probes,),
                             0, table_elems, jnp.int32)
    t_gather = _bench(jax.jit(lambda t, i: t[i]), table, idx)
    # subtract the streaming traffic of idx-in + gathered-out at the
    # measured stream rates; the remainder is the random-access term
    stream_s = w * probes * (1 / read_bw + 1 / write_bw)
    cache_bw = probes * line_bytes / max(t_gather - stream_s,
                                         t_gather * 0.1)

    # dispatch overhead: a tiny jitted op, timed round-trip per call
    tiny = jnp.zeros((8,), jnp.int32)
    t_launch = _bench(jax.jit(lambda x: x + 1), tiny, warmup=4, iters=20)

    return Calibration(backend=jax.default_backend(),
                       read_bw=float(read_bw), write_bw=float(write_bw),
                       cache_bw=float(cache_bw),
                       launch_overhead_s=float(t_launch),
                       measured_at=time.time(),
                       interconnect_bw=_measure_interconnect())


def _measure_interconnect(elems: int = 1 << 20) -> Optional[float]:
    """All-reduce microbenchmark: ``psum`` a per-device f32 payload over
    every visible device and price the ring volume — each device sends
    and receives ``(D-1)/D`` of the payload per direction, so the moved
    bytes are ``2(D-1) * elems * 4``.  None on single-device hosts (no
    interconnect to measure; the model then falls back to read_bw, which
    matches the host-loop merge actually taking that path)."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec
    devs = jax.devices()
    if len(devs) < 2:
        return None
    d = len(devs)
    mesh = Mesh(np.array(devs), ("data",))
    x = jnp.ones((d, elems), jnp.float32)
    f = jax.jit(jax.shard_map(lambda y: jax.lax.psum(y, "data"), mesh=mesh,
                              in_specs=PartitionSpec("data", None),
                              out_specs=PartitionSpec(None, None)))
    t = _bench(f, x)
    return float(2.0 * (d - 1) * elems * 4 / t)


# ---------------------------------------------------------------------------
# disk cache
# ---------------------------------------------------------------------------


def cache_dir() -> str:
    """Directory holding calibration + tuning caches.  Overridable for
    tests/CI via ``REPRO_CALIB_CACHE``."""
    return os.environ.get("REPRO_CALIB_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "repro")


def backend_fingerprint(backend: Optional[str] = None) -> str:
    """Cache-key suffix identifying what was measured: backend name +
    jax version + device kind.  A driver/library upgrade or a different
    accelerator model changes the fingerprint, so stale measurements are
    re-taken instead of silently served (the old flat
    ``calibration-{backend}.json`` key collided across all of those)."""
    import re
    backend = backend or jax.default_backend()
    kind = jax.devices()[0].device_kind if jax.devices() else "unknown"
    kind = re.sub(r"[^A-Za-z0-9._-]+", "-", kind).strip("-").lower()
    return f"{backend}-jax{jax.__version__}-{kind}"


def cache_path(backend: Optional[str] = None) -> str:
    """Per-(backend, jax version, device kind) calibration cache file.
    Overridable for tests/CI via ``REPRO_CALIB_CACHE`` (a directory)."""
    return os.path.join(cache_dir(),
                        f"calibration-{backend_fingerprint(backend)}.json")


# in-process memo over the disk cache: ``model.default_hardware()`` sits
# on the per-query auto path, so the JSON must not be re-read per query.
# ``save`` keeps it coherent; a path is memoized even when absent (tests
# point REPRO_CALIB_CACHE at a fresh dir per scenario).
_MEMO: dict = {}


def save(calib: Calibration) -> str:
    path = cache_path(calib.backend)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(calib.to_json(), f, indent=1)
    _MEMO[path] = calib
    return path


def load_cached(backend: Optional[str] = None) -> Optional[Calibration]:
    """Load the per-backend calibration, or None when there is none.

    A corrupted or truncated cache file (torn write, wrong schema, junk
    bytes) must never poison the process: it is detected, logged,
    *removed from disk*, and reported as no-cache — so the caller simply
    re-measures and writes a fresh file."""
    path = cache_path(backend)
    if path in _MEMO:
        return _MEMO[path]
    calib = None
    if os.path.exists(path):
        try:
            with open(path) as f:
                calib = Calibration.from_json(json.load(f))
        except (ValueError, TypeError, KeyError, AttributeError,
                OSError) as e:
            # ValueError covers JSONDecodeError (truncated/garbled
            # files); TypeError missing required fields; AttributeError
            # valid-JSON-wrong-shape (e.g. a bare number)
            logging.getLogger(__name__).warning(
                "discarding corrupt calibration cache %s (%s: %s); "
                "will re-measure", path, type(e).__name__, e)
            calib = None
            try:
                os.remove(path)         # torn file must not shadow a
            except OSError:             # future good write
                pass
    _MEMO[path] = calib
    return calib


# ---------------------------------------------------------------------------
# Hardware integration
# ---------------------------------------------------------------------------


def apply(calib: Calibration, base: Hardware) -> Hardware:
    """``base`` with its bandwidths replaced by the measured ones.
    Geometry (cache size, line bytes, capacity) stays from the base
    description — the microbenchmarks measure *rates*, not topology.
    The interconnect rate only overrides when it was measurable (>= 2
    devices); otherwise the base description's value survives."""
    kw = dict(name=base.name + "-calibrated",
              read_bw=calib.read_bw, write_bw=calib.write_bw,
              cache_bw=calib.cache_bw,
              launch_overhead_s=calib.launch_overhead_s)
    if calib.interconnect_bw:
        kw["interconnect_bw"] = calib.interconnect_bw
    return dataclasses.replace(base, **kw)


def calibrated_hardware(base: Hardware,
                        refresh: bool = False) -> Hardware:
    """Measure (or load the cached measurement) and fold into ``base``.
    This is the entry point ``benchmarks/run.py fig8`` uses."""
    calib = None if refresh else load_cached()
    if calib is None:
        calib = measure()
        save(calib)
    return apply(calib, base)


def cached_hardware(base: Hardware) -> Optional[Hardware]:
    """Non-measuring variant for ``model.default_hardware()``: returns
    the calibrated Hardware iff a disk cache exists, else None — so
    importing the model never triggers a multi-second microbenchmark."""
    calib = load_cached()
    return None if calib is None else apply(calib, base)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="measure memory bandwidths + launch overhead for the "
                    "cost model; results cached per backend")
    ap.add_argument("--refresh", action="store_true",
                    help="re-measure even if a cache exists")
    ap.add_argument("--json", metavar="OUTDIR",
                    help="also write OUTDIR/CALIBRATION.json")
    args = ap.parse_args(argv)
    calib = None if args.refresh else load_cached()
    source = "cached"
    if calib is None:
        calib = measure()
        save(calib)
        source = "measured"
    print(f"backend={calib.backend} ({source}; cache={cache_path()})")
    print(f"read_bw={calib.read_bw / 1e9:.2f} GB/s")
    print(f"write_bw={calib.write_bw / 1e9:.2f} GB/s")
    print(f"cache_bw={calib.cache_bw / 1e9:.2f} GB/s")
    print(f"launch_overhead={calib.launch_overhead_s * 1e6:.2f} us")
    if calib.interconnect_bw:
        print(f"interconnect_bw={calib.interconnect_bw / 1e9:.2f} GB/s "
              f"(all-reduce over {jax.device_count()} devices)")
    else:
        print("interconnect_bw=n/a (single device)")
    if args.json:
        os.makedirs(args.json, exist_ok=True)
        out = os.path.join(args.json, "CALIBRATION.json")
        with open(out, "w") as f:
            json.dump(calib.to_json(), f, indent=1)
        print(f"# wrote {out}")


if __name__ == "__main__":
    main()
