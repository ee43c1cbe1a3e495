"""Chip smoke test: the SSB serving path end to end on a TPU.

    python chip_smoke.py                  # one chip: three phases at SF-10
    python chip_smoke.py --chips 4        # four chips: the sharded phase only

One process, no children.  It generates a packed Star Schema Benchmark
database at ``--sf`` from ``--seed`` (``ssb.generate_packed``), holds its
whole fact table on the device (the morsel budget is the table's packed
size, so every scan is one resident morsel), and drives the 13 SSB
queries through the user's entry points:

  fused   each query solo through ``QueryServer`` with strategy "fused"
  auto    each query solo with strategy "auto": whatever the cost model
          picks (fused, opat, part) runs on the chip too
  shared  all 13 at once through a ``ServingLoop`` with the queries as
          its warm pool, after ``prewarm()``: one shared wave

With ``--chips 4`` it runs only the 13 queries with strategy "sharded"
over a 4-device mesh, then solo "fused" on one device to compare with.

Every result is checked against the numpy oracle
(``engine.run_query_oracle``, at ``rtol=1e-5, atol=1e-3``) and must have
run once (``attempts == 1``), without error or fallback, with the
strategy requested; the server's retry, breaker, re-entry and pressure
counters must stay at zero.  Any failure, or a device that is not a TPU,
exits non-zero without the result line.  The last line of standard
output is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``.

JAX's compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, and ``.jax_cache/`` beside this file otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
RTOL, ATOL = 1e-5, 1e-3             # examples/ssb_analytics.py's comparison
# server counters that must stay zero: each one means a request did not
# run once, on the path it asked for
CLEAN_COUNTERS = ("retries", "breaker_skips", "wave_reentries",
                  "member_reentries", "pressure_events")
WAVE_BATCH = 16                     # pow2 bucket that holds all 13 queries
# SF-10 (60M fact rows), half the paper's SF-20: the whole run, cold
# compiles and the host-side oracle included, must end inside 1200 s on
# one v5e, and at SF-2 the XLA path already took most of 900 s before
# its gathers were cut (PERF.md)
DEFAULT_SF = 10.0


def log(*parts) -> None:
    print(*parts, flush=True)


def max_rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0),
                        initial=0.0))


def generate(sf: float, seed: int):
    from repro.sql import ssb
    t0 = time.perf_counter()
    db = ssb.generate_packed(sf, seed=seed)
    log(f"generate sf={sf} seed={seed} fact_rows={db.lineorder.n_rows} "
        f"packed_fact_bytes={db.lineorder.nbytes} "
        f"seconds={time.perf_counter() - t0}")
    return db


def oracles(db, queries):
    """The oracle's answer to every query, computed once: over a decoded
    copy of the fact table (the packed one decodes a column per access)
    and on a few threads (numpy's loops release the GIL), as many as
    keep the oracle's int64/float64 temporaries, ~48 B per fact row
    each, near 16 GB."""
    import dataclasses
    from concurrent.futures import ThreadPoolExecutor

    from repro.sql import engine, ssb
    t0 = time.perf_counter()
    fact = db.lineorder
    plain = dataclasses.replace(db, lineorder=ssb.Table(
        fact.name, {c: fact[c] for c in fact.columns}))
    workers = max(1, min(os.cpu_count() or 1, 8,
                         int(16e9 // (48 * max(fact.n_rows, 1)))))
    with ThreadPoolExecutor(workers) as pool:
        want = dict(zip(queries, pool.map(
            lambda plan: engine.run_query_oracle(plain, plan),
            queries.values())))
    log(f"oracle queries={len(want)} threads={workers} "
        f"seconds={time.perf_counter() - t0}")
    return want


def check(phase: str, name: str, r, want, ok_strategy, failures) -> None:
    """Validate one QueryResult, log its line, record what failed."""
    bad = []
    if r.error is not None:
        bad.append(f"error={r.error}")
    if r.attempts != 1:
        bad.append(f"attempts={r.attempts}")
    if r.fallback_reason is not None:
        bad.append(f"fallback={r.fallback_reason}")
    if not ok_strategy(r):
        bad.append(f"strategy={r.strategy}")
    err = None
    if r.result is not None:
        err = max_rel_err(r.result, want)
        if not np.allclose(r.result, want, rtol=RTOL, atol=ATOL):
            bad.append(f"mismatch max_rel_err={err}")
    elif r.error is None:
        bad.append("no result")
    impls = ",".join(f"{fam}:{cfg.get('impl')}"
                     for fam, cfg in sorted((r.launch_config or {}).items()))
    log(f"phase={phase} query={name} ran={r.strategy} latency_s={r.latency_s} "
        f"impl={impls or '-'} max_rel_err={err}"
        + ("" if not bad else " FAIL " + " ".join(bad)))
    failures.extend(f"{phase}/{name}: {b}" for b in bad)


def check_counters(phase: str, server, failures) -> None:
    dirty = {k: server.stats[k] for k in CLEAN_COUNTERS if server.stats[k]}
    if dirty:
        failures.append(f"{phase}: server counters {dirty}")


def solo_phase(server, queries, want, strategy: str, ok_strategy,
               failures, warm: bool = True) -> dict:
    """Each query alone (one request per ``run()``, so ``auto`` never
    forms a wave): a cold pass that compiles, then (``warm``) the warm
    pass that is checked and reported.  Returns the checked results."""
    def one_pass():
        out = {}
        for name, plan in queries.items():
            server.submit(plan, strategy=strategy)
            (r,) = server.run().values()
            out[name] = r
        return out

    t0 = time.perf_counter()
    results = one_pass()
    cold = time.perf_counter() - t0
    if warm:
        t0 = time.perf_counter()
        results = one_pass()
        warm_s = time.perf_counter() - t0
    for name, r in results.items():
        check(strategy, name, r, want[name], ok_strategy, failures)
    check_counters(strategy, server, failures)
    log(f"phase={strategy} cold_pass_s={cold}"
        + (f" warm_pass_s={warm_s} compile_s~={cold - warm_s}"
           if warm else ""))
    return results


def shared_phase(db, queries, want, morsel_bytes: int, failures) -> None:
    """All 13 queries submitted at once to a pool-anchored ServingLoop."""
    from repro.sql.serving import ServingLoop
    plans = list(queries.values())
    loop = ServingLoop(db, mode="auto", max_batch=WAVE_BATCH,
                       warm_pool=plans, morsel_bytes=morsel_bytes)
    t0 = time.perf_counter()
    buckets = loop.prewarm()
    cold = time.perf_counter() - t0
    with loop:
        tickets = loop.submit_many(plans, strategy="shared")
        results = [t.wait(timeout=900) for t in tickets]
    for name, r in zip(queries, results):
        check("shared", name, r, want[name],
              lambda r: (r.strategy == "shared"
                         and r.shared_wave_size == len(plans)), failures)
    check_counters("shared", loop.server, failures)
    log(f"phase=shared prewarm_buckets={buckets} cold_pass_s={cold} "
        f"wave_s={results[0].latency_s} "
        f"wave_sizes={sorted({r.shared_wave_size for r in results})}")


def memory(label: str) -> None:
    import jax
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"memory {label} device={d.id} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')} "
            f"bytes_in_use={stats.get('bytes_in_use', 'n/a')}")


def run_one_chip(sf: float, seed: int) -> list:
    from repro.sql import engine
    from repro.sql.server import QueryServer
    failures: list = []
    db = generate(sf, seed)
    queries = engine.ssb_queries()
    want = oracles(db, queries)
    morsel_bytes = db.lineorder.nbytes
    server = QueryServer(db, mode="auto", morsel_bytes=morsel_bytes)
    solo_phase(server, queries, want, "fused",
               lambda r: r.strategy == "fused", failures)
    memory("after fused")
    solo_phase(server, queries, want, "auto",
               lambda r: (r.strategy == r.model_choice
                          and r.strategy in ("fused", "opat", "part",
                                             "sharded")), failures)
    memory("after auto")
    shared_phase(db, queries, want, morsel_bytes, failures)
    memory("after shared")
    return failures


def run_four_chips(sf: float, seed: int, n_chips: int) -> list:
    from repro.sql import engine
    from repro.sql import shard as SH
    from repro.sql.server import QueryServer
    failures: list = []
    db = generate(sf, seed)
    queries = engine.ssb_queries()
    want = oracles(db, queries)
    morsel_bytes = db.lineorder.nbytes
    sdb = SH.shard_database(db, n_chips)
    if sdb.mesh is None:
        return [f"no {n_chips}-device mesh"]
    sharded = solo_phase(
        QueryServer(sdb, mode="auto", morsel_bytes=morsel_bytes), queries,
        want, "sharded",
        lambda r: r.strategy == "sharded" and r.device_count == n_chips,
        failures)
    memory("after sharded")
    fused = solo_phase(QueryServer(db, mode="auto", morsel_bytes=morsel_bytes),
                       queries, want, "fused",
                       lambda r: r.strategy == "fused", failures, warm=False)
    memory("after fused")
    for name in queries:
        a, b = sharded[name].result, fused[name].result
        if a is None or b is None:
            continue
        same = bool(np.array_equal(a, b))
        log(f"compare query={name} sharded_vs_fused_max_rel_err="
            f"{max_rel_err(a, b)} identical={same}")
        if not np.allclose(a, b, rtol=RTOL, atol=ATOL):
            failures.append(f"compare/{name}: sharded != fused")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sf", type=float, default=DEFAULT_SF)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s); JAX sees "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"cache={jax.config.jax_compilation_cache_dir}")

    t0 = time.perf_counter()
    if args.chips == 1:
        failures = run_one_chip(args.sf, args.seed)
    else:
        failures = run_four_chips(args.sf, args.seed, args.chips)
    log(f"total_s={time.perf_counter() - t0} failures={len(failures)}")
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
