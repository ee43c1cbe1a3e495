"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Empirical timings are the
XLA-compiled jnp path on the host CPU (this container); `derived` carries
the model prediction(s) — paper-CPU / paper-GPU / TPU-v5e — so every row
pairs a measurement with the bandwidth-saturation model the paper uses.

  PYTHONPATH=src python -m benchmarks.run             # all tables
  PYTHONPATH=src python -m benchmarks.run fig12 fig16 # subset
  PYTHONPATH=src python -m benchmarks.run --json out fig17
      # override the JSON destination (default: bench_out/)

Every run also writes one ``BENCH_<table>.json`` per table into
``bench_out/`` (gitignored) so the perf trajectory is recorded for every
table consistently, not only the ones CI happens to pass ``--json`` to;
``--json DIR`` overrides the destination, ``--no-json`` disables it.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cost import model as M
from repro.kernels import ref
from repro.sql import calibrate as CAL
from repro.sql import compile as C
from repro.sql import engine, ssb
from repro.sql import model as SM
from repro.sql.compile import compile_plan
from repro.sql.hashtable import HashTableCache
from repro.sql.plan import ColExpr, QueryBuilder

ROWS = []


def emit(name: str, us: float, derived: str = "", extra: dict = None):
    """``extra`` rides into the JSON record only (machine-readable
    attribution — launch counts, partition geometry — that would bloat
    the CSV line)."""
    ROWS.append((name, us, derived, extra))
    print(f"{name},{us:.2f},{derived}")


def timeit(fn, *args, warmup=2, iters=5) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


# ---------------------------------------------------------------------------


def fig3_coprocessor():
    """Fig. 3: coprocessor model vs CPU — model-based, SF20 Q1.1."""
    n = 120_000_000
    cop = M.coprocessor_time(4 * 4 * n) * 1e6
    cpu = M.q1_time(n, M.PAPER_CPU) * 1e6
    gpu = M.q1_time(n, M.PAPER_GPU) * 1e6
    emit("fig3.q1_coprocessor_model", cop, "PCIe-bound")
    emit("fig3.q1_cpu_model", cpu,
         f"coprocessor_loses={cop > cpu}")
    emit("fig3.q1_gpu_resident_model", gpu,
         f"resident_speedup_vs_cpu={cpu / gpu:.1f}x")


def _fig8_db(n_fact: int, n_dim: int, seed: int = 0) -> ssb.Database:
    """Synthetic star join: fact FK uniform over a dim of n_dim rows."""
    rng = np.random.default_rng(seed)
    i32 = np.int32
    fact = ssb.Table("lineorder", {
        "lo_partkey": rng.integers(0, n_dim, n_fact, dtype=i32),
        "lo_revenue": rng.integers(1, 1000, n_fact, dtype=i32)})
    dim = ssb.Table("part", {
        "p_partkey": np.arange(n_dim, dtype=i32),
        "p_group": (np.arange(n_dim, dtype=i32) % 64)})
    stub = ssb.Table("stub", {"x": np.zeros(1, i32)})
    return ssb.Database(fact, stub, stub, stub, dim, sf=0.0)


def fig8_partitioned_join(n_fact: int = 1 << 21):
    """Fig. 8: join strategy vs build-side cardinality.  One FK join probed
    through each physical strategy (fused / opat / part / part_loop) as
    the dim table grows past the cache, paired with the bandwidth cost
    model's predicted seconds for the *calibrated* measuring host — the
    paper's claim is that the model picks the right strategy, so every
    row reports whether the predicted ranking matches the measured one
    (`auto` executes that prediction).

    ``part`` is the fused single-launch probe, ``part_loop`` the host
    partition-at-a-time baseline it replaced; per-strategy launch counts
    and the partition geometry ride into the JSON record so the
    fused-vs-loop win is attributable to dispatches, not noise."""
    plan = (QueryBuilder("fig8").scan("lineorder")
            .hash_join("lo_partkey", "part", "p_partkey",
                       payload=ColExpr("p_group"), mult=1)
            .measure("lo_revenue").group_by(64).build())
    # measure (or load) this backend's bandwidths + launch overhead; the
    # execute path's part_bits sizing reads the same calibration cache
    hw = CAL.calibrated_hardware(SM.base_hardware())
    strategies = ("fused", "opat", "part", "part_loop")
    for log_dim in (12, 16, 20, 22):
        db = _fig8_db(n_fact, 1 << log_dim)
        bits = SM.part_bits(1 << log_dim, hw)
        measured, launches = {}, {}
        for strat in strategies:
            cache = HashTableCache()        # warmup builds; timed = probes
            cq = compile_plan(plan, strat)
            warmup, iters = 1, 2
            C.reset_launch_stats()
            measured[strat] = timeit(
                lambda cq=cq, cache=cache, db=db: cq.execute(
                    db, mode="ref", cache=cache),
                warmup=warmup, iters=iters)
            launches[strat] = {k: v // (warmup + iters)
                               for k, v in C.LAUNCH_STATS.items()}
        preds = SM.predict(plan, db, hw)
        meas_rank = sorted(measured, key=measured.get)
        pred_rank = sorted(preds, key=preds.get)
        fused_win = measured["part_loop"] / measured["part"]
        emit(f"fig8.join_dim2e{log_dim}", measured[meas_rank[0]],
             ";".join(f"{s}_us={measured[s]:.0f}" for s in sorted(measured))
             + ";" + ";".join(f"model_{s}_us={preds[s] * 1e6:.0f}"
                              for s in sorted(preds))
             + f";part_bits={bits};n_parts={1 << bits}"
             + f";probe_launches_part={launches['part']['probe']}"
             + f";probe_launches_loop={launches['part_loop']['probe']}"
             + f";fused_vs_loop={fused_win:.2f}x"
             + f";measured_best={meas_rank[0]};model_best={pred_rank[0]}"
             + f";ranking_match={meas_rank == pred_rank}",
             extra={
                 "n_fact": n_fact, "n_dim": 1 << log_dim,
                 "part_bits": bits, "n_parts": 1 << bits,
                 "measured_us": {s: measured[s] for s in strategies},
                 "model_us": {s: preds[s] * 1e6 for s in preds},
                 "launches_per_call": launches,
                 "fused_vs_loop": fused_win,
                 "hardware": {"name": hw.name, "read_bw": hw.read_bw,
                              "write_bw": hw.write_bw,
                              "cache_bw": hw.cache_bw,
                              "launch_overhead_s": hw.launch_overhead_s},
                 "ranking_match": meas_rank == pred_rank,
             })


def fig9_tile_sweep():
    """Fig. 9: tile-size sweep.  Without hardware, report the structural
    quantities that drive the figure: VMEM working set per tile and the
    grid-step count (DMA efficiency), plus the paper's best config."""
    n = 1 << 20
    for tile in (256, 512, 1024, 2048, 4096, 8192):
        vmem = 2 * tile * 4  # x + compacted tile double-buffered
        steps = n // tile
        emit(f"fig9.tile_{tile}", 0.0,
             f"vmem_bytes={vmem};grid_steps={steps};"
             f"items_per_lane={tile // 128};paper_best=2048")


def fig10_project():
    """Fig. 10: Q1/Q2 projection, measured + models."""
    n = 1 << 24
    k = jax.random.PRNGKey(0)
    x1 = jax.random.normal(k, (n,), jnp.float32)
    x2 = jax.random.normal(jax.random.fold_in(k, 1), (n,), jnp.float32)
    f_q1 = jax.jit(lambda a, b: ref.project(a, b, 2.0, 3.0, False))
    f_q2 = jax.jit(lambda a, b: ref.project(a, b, 2.0, 3.0, True))
    for name, fn in (("q1_linear", f_q1), ("q2_sigmoid", f_q2)):
        us = timeit(fn, x1, x2)
        mc = M.project_time(n, M.PAPER_CPU) * 1e6
        mg = M.project_time(n, M.PAPER_GPU) * 1e6
        mt = M.project_time(n, M.TPU_V5E) * 1e6
        emit(f"fig10.{name}", us,
             f"model_cpu={mc:.0f};model_gpu={mg:.0f};model_tpu={mt:.0f};"
             f"gpu_speedup={mc / mg:.1f}x")


def fig12_select():
    """Fig. 12: selection scan over selectivity 0..1."""
    n = 1 << 24
    k = jax.random.PRNGKey(0)
    x = jax.random.uniform(k, (n,), jnp.float32)
    y = jax.random.normal(jax.random.fold_in(k, 1), (n,), jnp.float32)
    fn = jax.jit(lambda x, y, v: ref.select_scan(x, y, -1.0, v)[0])
    for sel in (0.1, 0.5, 0.9):
        us = timeit(fn, x, y, jnp.float32(sel))
        mc = M.select_time(n, sel, M.PAPER_CPU) * 1e6
        mg = M.select_time(n, sel, M.PAPER_GPU) * 1e6
        mt = M.select_time(n, sel, M.TPU_V5E) * 1e6
        emit(f"fig12.select_sel{sel}", us,
             f"model_cpu={mc:.0f};model_gpu={mg:.0f};model_tpu={mt:.0f};"
             f"gpu_speedup={mc / mg:.1f}x")


def fig13_join():
    """Fig. 13: probe vs hash-table size (cache step function)."""
    n_probe = 1 << 22
    k = jax.random.PRNGKey(0)
    vals = jax.random.randint(jax.random.fold_in(k, 1), (n_probe,), 0, 100,
                              jnp.int32)
    fn = jax.jit(ref.probe_agg)
    for ht_kb in (8, 256, 4096, 65536):
        n_build = max(16, ht_kb * 1024 // 8 // 2)  # 50% fill
        n_slots = engine.next_pow2(n_build)
        htk, htv = engine.np_build(np.arange(n_build, dtype=np.int32),
                                   np.arange(n_build, dtype=np.int32),
                                   n_slots)
        probe = jax.random.randint(k, (n_probe,), 0, n_build, jnp.int32)
        us = timeit(fn, probe, vals, jnp.asarray(htk), jnp.asarray(htv),
                    iters=3)
        ht_bytes = ht_kb * 1024.0
        mc = M.join_probe_time(n_probe, ht_bytes, M.PAPER_CPU) * 1e6
        mg = M.join_probe_time(n_probe, ht_bytes, M.PAPER_GPU) * 1e6
        mt = M.join_probe_time(n_probe, ht_bytes, M.TPU_V5E) * 1e6
        emit(f"fig13.join_ht{ht_kb}kb", us,
             f"model_cpu={mc:.0f};model_gpu={mg:.0f};model_tpu={mt:.0f};"
             f"gpu_speedup={mc / mg:.1f}x")


def fig14_radix():
    """Fig. 14: radix partition passes (stable oracle measured + model)."""
    n = 1 << 22
    k = jax.random.PRNGKey(0)
    keys = jax.random.randint(k, (n,), 0, 2**31 - 1, jnp.int32)
    vals = jnp.arange(n, dtype=jnp.int32)
    for r in (4, 6, 8):
        fn = jax.jit(lambda kk, vv, r=r: ref.partition(kk, vv, 0, r))
        us = timeit(fn, keys, vals, iters=3)
        mc = M.radix_pass_time(n, M.PAPER_CPU) * 1e6
        mg = M.radix_pass_time(n, M.PAPER_GPU) * 1e6
        mt = M.radix_pass_time(n, M.TPU_V5E) * 1e6
        emit(f"fig14.partition_r{r}", us,
             f"model_cpu={mc:.0f};model_gpu={mg:.0f};model_tpu={mt:.0f}")
    mc32 = M.sort_time(1 << 28, M.PAPER_CPU) * 1e6
    mg32 = M.sort_time(1 << 28, M.PAPER_GPU) * 1e6
    emit("fig14.sort_2e28_model", 0.0,
         f"model_cpu={mc32:.0f};model_gpu={mg32:.0f};"
         f"speedup={mc32 / mg32:.1f}x;paper_measured=17.13x")


def ssb_model_time(name: str, db, hw) -> float:
    """Paper cost-model prediction (seconds) for one SSB query: flight 1
    is the 4-column scan bound; the join flights reuse the §5.3 q2.1
    three-term model (the paper's representative full query)."""
    n_lo = db.lineorder.n_rows
    if name.startswith("q1"):
        return M.q1_time(n_lo, hw)
    part_ht = 2 * 4 * db.part.n_rows / 25 * 2.0
    return M.q21_time(n_lo, db.supplier.n_rows, 2556, part_ht, hw)


def fig16_ssb(sf: float = 0.05):
    """Fig. 16: full SSB, crystal pipeline (ref path) measured + models."""
    db = ssb.generate(sf=sf, seed=7)
    qs = engine.ssb_queries()
    for name, spec in qs.items():
        us = timeit(lambda spec=spec: engine.run_query(db, spec, mode="ref"),
                    warmup=1, iters=3)
        mg = ssb_model_time(name, db, M.PAPER_GPU) * 1e6
        mt = ssb_model_time(name, db, M.TPU_V5E) * 1e6
        mc = ssb_model_time(name, db, M.PAPER_CPU) * 1e6
        emit(f"fig16.{name}", us,
             f"model_cpu={mc:.0f};model_gpu={mg:.0f};model_tpu={mt:.0f};"
             f"gpu_speedup={mc / mg:.1f}x")


def fig17_fusion(sf: float = 0.05):
    """Fig. 17 (repo extension of the paper's §5.3 argument): fused vs.
    operator-at-a-time lowering of every SSB query.  The fused plan makes
    one pass over the fact table; opat emits a selection vector per
    operator and re-materializes the live columns through it.

    Two readings per row: the *measured* host ratio (cache-resident
    intermediates, so selective queries can favor opat — work-skipping
    beats fusion when materialization is nearly free), and the paper's
    bandwidth model on the V100, where every intermediate is an HBM
    round-trip (upper bound: full fact cardinality per operator) — the
    regime where fusion-beats-materialization is the headline."""
    db = ssb.generate(sf=sf, seed=7)
    n_lo = db.lineorder.n_rows
    qs = engine.ssb_queries()
    # shared dim-table cache: the warmup iteration builds, so the timed
    # region is the scan path only — the host-side build would otherwise
    # inflate both sides and bias the ratio toward 1
    cache = engine.HashTableCache()
    for name, plan in qs.items():
        fused = compile_plan(plan, "fused")
        opat = compile_plan(plan, "opat")
        us_f = timeit(lambda f=fused: f.execute(db, mode="ref",
                                                cache=cache),
                      warmup=1, iters=3)
        us_o = timeit(lambda o=opat: o.execute(db, mode="ref",
                                               cache=cache),
                      warmup=1, iters=3)
        hw = M.PAPER_GPU
        base = ssb_model_time(name, db, hw)
        n_ops = len(plan.filters) + len(plan.joins)
        live_cols = 2                    # row ids + running group id
        mat = n_ops * live_cols * (4 * n_lo / hw.write_bw
                                   + 4 * n_lo / hw.read_bw)
        emit(f"fig17.{name}", us_f,
             f"opat_us={us_o:.2f};fusion_speedup={us_o / us_f:.2f}x;"
             f"model_gpu_fusion_speedup={(base + mat) / base:.2f}x;"
             f"n_joins={len(plan.joins)}")


def shared_throughput(sf: float = 0.02):
    """Wave-serving throughput: queries/sec vs concurrency, the shared
    single-pass wave (strategy ``shared``) against per-query solo fused
    execution — the serving analogue of the paper's fusion result.  At
    concurrency c the wave is the 13 SSB queries round-robin (so small
    waves are all-distinct and only c > 13 repeats a member); solo-fused
    streams the fact table once per QUERY, the shared wave once per WAVE
    with every deduplicated dim table probed once for all members.

    The JSON ``extra`` records wave occupancy, the model's bytes-moved
    ratio (union fact columns read once + deduplicated probe streams vs
    Σ per-query full scans), and the probe-stream dedup factor."""
    from repro.sql.server import QueryServer
    db = ssb.generate(sf=sf, seed=7)
    n = db.lineorder.n_rows
    qs = engine.ssb_queries()
    names = list(qs)
    max_batch = 16
    for conc in (1, 2, 4, 8, 16):
        batch = [qs[names[i % len(names)]] for i in range(conc)]

        def run_wave(strategy, batch=batch):
            server = QueryServer(db, mode="ref", max_batch=max_batch)
            iters, warmup = 3, 1
            for it in range(warmup + iters):
                if it == warmup:
                    t0 = time.perf_counter()
                for plan in batch:
                    server.submit(plan, strategy=strategy)
                results = server.run()
            dt = (time.perf_counter() - t0) / iters
            assert all(r.error is None for r in results.values())
            return dt, server, results

        dt_shared, sserver, sres = run_wave("shared")
        dt_solo, _, fres = run_wave("fused")
        for rid, r in sres.items():     # shared must match solo fused
            np.testing.assert_allclose(r.result, fres[rid].result,
                                       rtol=1e-5, atol=1e-3)
        qps_shared = conc / dt_shared
        qps_solo = conc / dt_solo
        # model bytes-moved: the wave's union streams (predicate / FK /
        # measure columns, deduplicated within their role exactly as the
        # kernel loads them — compile.shared_footprint is the single
        # owner of that rule) once per wave, vs Σ per-query full scans
        col_ix, join_nodes, mcol_ix = C.shared_footprint(batch)
        solo_bytes = sum(SM._scan_cols(p) * SM.W * n for p in batch)
        shared_bytes = (len(col_ix) + len(join_nodes)
                        + len(mcol_ix)) * SM.W * n
        n_solo_probes = sum(len(p.joins) for p in batch)
        occupancy = sserver.stats["occupancy"]
        emit(f"shared_throughput.c{conc}", dt_shared / conc * 1e6,
             f"qps_shared={qps_shared:.1f};qps_solo={qps_solo:.1f};"
             f"shared_speedup={qps_shared / qps_solo:.2f}x;"
             f"bytes_ratio={shared_bytes / solo_bytes:.2f};"
             f"probe_streams={len(join_nodes)}v{n_solo_probes};"
             f"wave_size={max(r.shared_wave_size or 0 for r in sres.values())}",
             extra={
                 "sf": sf, "n_fact": n, "concurrency": conc,
                 "qps_shared": qps_shared, "qps_solo": qps_solo,
                 "shared_speedup": qps_shared / qps_solo,
                 "wave_occupancy": occupancy,
                 "shared_wave_sizes": sorted(
                     {r.shared_wave_size for r in sres.values()}),
                 "bytes_moved_ratio": shared_bytes / solo_bytes,
                 "fact_bytes_shared": shared_bytes,
                 "fact_bytes_solo": solo_bytes,
                 "probe_streams_shared": len(join_nodes),
                 "probe_streams_solo": n_solo_probes,
             })


def compression(sf: float = 0.1):
    """Compressed storage (bit-pack / frame-of-reference, decode-on-scan):
    bytes-moved and measured speedup of every SSB query on a packed
    database vs the plain int32 one, both through the fused lowering.

    Three claims, each observable per row: (1) the packed fact table
    streams a fraction of the plain bytes (per-query ratio from the
    encoded-width cost model, whole-table ratio in the header row);
    (2) decode-on-scan turns that into measured wall-clock wins where
    the query is scan-bound (flight 1 — selection + aggregate over 5
    streams); join-heavy flights are probe-dominated on this host, so
    their ratio hovers near 1 (the honest result: compression shrinks
    the scan term only); (3) packed results are BIT-identical to plain
    (asserted here, not just eyeballed)."""
    from repro.sql import storage as ST
    db = ssb.generate(sf=sf, seed=7)
    pdb = ST.pack_database(db)
    lo = pdb.lineorder
    encs = {c: lo.encoding(c) for c in lo.columns}
    fact_ratio = lo.plain_nbytes / lo.nbytes
    emit("compression.lineorder", 0.0,
         f"plain_mb={lo.plain_nbytes / 1e6:.1f};"
         f"packed_mb={lo.nbytes / 1e6:.1f};bytes_ratio={fact_ratio:.2f}x;"
         + ";".join(f"{c}={e.kind}{e.phys}" for c, e in encs.items()),
         extra={
             "sf": sf, "n_fact": db.lineorder.n_rows,
             "plain_bytes": lo.plain_nbytes, "packed_bytes": lo.nbytes,
             "bytes_ratio": fact_ratio,
             "encodings": {c: {"kind": e.kind, "width": e.width,
                               "phys": e.phys, "ref": e.ref}
                           for c, e in encs.items()},
         })
    qs = engine.ssb_queries()
    cache_plain = HashTableCache()
    cache_packed = HashTableCache()
    for name, plan in qs.items():
        cq_plain = compile_plan(plan, "fused")
        cq_packed = compile_plan(plan, "fused")
        us_plain = timeit(lambda cq=cq_plain: cq.execute(
            db, mode="ref", cache=cache_plain), warmup=1, iters=3)
        us_packed = timeit(lambda cq=cq_packed: cq.execute(
            pdb, mode="ref", cache=cache_packed), warmup=1, iters=3)
        out_plain = cq_plain.execute(db, mode="ref", cache=cache_plain)
        out_packed = cq_packed.execute(pdb, mode="ref", cache=cache_packed)
        identical = bool(np.array_equal(out_plain, out_packed))
        assert identical, f"{name}: packed result diverged from plain"
        enc_bytes, plain_bytes = SM.scanned_bytes(plan, pdb.lineorder)
        emit(f"compression.{name}", us_packed,
             f"plain_us={us_plain:.0f};speedup={us_plain / us_packed:.2f}x;"
             f"bytes_ratio={plain_bytes / enc_bytes:.2f}x;"
             f"bit_identical={identical}",
             extra={
                 "us_plain": us_plain, "us_packed": us_packed,
                 "speedup": us_plain / us_packed,
                 "bytes_scanned_packed": enc_bytes,
                 "bytes_scanned_plain": plain_bytes,
                 "bytes_ratio": plain_bytes / enc_bytes,
                 "bit_identical": identical,
             })


def scaleout(sf: float = 0.02):
    """Scale-out: the 13 SSB queries sharded over 1/2/4/8 fact shards
    (``repro.sql.shard``), one row per shard count.  The paper's
    bandwidth argument extended to aggregate multi-chip bandwidth: N
    devices scanning disjoint shards deliver ~N x scan GB/s while only
    the (n_groups,) partial grids cross the interconnect.

    Two scan rates per row, honestly separated: ``agg_scan_gbps``
    divides the scanned bytes by Σ per-query max-shard time — the wall
    clock N *parallel* devices would see, the number that must grow
    toward N x (on this single-CPU host the shards run sequentially, so
    this is the as-if-parallel projection from per-shard timings);
    ``wall_scan_gbps`` divides by the actual host wall time (flat on one
    CPU — the honest single-device number).  ``auto``'s single- vs
    multi-device choice (``model.choose(..., n_shards=s)``) is logged
    per query.  Results are asserted bit-identical to the solo fused
    pass at every shard count before anything is reported."""
    from repro.sql import shard as SH
    from repro.sql.server import QueryServer
    db = ssb.generate(sf=sf, seed=7)
    n = db.lineorder.n_rows
    qs = engine.ssb_queries()
    solo_cache = HashTableCache()
    solo = {name: compile_plan(p, "fused").execute(db, mode="ref",
                                                   cache=solo_cache)
            for name, p in qs.items()}
    for s in (1, 2, 4, 8):
        sdb = SH.shard_database(db, s)
        server = QueryServer(sdb, mode="ref")
        warmup, iters = 1, 2
        best_wall = float("inf")
        best_shard = {}                 # per query: min-of-iters max-shard
        for it in range(warmup + iters):
            rids = {server.submit(p, strategy="sharded"): name
                    for name, p in qs.items()}
            t0 = time.perf_counter()
            results = server.run()
            wall = time.perf_counter() - t0
            for rid, r in results.items():
                assert r.error is None, f"{rids[rid]}: {r.error}"
                assert np.array_equal(r.result, solo[rids[rid]]), \
                    f"{rids[rid]}: sharded diverged from solo at S={s}"
                if it >= warmup:
                    t_q = max(r.shard_times_s)
                    name = rids[rid]
                    best_shard[name] = min(best_shard.get(name, t_q), t_q)
            if it >= warmup:
                best_wall = min(best_wall, wall)
        bytes_by_q = {rids[rid]: r.bytes_scanned
                      for rid, r in results.items()}
        total_bytes = sum(bytes_by_q.values())
        shard_times = {rids[rid]: r.shard_times_s
                       for rid, r in results.items()}
        agg_gbps = total_bytes / sum(best_shard.values()) / 1e9
        wall_gbps = total_bytes / best_wall / 1e9
        qps = len(qs) / best_wall
        choices = {name: SM.choose(p, db, n_shards=s).strategy
                   for name, p in qs.items()}
        n_multi = sum(1 for c in choices.values() if c == "sharded")
        emit(f"scaleout.d{s}", best_wall / len(qs) * 1e6,
             f"qps={qps:.1f};agg_scan_gbps={agg_gbps:.2f};"
             f"wall_scan_gbps={wall_gbps:.2f};"
             f"devices={jax.device_count()};"
             f"auto_sharded={n_multi}/{len(qs)}",
             extra={
                 "sf": sf, "n_fact": n, "n_shards": s,
                 "qps": qps, "agg_scan_gbps": agg_gbps,
                 "wall_scan_gbps": wall_gbps,
                 "bytes_scanned": total_bytes,
                 "shard_times_s": shard_times,
                 "auto_choice": choices,
                 "auto_sharded_queries": n_multi,
                 "bit_identical": True,
             })


def scaleup(sfs=None):
    """Out-of-core scale-up: the 13 SSB queries streamed through the
    bounded-memory morsel spine (``repro.sql.morsel``) at growing scale
    factors.  The packed database is built by the chunked streaming
    generator (``ssb.generate_packed`` — the full plain fact table is
    never materialized), and every query executes under a HARD per-morsel
    budget of a tenth of the packed fact table, so the double-buffered
    device residency is bounded at ~a fifth of the data whatever the SF.

    Three claims, asserted before anything is reported: (1) every query
    actually streams (``n_morsels > 1``); (2) the residency bound holds
    (``peak_resident_bytes <= 2 x budget`` plus per-column word
    rounding); (3) morselized results are BIT-identical to the
    whole-table oracle at SFs where the plain database is cheap to
    build.  Per-SF header rows carry the scan rate (packed GB/s over the
    summed per-query times) and one shared WAVE row streams all 13
    queries in a single morselized pass (PR 4 x out-of-core).

    Default SFs are CI-sized; set ``REPRO_SCALEUP_SFS=0.02,0.1,1`` to
    extend the sweep to SF-1 (6M rows) on a real machine."""
    from repro.sql.server import QueryServer
    if sfs is None:
        env = os.environ.get("REPRO_SCALEUP_SFS", "0.02,0.1")
        sfs = tuple(float(s) for s in env.split(",") if s)
    qs = engine.ssb_queries()
    for sf in sfs:
        pdb = ssb.generate_packed(sf, seed=7)
        fact_bytes = pdb.lineorder.nbytes
        budget = max(1 << 16, fact_bytes // 10)
        bound = 2 * budget + 4 * 1024   # word rounding per scanned column
        oracle = None
        if sf <= 0.1:
            plain = ssb.generate(sf, seed=7)
            oracle = {name: np.asarray(engine.run_query_oracle(plain, p))
                      for name, p in qs.items()}
        cache = HashTableCache()
        per_q, total_bytes = {}, 0
        for name, plan in qs.items():
            cq = compile_plan(plan, "fused")
            us = timeit(lambda cq=cq, pdb=pdb, cache=cache,
                        budget=budget: cq.execute(
                            pdb, mode="ref", cache=cache,
                            morsel_bytes=budget),
                        warmup=1, iters=2)
            out = cq.execute(pdb, mode="ref", cache=cache,
                             morsel_bytes=budget)
            assert cq.n_morsels > 1, \
                f"{name}: expected a multi-morsel stream at sf={sf}"
            assert cq.peak_resident_bytes <= bound, \
                (f"{name}: residency {cq.peak_resident_bytes} over "
                 f"2x budget {bound}")
            if oracle is not None:
                assert np.array_equal(np.asarray(out), oracle[name]), \
                    f"{name}: morselized result diverged at sf={sf}"
            per_q[name] = (us, cq.n_morsels, cq.peak_resident_bytes)
            enc_bytes, _ = SM.scanned_bytes(plan, pdb.lineorder)
            total_bytes += enc_bytes
        total_us = sum(us for us, _, _ in per_q.values())
        gbps = total_bytes / (total_us / 1e6) / 1e9
        peak = max(p for _, _, p in per_q.values())
        # the whole flight as ONE shared wave, streamed under the same
        # budget: the fact table crosses once per wave, morsel by morsel
        server = QueryServer(pdb, mode="ref", max_batch=16,
                             morsel_bytes=budget)

        def run_wave(server=server):
            for p in qs.values():
                server.submit(p, strategy="shared")
            return server.run()

        wave_us = timeit(lambda rw=run_wave: np.zeros(1) if rw() else None,
                         warmup=1, iters=2)
        wres = run_wave()
        assert all(r.error is None for r in wres.values())
        if oracle is not None:
            byname = {r.name: r for r in wres.values()}
            for name in qs:
                assert np.array_equal(np.asarray(byname[name].result),
                                      oracle[name]), \
                    f"{name}: shared wave diverged at sf={sf}"
        wave_m = max(r.n_morsels for r in wres.values())
        wave_peak = max(r.peak_resident_bytes for r in wres.values())
        assert wave_peak <= bound
        emit(f"scaleup.sf{sf:g}", 0.0,
             f"packed_mb={fact_bytes / 1e6:.1f};"
             f"budget_mb={budget / 1e6:.2f};scan_gbps={gbps:.2f};"
             f"n_morsels={per_q['q1.1'][1]};peak_mb={peak / 1e6:.2f};"
             f"residency_bound_held=True;bit_identical={oracle is not None}",
             extra={
                 "sf": sf, "n_fact": pdb.lineorder.n_rows,
                 "packed_bytes": fact_bytes, "morsel_budget": budget,
                 "scan_gbps": gbps,
                 "peak_resident_bytes": peak,
                 "n_morsels": {n: m for n, (_, m, _) in per_q.items()},
                 "bit_identical_vs_oracle": oracle is not None,
             })
        for name, (us, n_m, pk) in per_q.items():
            emit(f"scaleup.sf{sf:g}.{name}", us,
                 f"n_morsels={n_m};peak_mb={pk / 1e6:.2f}")
        emit(f"scaleup.sf{sf:g}.wave13", wave_us,
             f"n_morsels={wave_m};peak_mb={wave_peak / 1e6:.2f};"
             f"wave_size=13",
             extra={"sf": sf, "wave_n_morsels": wave_m,
                    "wave_peak_resident_bytes": wave_peak})


def chaos(sf: float = 0.01, rates=(0.0, 0.05, 0.2), seed: int = 123):
    """Chaos harness: the 13 SSB queries replayed under a seeded
    deterministic fault plan (``repro.sql.faults``) at increasing fault
    rates on the kernel-dispatch, morsel-upload and hash-build sites.

    The contract asserted per request, before anything is emitted:
    every request TERMINATES (no hang, no unhandled escape); every
    survivor is BIT-identical to the numpy oracle (a faulted neighbor
    or a mid-stream fault must not contaminate a later answer); every
    casualty carries a TYPED error (taxonomy kind + attempt count), or
    was shed at admission with a typed ``MemoryPressure``.  The fused
    ladder (fused -> opat -> ref) plus the resource governor do the
    surviving: injected OOMs shrink the morsel budget and evict caches
    instead of killing the request.

    Per-rate rows report availability (survivors / submitted), mean and
    p99 latency, and the server's resilience counters (retries, breaker
    skips, pressure events, sheds).  The fault schedule is counter-based
    on ``seed``, so a re-run replays the same faults."""
    from repro.sql import faults
    from repro.sql import resilience as RS
    from repro.sql import storage as ST
    from repro.sql.server import QueryServer
    db = ssb.generate(sf=sf, seed=7)
    pdb = ST.pack_database(db)
    qs = engine.ssb_queries()
    want = {name: np.asarray(engine.run_query_oracle(db, p))
            for name, p in qs.items()}
    # an eighth of the packed fact table: every query streams >1 morsel,
    # so the upload fault site actually fires
    budget = max(1 << 16, pdb.lineorder.nbytes // 8)
    known_kinds = {"PlanError", "CompileError", "ExecError",
                   "DeadlineExceeded", "MemoryPressure", "FaultInjected",
                   "InjectedOOM"}
    for rate in rates:
        plan = faults.FaultPlan(
            seed, {"kernel": rate, "upload": rate, "build": rate})
        from repro.sql.result_cache import ResultCache
        srv = QueryServer(pdb, mode="ref", morsel_bytes=budget,
                          result_cache=ResultCache())
        lat_us, ok, typed_err, shed = {}, 0, 0, 0
        with faults.active(plan):
            for name, p in qs.items():
                t0 = time.perf_counter()
                try:
                    rid = srv.submit(p, strategy="fused")
                except RS.MemoryPressure:
                    lat_us[name] = (time.perf_counter() - t0) * 1e6
                    shed += 1           # typed admission shed: terminated
                    continue
                r = srv.run()[rid]
                lat_us[name] = (time.perf_counter() - t0) * 1e6
                if r.error is None:
                    assert np.array_equal(np.asarray(r.result),
                                          want[name]), \
                        f"{name}: survivor diverged at rate {rate}"
                    ok += 1
                else:
                    assert r.error.error_kind in known_kinds, \
                        f"{name}: untyped error {r.error!r}"
                    assert r.attempts >= 1
                    typed_err += 1
        assert ok + typed_err + shed == len(qs)     # all terminated
        if rate == 0.0:
            assert ok == len(qs), "fault-free run must be 100% available"
        # cache correctness under pressure: replay the same queries
        # fault-free — answers may now come from the result cache
        # (unless mid-run pressure cleared it: the governor wipes the
        # grids on every MemoryPressure).  Served-from-cache or fresh,
        # every answer must stay bit-identical to the oracle, and every
        # hit must say so on the QueryResult.
        cache_served = 0
        for name, p in qs.items():
            try:
                rid = srv.submit(p, strategy="fused")
            except RS.MemoryPressure:
                continue                # still shedding: nothing to check
            r = srv.run()[rid]
            if r.error is None:
                assert np.array_equal(np.asarray(r.result), want[name]), \
                    f"{name}: cached replay diverged at rate {rate}"
                if r.cache_hit:
                    assert r.strategy == "cached"
                    cache_served += 1
        lats = sorted(lat_us.values())
        p99 = lats[min(len(lats) - 1, int(np.ceil(0.99 * len(lats))) - 1)]
        avail = ok / len(qs)
        inj = plan.stats()["faults"]
        emit(f"chaos.rate{rate:g}", float(np.mean(lats)),
             f"availability={avail:.2f};ok={ok};typed_errors={typed_err};"
             f"shed={shed};p99_us={p99:.0f};"
             f"injected={sum(inj.values())};"
             f"retries={srv.stats.get('retries', 0)};"
             f"breaker_skips={srv.stats.get('breaker_skips', 0)};"
             f"pressure_events={srv.stats.get('pressure_events', 0)};"
             f"cache_served_replay={cache_served};"
             f"all_terminated=True",
             extra={
                 "sf": sf, "seed": seed, "fault_rate": rate,
                 "availability": avail, "ok": ok,
                 "typed_errors": typed_err, "shed": shed,
                 "p99_us": p99, "mean_us": float(np.mean(lats)),
                 "injected_faults": dict(inj),
                 "fault_visits": dict(plan.stats()["visits"]),
                 "server_stats": {k: v for k, v in srv.stats.items()
                                  if isinstance(v, (int, float))},
                 "morsel_budget": budget,
                 "cache_served_replay": cache_served,
                 "result_cache": srv.result_cache.stats(),
             })


def serving(sf: float = 0.01, seed: int = 321, n_requests: int = 36):
    """Continuous serving under open-loop Poisson load: the 13 SSB
    queries plus their narrowed subsumption variants submitted to the
    ``ServingLoop`` on a seeded arrival schedule at three rates (0.5x /
    1.5x / 3x the measured solo-fused capacity), vs two baselines on
    the *same* schedule: solo-fused (submit+run one request at a time,
    the pre-PR-4 service) and the batch wave (whole workload handed
    over at t=0 — the PR 4 best case serving cannot exceed).

    Asserted per rate before anything is emitted: EVERY response —
    executed, exact cache hit, or subsumption-served — is bit-identical
    to the numpy oracle; p99 end-to-end latency holds the configured
    SLO; and at the highest rate the serving loop's qps beats the
    solo-fused baseline's (the wave former + result cache must pay for
    themselves exactly when the queue is deepest).

    Rows report mean end-to-end latency (the gated figure) with
    p50/p99, qps for all three services, and the cache/wave counters."""
    from repro.sql import serving as SV
    from repro.sql.server import QueryServer
    slo_s = 2.0
    db = ssb.generate(sf=sf, seed=7)
    qs = engine.ssb_queries()
    variants = engine.ssb_narrowed_variants(qs)
    pool = list(qs.items()) + [(n, p) for n, (_, p) in variants.items()]
    want = {n: np.asarray(engine.run_query_oracle(db, p)) for n, p in pool}
    workload = [pool[i % len(pool)] for i in range(n_requests)]

    # solo-fused capacity, measured warm (first pass pays the JIT)
    cap_srv = QueryServer(db, mode="ref")
    for _ in range(2):
        t0 = time.perf_counter()
        for _, p in pool:
            rid = cap_srv.submit(p, strategy="fused")
            r = cap_srv.run()[rid]
            assert r.error is None
    t_solo = (time.perf_counter() - t0) / len(pool)
    cap = 1.0 / t_solo
    anchor = [p for _, p in pool]

    def replay(submit_fn, schedule):
        """Drive one service over the arrival schedule; returns
        (per-request results, wall seconds first-arrival -> last
        completion).  submit_fn(name, plan) -> (result, latency_s)."""
        t0 = time.monotonic()
        out = []
        for t_arr, (name, p) in zip(schedule, workload):
            now = time.monotonic()
            if t0 + t_arr > now:
                time.sleep(t0 + t_arr - now)
            out.append((name,) + submit_fn(name, p))
        return out, time.monotonic() - t0

    qps_hi = {}
    for k, (label, mult) in enumerate(
            [("load0.5x", 0.5), ("load1.5x", 1.5), ("load3x", 3.0)]):
        schedule = SV.poisson_arrivals(mult * cap, n_requests, seed + k)
        # --- continuous serving loop (pool-anchored waves; prewarm
        # compiles the 4 pow2-bucket executables so the measured pass
        # never sees a novel shape regardless of wave composition) ---
        loop = SV.ServingLoop(db, mode="ref", slo_s=slo_s, max_batch=8,
                              warm_pool=anchor)
        loop.prewarm()
        with loop:
            t0 = time.monotonic()
            tickets = []
            for t_arr, (name, p) in zip(schedule, workload):
                now = time.monotonic()
                if t0 + t_arr > now:
                    time.sleep(t0 + t_arr - now)
                tickets.append((name, loop.submit(p, strategy="auto")))
            served = [(name, tk.wait(timeout=120), tk)
                      for name, tk in tickets]
            serving_wall = time.monotonic() - t0
        exact = subs = 0
        for name, r, _ in served:
            assert r.error is None, f"{name}: {r.error}"
            assert np.array_equal(np.asarray(r.result), want[name]), \
                f"{name}: serving answer diverged from the oracle"
            exact += bool(r.cache_hit and not r.subsumption_hit)
            subs += bool(r.subsumption_hit)
        lats = np.array([tk.latency_s for _, _, tk in served])
        p50, p99 = (float(np.percentile(lats, q)) for q in (50, 99))
        assert p99 <= slo_s, \
            f"{label}: p99 {p99:.3f}s blew the {slo_s}s SLO"
        qps = n_requests / serving_wall

        # --- solo-fused baseline, same schedule (serial open loop:
        # queueing shows up as lateness against the schedule) ---
        solo_srv = QueryServer(db, mode="ref")

        def solo_submit(name, p, _srv=solo_srv):
            t_in = time.monotonic()
            rid = _srv.submit(p, strategy="fused")
            r = _srv.run()[rid]
            assert r.error is None, f"{name}: {r.error}"
            assert np.array_equal(np.asarray(r.result), want[name])
            return r, time.monotonic() - t_in
        solo_served, solo_wall = replay(solo_submit, schedule)
        solo_lats = np.array([lat for _, _, lat in solo_served])
        qps_solo = n_requests / solo_wall
        qps_hi[label] = (qps, qps_solo)

        emit(f"serving.{label}", float(lats.mean() * 1e6),
             f"qps={qps:.1f};solo_qps={qps_solo:.1f};"
             f"p50_us={p50 * 1e6:.0f};p99_us={p99 * 1e6:.0f};"
             f"slo_s={slo_s};rate_qps={mult * cap:.1f};"
             f"exact_hits={exact};subsume_hits={subs};"
             f"shared_waves={loop.server.stats.get('shared_waves', 0)};"
             f"solo_p99_us={float(np.percentile(solo_lats, 99)) * 1e6:.0f}",
             extra={
                 "sf": sf, "seed": seed + k, "n_requests": n_requests,
                 "rate_qps": mult * cap, "slo_s": slo_s,
                 "qps": qps, "qps_solo": qps_solo,
                 "p50_us": p50 * 1e6, "p99_us": p99 * 1e6,
                 "solo_mean_us": float(solo_lats.mean() * 1e6),
                 "solo_p99_us": float(np.percentile(solo_lats, 99)) * 1e6,
                 "exact_hits": exact, "subsume_hits": subs,
                 "dispatch_reasons": dict(loop.former.dispatch_reasons),
                 "result_cache": loop.server.result_cache.stats(),
                 "server_stats": {k2: v for k2, v in
                                  loop.server.stats.items()
                                  if isinstance(v, (int, float))},
             })

    hi_qps, hi_solo = qps_hi["load3x"]
    assert hi_qps > hi_solo, \
        (f"serving qps {hi_qps:.1f} must beat solo-fused "
         f"{hi_solo:.1f} at the highest arrival rate")

    # --- batch-wave upper bound: the whole workload at t=0, one run ---
    bsrv = QueryServer(db, mode="ref", max_batch=8, anchor_plans=anchor)
    t0 = time.perf_counter()
    rids = {bsrv.submit(p, strategy="shared"): name
            for name, p in workload}
    batch_results = bsrv.run()
    batch_wall = time.perf_counter() - t0
    for rid, name in rids.items():
        r = batch_results[rid]
        assert r.error is None and np.array_equal(
            np.asarray(r.result), want[name])
    emit("serving.batch_wave", batch_wall / n_requests * 1e6,
         f"qps={n_requests / batch_wall:.1f};n={n_requests};"
         f"waves={bsrv.stats.get('shared_waves', 0)}",
         extra={"sf": sf, "n_requests": n_requests,
                "qps": n_requests / batch_wall})


def tuning():
    """Tuned-vs-default launch configuration per kernel family
    (``repro.sql.tune``): the empirical sweep's measured best time
    against the shipped-default configuration at the same shape.

    Bit-identity to the numpy oracle is asserted inside the sweep for
    EVERY candidate configuration BEFORE it is timed — a configuration
    that changes answers never produces a timing row.  The tie rule
    (a winner must beat the default beyond noise, else the default is
    kept) makes the >= 1.0x gate structural: a family whose knobs are
    inert on this backend reports exactly 1.0x because tuned and
    default are the same executable.  The hard gates — no family below
    1.0x, at least two families with a real (> 1.05x) measured win —
    are asserted, not just reported."""
    from repro.sql import tune as TN
    store = TN.tuned_store()        # cached sweep, or measure right now
    cfgs = store.tunings.configs
    real_wins = []
    for key in sorted(cfgs):
        c = cfgs[key]
        sp = c.speedup
        assert sp >= 1.0, (
            f"{key}: tuned configuration slower than default "
            f"({sp:.3f}x) — the tie rule should have kept the default")
        if sp > 1.05:
            real_wins.append(key)
        knobs = f"tile={c.tile}"
        if c.r:
            knobs += f";r={c.r}"
        if c.part_bits:
            knobs += f";bits={c.part_bits}"
        emit(f"tuning.{key.replace('/', '_')}", c.best_us,
             f"speedup={sp:.2f}x;{knobs}",
             extra={"default_us": c.default_us, "speedup": sp,
                    "tile": c.tile, "r": c.r, "part_bits": c.part_bits,
                    "part_budget_bytes": c.part_budget_bytes,
                    "eff_bw": c.eff_bw})
    assert len(real_wins) >= 2, (
        f"expected >= 2 kernel families with a real (>1.05x) tuned win, "
        f"got {real_wins}")
    emit("tuning.families_with_real_win", 0.0,
         f"count={len(real_wins)};{'+'.join(sorted(real_wins))}")


def table3_cost():
    """Table 3: cost effectiveness (renting)."""
    cpu_hr, gpu_hr = 0.504, 3.06
    speedup = 25.0  # paper's measured SSB average
    eff = speedup / (gpu_hr / cpu_hr)
    emit("table3.cost_ratio", 0.0, f"gpu_vs_cpu_rent={gpu_hr / cpu_hr:.2f}x")
    emit("table3.cost_effectiveness", 0.0,
         f"speedup=25x;cost_eff={eff:.1f}x;paper_claim=4x")


ALL = {
    "fig3": fig3_coprocessor,
    "fig8": fig8_partitioned_join,
    "fig9": fig9_tile_sweep,
    "fig10": fig10_project,
    "fig12": fig12_select,
    "fig13": fig13_join,
    "fig14": fig14_radix,
    "fig16": fig16_ssb,
    "fig17": fig17_fusion,
    "shared_throughput": shared_throughput,
    "compression": compression,
    "scaleout": scaleout,
    "scaleup": scaleup,
    "chaos": chaos,
    "serving": serving,
    "tuning": tuning,
    "table3": table3_cost,
}


def write_json(out_dir: str, name: str, rows) -> None:
    """One BENCH_<name>.json per table so the perf trajectory accumulates
    machine-readable points, not just stdout CSV."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    # device_count on every row's extra (and top-level): trajectories
    # recorded on an 8-virtual-device CI host and a 1-device laptop must
    # be tellable apart before anyone compares their timings
    dc = jax.device_count()
    dev = jax.devices()[0]
    payload = {
        "table": name,
        "unix_time": time.time(),
        "backend": jax.default_backend(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": dc,
        "rows": [dict({"name": n, "us_per_call": us, "derived": d},
                      extra=dict(extra or {}, device_count=dc))
                 for n, us, d, extra in rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {path}", file=sys.stderr)


def main() -> None:
    # JAX_COMPILATION_CACHE_DIR, when set, places the compile cache; else
    # a fixed directory in the checkout, so repeated runs hit it
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache"))
    argv = sys.argv[1:]
    json_out = "bench_out"      # every table records its trajectory
    if "--json" in argv:
        i = argv.index("--json")
        try:
            json_out = argv[i + 1]
        except IndexError:
            raise SystemExit(
                "--json requires an output directory") from None
        del argv[i:i + 2]
    if "--no-json" in argv:
        argv.remove("--no-json")
        json_out = None
    which = argv or list(ALL)
    unknown = [w for w in which if w not in ALL]
    if unknown:
        raise SystemExit(
            f"unknown table(s) {unknown}; available: {', '.join(ALL)}")
    print("name,us_per_call,derived")
    for w in which:
        start = len(ROWS)
        ALL[w]()
        if json_out is not None:
            write_json(json_out, w, ROWS[start:])


if __name__ == "__main__":
    main()
