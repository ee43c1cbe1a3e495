"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload ssb_sf10.joins --seed 7 \
        --seconds 49 --trace 0

The cell, its configuration and its traffic come from ``BENCHMARK.json``
and the files named after them (``chipbench.spec``).  One process, on
the machine it is started on:

1. set-up (``setup_s``, from process start): draw the tables from
   ``--seed`` and hold them as the configuration says (``data.load``:
   by default packed, every table on the device), build the
   ``QueryServer``, and send each distinct query of the mix once, which
   loads or compiles every program the window runs;
2. the window: one closed-loop client sends the mix's queries, each as
   ``submit`` then ``run``, until the first completion at or after
   ``--seconds``; with ``--trace 1`` the profiler records it;
3. the check: the plain reference (``chipbench.reference``) answers
   every query the window sent, and ``correct`` holds when every
   answer lies within the configuration's limit of float32 steps from
   the exact sum, and no query of the window failed: none errored,
   retried, fell back to another strategy or ran one it was not sent
   with.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end metrics with
``--trace 0``, per-layer ones with ``--trace 1``), ``device``,
``breakdown`` when traced, and last ``checks``, each compared number
beside its limit.  The same numbers end standard error.  With no
accelerator, fewer chips than the cell asks for, or a device kind
missing from ``peaks.json``, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if not __package__:                   # run as a script: import the package
    sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from chipbench import data, loadgen, reference, roofline, spec  # noqa: E402
from chipbench import trace as TR  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def accelerators(jax, chips: int):
    """The first ``chips`` accelerator devices and their peaks."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if devices[0].platform == "cpu" or len(devices) < chips:
        raise NoChip(f"needs {chips} accelerator(s); JAX sees "
                     f"{len(devices)} {devices[0].platform} device(s)")
    try:
        peaks = roofline.peaks(devices[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e
    return devices[:chips], peaks


def configure_cache(jax) -> None:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else at ``.jax_cache`` in the checkout; every program cached, so a
    second run of a cell compiles nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def faults(r, strategy: str) -> list:
    out = []
    if r.error is not None:
        out.append("error")
    if r.attempts != 1:
        out.append("attempts")
    if r.fallback_reason is not None:
        out.append("fallback")
    if r.strategy != strategy:
        out.append("strategy")
    return out


def end_to_end(name: str, window: dict, setup_s: float) -> float:
    """An end-to-end metric by its base name (``qps.scan`` is ``qps``)."""
    name = name.split(".", 1)[0]
    if name == "qps":
        return len(window["queries"]) / window["window_s"]
    if name == "latency_p95_s":
        lat = [q["latency_s"] for q in window["queries"]]
        return statistics.quantiles(lat, n=20)[-1] if len(lat) > 1 \
            else lat[0]
    if name == "setup_s":
        return setup_s
    raise KeyError(f"no end-to-end metric {name!r} in this harness")


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def measure(cell: spec.Cell, cfg: dict, mix: dict, seed: int,
            seconds: float, traced: bool, devices, peaks: dict,
            t0: float) -> dict:
    """Set up, run the window, check it; returns the result object."""
    import jax
    from jax.profiler import TraceAnnotation as span

    from repro.sql import engine
    from repro.sql.server import QueryServer

    compiles = []

    def on_event(event, _secs, **_kw):
        if event == COMPILE_EVENT:
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        tables, placed = data.load(cfg, seed)
        server = QueryServer(**placed)
        plans = engine.ssb_queries()
        strategy = mix["strategy"]

        def one(name: str) -> dict:
            t = time.perf_counter()
            with span("chipbench.submit"):
                server.submit(plans[name], strategy=strategy)
            with span("chipbench.run"):
                (r,) = server.run().values()
            done = time.perf_counter()
            return {"name": name, "latency_s": done - t, "done": done,
                    "result": r.result, "bytes_scanned": r.bytes_scanned,
                    "faults": faults(r, strategy)}

        for name in loadgen.distinct(mix):
            w = one(name)
            log(f"warm query={name} s={w['latency_s']:.4f} "
                f"faults={w['faults']}")
        gc.collect()
        if traced:
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(trace_dir, profiler_options=_options())
        names = loadgen.sequence(mix)
        queries = []
        start = time.perf_counter()
        with span("chipbench.window"):
            while not queries or queries[-1]["done"] - start < seconds:
                queries.append(one(next(names)))
        window = {"queries": queries, "window_s": queries[-1]["done"] - start}
        if traced:
            jax.profiler.stop_trace()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    setup_s = start - t0
    in_window = sum(start <= c <= start + window["window_s"]
                    for c in compiles)
    log(f"window queries={len(queries)} s={window['window_s']:.4f} "
        f"setup_s={setup_s:.3f} compiles_in_window={in_window} "
        f"compiles_in_setup={len(compiles) - in_window}")
    mem = memory_peak(devices)
    del server, placed
    gc.collect()

    if traced:
        reduced = TR.reduce_file(TR.find(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    t = time.perf_counter()
    want = reference.answers(tables, [q["name"] for q in queries])
    gaps = [reference.gap(q["result"], want[q["name"]]) for q in queries]
    log(f"reference queries={len(want)} s={time.perf_counter() - t:.3f}")
    failed = sum(bool(q["faults"]) for q in queries)
    limits = cfg["limits"]
    checks = {"max_f32_steps": {"value": max(gaps), "limit":
                                limits["max_f32_steps"]},
              "failed_queries": {"value": failed, "limit":
                                 limits["failed_queries"]}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": len(queries),
           "failed": failed}
    if traced:
        record = {"queries": queries, "window_s": window["window_s"],
                  "compiles_in_window": in_window, "trace": reduced,
                  "peaks": peaks,
                  "least_bytes": {n: roofline.least_bytes(cfg, n)
                                  for n in want}}
        metrics = {}
        for m in cell.per_layer:
            v = spec.reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": [list(o) for o in reduced.ops],
                            "idle_gaps": [list(g) for g in
                                          reduced.idle_gaps]}
    else:
        out["metrics"] = {m["name"]: {
            "value": end_to_end(m["name"], window, setup_s),
            "unit": m["unit"]} for m in cell.end_to_end}
        out["device"] = device
    out["checks"] = checks
    return out


def _options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host spans and runtime only
    opts.enable_hlo_proto = False     # op names suffice; keeps it small
    return opts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload, spec.benchmark())
    cfg, mix = spec.config(cell.config), loadgen.validate(
        spec.traffic(cell.traffic))
    import jax
    configure_cache(jax)
    try:
        devices, peaks = accelerators(jax, cell.chips)
    except NoChip as e:
        log(f"chipbench: {e}")
        return 2
    log(f"cell={cell.name} device={devices[0].device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"cache={jax.config.jax_compilation_cache_dir}")
    out = measure(cell, cfg, mix, args.seed, args.seconds, bool(args.trace),
                  devices, peaks, T0)
    log(f"correct={out['correct']} attempted={out['attempted']} "
        f"failed={out['failed']}")
    for name, c in out["checks"].items():
        log(f"check {name} value={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
