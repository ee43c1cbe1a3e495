"""Run one cell as ``chipbench/run.py`` does, traced, and show where the
device's idle and busy time go by the program's own spans and scopes
(``chipbench.span_trace``), with the host-to-device bytes each query
copied (``QueryResult.upload_bytes``):

    python3 chipbench/split.py --workload ssb_sf20.flight1 --seed 7 \
        --seconds 49

The run is ``run.measure`` itself, with two hooks: its trace is reduced
by ``span_trace``, so the result line's ``breakdown`` names the
program's spans and scopes, and each query's result is kept.  Two JSON
lines end standard output: that result line, then ``per_query`` (each
span's count, total and self seconds over the window's queries),
``scopes`` (device self seconds and share of busy time) and
``upload_bytes_per_query``.  Exits 2 where ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent.parent
if not __package__:                   # run as a script: import the package
    sys.path[0] = str(ROOT)

from chipbench import loadgen, run, span_trace, spec  # noqa: E402


def split(cell: spec.Cell, cfg: dict, mix: dict, seed: int,
          seconds: float, devices, peaks: dict, t0: float):
    """``run.measure`` traced; returns its result and the split."""
    results, splits = [], []
    faults, trace = run.faults, run.TR

    def keep(r, strategy):
        results.append(r)
        return faults(r, strategy)

    def reduce_file(path, top=10):
        splits.append(span_trace.reduce_file(path, top))
        return splits[-1]

    run.faults = keep
    run.TR = SimpleNamespace(find=trace.find, reduce_file=reduce_file)
    try:
        out = run.measure(cell, cfg, mix, seed, seconds, True, devices,
                          peaks, t0)
    finally:
        run.faults, run.TR = faults, trace
    (s,) = splits
    n = out["attempted"]
    window = [r.upload_bytes for r in results[-n:]]
    return out, {
        "queries": n,
        "per_query": {name: {"count": t.count / n, "total_s": t.total_s / n,
                             "self_s": t.self_s / n}
                      for name, t in sorted(s.spans.items())},
        "scopes": {k: {"s": v, "share_of_busy": v / s.busy_s}
                   for k, v in sorted(s.scopes.items()) if s.busy_s > 0},
        "upload_bytes_per_query": sum(window) / n}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload, spec.benchmark())
    cfg, mix = spec.config(cell.config), loadgen.validate(
        spec.traffic(cell.traffic))
    import jax
    run.configure_cache(jax)
    # the cache key leaves op metadata out by default: an executable
    # compiled from sources without the scopes would carry none
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        devices, peaks = run.accelerators(jax, cell.chips)
    except run.NoChip as e:
        run.log(f"chipbench: {e}")
        return 2
    out, found = split(cell, cfg, mix, args.seed, args.seconds, devices,
                       peaks, run.T0)
    print(json.dumps(out), flush=True)
    print(json.dumps(found), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
