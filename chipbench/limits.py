"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 chipbench/limits.py --workload ssb_sf10.joins \
        --seeds 11 12 13 --control-seeds 11 12

For each seed: set up the cell's tables as a run does (``data.load``),
send each distinct query of its mix once through the timed path
(``QueryServer.submit`` then ``run``, the cell's strategy) and compare
every answer with the plain reference, as a run does.  For each control seed,
also compare the control with the reference: the reference with its
sums accumulated in float32 (``f32``).  Gaps are ``reference.gap``'s
float32 steps.  One JSON line per seed, then a summary: the lower
reading (largest program gap over the seeds) and the control's upper
reading (smallest gap).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if not __package__:
    sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from chipbench import data, loadgen, reference, run, spec  # noqa: E402

CONTROLS = ("f32",)


def readings(cell: spec.Cell, cfg: dict, mix: dict, seed: int,
             controls: bool) -> dict:
    from repro.sql import engine
    from repro.sql.server import QueryServer
    t0 = time.perf_counter()
    tables, placed = data.load(cfg, seed)
    server = QueryServer(**placed)
    plans = engine.ssb_queries()
    names = loadgen.distinct(mix)
    got, faults = {}, 0
    for name in names:
        server.submit(plans[name], strategy=mix["strategy"])
        (r,) = server.run().values()
        got[name] = r.result
        faults += bool(run.faults(r, mix["strategy"]))
    del server, placed
    gc.collect()
    want = reference.answers(tables, names)
    out = {"seed": seed, "faults": faults,
           "program": max(reference.gap(got[n], want[n]) for n in names)}
    for precision in CONTROLS if controls else ():
        ctl = reference.answers(tables, names, precision)
        out[precision] = max(reference.gap(ctl[n], want[n]) for n in names)
        out[precision + "_by_query"] = {
            n: reference.gap(ctl[n], want[n]) for n in names}
    out["s"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, spec.benchmark())
    cfg, mix = spec.config(cell.config), spec.traffic(cell.traffic)
    import jax
    run.configure_cache(jax)
    try:
        run.accelerators(jax, cell.chips)
    except run.NoChip as e:
        run.log(f"chipbench: {e}")
        return 2
    rows = []
    for seed in dict.fromkeys(list(args.seeds) + list(args.control_seeds)):
        rows.append(readings(cell, cfg, mix, seed,
                             seed in args.control_seeds))
        print(json.dumps(rows[-1]), flush=True)
    summary = {"workload": cell.name, "seeds": len(rows),
               "lower": max(r["program"] for r in rows),
               "faults": sum(r["faults"] for r in rows)}
    for precision in CONTROLS:
        got = [r[precision] for r in rows if precision in r]
        if got:
            summary[precision + "_upper"] = min(got)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
