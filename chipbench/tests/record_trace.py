"""Record the small profiler trace that ``test_span_trace.py`` reduces:
q1.1 then q3.4 through ``QueryServer`` over 65,632 fact rows drawn by
``ssb_sf10``'s rules, resident on the device, warmed once, then traced
under the benchmark's spans with ``run._options()``.  Run it on the
chip, from the root of a checkout:

    python3 chipbench/tests/record_trace.py \
        chipbench/tests/data/tpu_v5e_spans.xplane.pb
"""
from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

ROWS = 65_632
QUERIES = ("q1.1", "q3.4")


def main(out: str) -> int:
    import jax
    from jax.profiler import TraceAnnotation as span

    from chipbench import data, run, span_trace, spec
    from chipbench import trace as TR
    from chipbench.tests.conftest import shrink
    from repro.sql import engine
    from repro.sql.server import QueryServer

    cfg = shrink(spec.config("ssb_sf10"), ROWS)
    _, placed = data.load(cfg, seed=7)
    server = QueryServer(**placed)
    plans = engine.ssb_queries()

    def one(name: str) -> None:
        with span("chipbench.submit"):
            server.submit(plans[name], strategy="fused")
        with span("chipbench.run"):
            (r,) = server.run().values()
        assert r.error is None and r.strategy == "fused", r

    for name in QUERIES:                # compiles outside the trace
        one(name)
    trace_dir = tempfile.mkdtemp(prefix="record-trace-")
    jax.profiler.start_trace(trace_dir, profiler_options=run._options())
    with span("chipbench.window"):
        for name in QUERIES:
            one(name)
    jax.profiler.stop_trace()
    shutil.copyfile(TR.find(trace_dir), out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(span_trace.reduce_file(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
