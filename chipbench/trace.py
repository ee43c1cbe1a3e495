"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The device planes (``/device:TPU:<n>``) carry one event per executed
operation on their ``XLA Ops`` line, inside the program's event on the
``XLA Modules`` line.  The host planes carry the
benchmark's own spans (``jax.profiler.TraceAnnotation`` names starting
with ``chipbench.``), among them ``chipbench.window`` around the
measured window.  Device and host events share the profiler's clock.

* busy: the union of the device's op intervals inside the window,
  averaged over the devices that ran anything;
* ops: each op's total device time inside the window, the largest
  first, named ``<program>:<HLO instruction>``;
* idle gaps: the stretches of the window in which no op ran, each
  charged to the innermost benchmark span around its midpoint
  (``chipbench.window`` itself: the client between its calls), summed
  by span name, the largest first.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OUTSIDE = "no benchmark span"

Interval = Tuple[int, int]


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def find(log_dir) -> Path:
    """The one ``.xplane.pb`` under a profiler log directory."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line):
    for e in line.events:
        start = int(e.start_ns)
        yield e.name, start, start + int(e.duration_ns)


def _op_names(ops, modules):
    """Each op event named ``<program>:<instruction>``: the ``XLA
    Modules`` event around it without its fingerprint, and the HLO
    instruction's name without its text (``jit_f:fusion.3``)."""
    modules = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for text, a, b in sorted(ops, key=lambda e: e[1]):
        while j + 1 < len(modules) and modules[j + 1][1] <= a:
            j += 1
        mod = modules[j][0] if modules and modules[j][1] <= a else "?"
        op = text.split(" = ", 1)[0].lstrip("%")
        out.append((f"{mod.split('(', 1)[0]}:{op}", a, b))
    return out


def read(path) -> Tuple[Dict[str, list], List[Tuple[str, int, int]]]:
    """Device op events by plane, and the benchmark's host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    device, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(_events(line)) for line in plane.lines}
            device[plane.name] = _op_names(lines.get(OPS_LINE, []),
                                           lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            spans.extend(ev for line in plane.lines for ev in _events(line)
                         if ev[0].startswith(SPAN_PREFIX))
    return device, spans


def reduce(device: Dict[str, list], spans: List[Tuple[str, int, int]],
           top: int = 10) -> Reduced:
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    all_ops = [ev for evs in device.values() for ev in evs]
    if windows:
        lo, hi = windows[0]
    elif all_ops:
        lo = min(a for _, a, _ in all_ops)
        hi = max(b for _, _, b in all_ops)
    else:
        raise ValueError("trace holds neither a window span nor device ops")
    used = {p: evs for p, evs in device.items() if clip(
        [(a, b) for _, a, b in evs], lo, hi)}
    n_dev = max(1, len(used))
    busy_ns, op_ns = 0, defaultdict(int)
    idle: List[Interval] = []
    for evs in used.values():
        merged = union(clip([(a, b) for _, a, b in evs], lo, hi))
        busy_ns += sum(b - a for a, b in merged)
        for name, a, b in evs:
            if min(b, hi) > max(a, lo):
                op_ns[name] += min(b, hi) - max(a, lo)
        idle.extend(gaps(merged, lo, hi))
    if not used:                      # a window in which nothing ran
        idle.append((lo, hi))

    def ranked(d):
        return [(k, v / n_dev / 1e9) for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy_ns / n_dev / 1e9,
                   devices=len(used), ops=ranked(op_ns),
                   idle_gaps=ranked(charge(idle, spans)))


def charge(idle: List[Interval], spans) -> Dict[str, int]:
    """Nanoseconds of ``idle`` by the innermost (latest-starting)
    benchmark span around each gap's midpoint, in one sweep."""
    order = sorted((a, b, n) for n, a, b in spans)
    out: Dict[str, int] = defaultdict(int)
    active: list = []
    i = 0
    for a, b in sorted(idle, key=lambda g: g[0] + g[1]):
        t = (a + b) // 2
        while i < len(order) and order[i][0] <= t:
            active.append(order[i])
            i += 1
        active = [s for s in active if s[1] >= t]
        out[active[-1][2] if active else OUTSIDE] += b - a
    return out


def reduce_file(path, top: int = 10) -> Reduced:
    return reduce(*read(path), top=top)
