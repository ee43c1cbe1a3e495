"""The trace reduction of ``chipbench.trace`` with the program's own
spans and scopes: where a cell's device idle time and busy time go.

``chipbench.trace`` reads the benchmark's spans alone (``chipbench.``).
This reduction reads the same ``.xplane.pb`` and adds the program's:

* host spans starting with ``sql.`` (named in ``repro/sql/spans.py``)
  join ``chipbench.`` ones as places an idle gap is charged to, still
  the innermost span around the gap's midpoint; a name is cut at ``#``,
  where the profiler keeps a span's metadata (``sql.query#rid=7#``);
* each op is named ``<program>:<scope>:<HLO instruction>`` where it
  lies in a ``spja.*`` scope (``jax.named_scope`` in ``kernels/ref.py``
  and ``kernels/ops.py``), read from the ``tf_op`` stat of the op's
  event metadata;
* ``spans``: for each span name inside the window, its count, total
  seconds and self seconds (less what the spans nested in it cover);
* ``scopes``: device self time by innermost scope: an op nested in
  another (a loop's body in its ``while``) counts once, in the
  innermost op.

Window, busy time and the op sums are those ``chipbench.trace`` gives
for the same trace.  ``chipbench/split.py`` runs a cell with it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, NamedTuple, Tuple

from chipbench import trace as TR

SPAN_PREFIXES = ("chipbench.", "sql.")
SCOPE = re.compile(r"spja\.[a-z]+")
NESTED_SHARE = 0.99     # of an unscoped op's nested time, to take a scope


class SpanTime(NamedTuple):
    count: int
    total_s: float
    self_s: float


@dataclass
class Split(TR.Reduced):
    spans: Dict[str, SpanTime] = field(default_factory=dict)
    scopes: Dict[str, float] = field(default_factory=dict)


def _op_names(ops, modules, scopes):
    """Each op event named as ``chipbench.trace`` names it
    (``jit_f:fusion.3``), with its scope between program and instruction
    where one is found (``jit_f:spja.probe:while.4``).  An op's scope is
    its own from ``scopes`` (:func:`op_scopes`), or, where it has none,
    the scope of at least ``NESTED_SHARE`` of the scoped device time
    nested in it: a ``while`` carries no name stack of its own, and XLA
    may sink a sliver of another phase into a loop's body (a probe loop
    holds ~0.1% of key decode), while the fold over row blocks, which
    nests every phase, stays unscoped."""
    modules = sorted(modules, key=lambda m: m[1])
    keyed, j = [], 0
    for text, a, b in sorted(ops, key=lambda e: e[1]):
        while j + 1 < len(modules) and modules[j + 1][1] <= a:
            j += 1
        mod = modules[j][0] if modules and modules[j][1] <= a else "?"
        keyed.append((mod, text, a, b))
    own = {(mod, text): scopes.get((mod.partition("(")[2].rstrip(")"),
                                    text))
           for mod, text, _, _ in keyed}
    inner: Dict[Tuple[str, str], Dict[str, int]] = defaultdict(
        lambda: defaultdict(int))
    stack: List[Tuple[Tuple[str, str], int]] = []
    if keyed:
        lo, hi = keyed[0][2], max(b for *_, b in keyed)
        for key, a, b, self_ns in nested(
                [((m, t), a, b) for m, t, a, b in keyed], lo, hi):
            while stack and stack[-1][1] <= a:
                stack.pop()
            if own[key] is not None:
                for outer, end in stack:
                    if b <= end:        # nested, not merely overlapping
                        inner[outer][own[key]] += self_ns
            stack.append((key, b))
    out = []
    for mod, text, a, b in keyed:
        scope = own[(mod, text)]
        if scope is None and inner[(mod, text)]:
            held = inner[(mod, text)]
            top = max(held, key=held.get)
            if held[top] >= NESTED_SHARE * sum(held.values()):
                scope = top
        prog = mod.split("(", 1)[0]
        op = text.split(" = ", 1)[0].lstrip("%")
        out.append((f"{prog}:{scope}:{op}" if scope else f"{prog}:{op}",
                    a, b))
    return out


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for a varint, a memoryview for a length-delimited field,
    None for a fixed-width one."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind, value = key & 7, None
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _map_values(fields, number: int):
    """The messages a protobuf map field holds (each entry's field 2)."""
    for f, entry in fields:
        if f == number:
            yield from (v for k, v in _fields(entry) if k == 2)


def op_scopes(path) -> Dict[Tuple[str, str], str]:
    """The innermost ``spja.*`` scope of each device op, keyed by its
    program id and HLO text, from the ``tf_op`` stat of the op's event
    metadata (``XPlane.event_metadata``, which ``ProfileData`` does not
    show).  Host planes are skipped after their name."""
    out: Dict[Tuple[str, str], str] = {}
    for f, plane in _fields(memoryview(Path(path).read_bytes())):
        if f != 1:                                  # XSpace.planes
            continue
        fields = []
        for k, v in _fields(plane):
            if k == 2 and not TR.DEVICE_PLANE.match(bytes(v).decode()):
                break                               # XPlane.name
            fields.append((k, v))
        else:
            stat_names = {}
            for md in _map_values(fields, 5):       # XPlane.stat_metadata
                d = dict(_fields(md))
                stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
            for md in _map_values(fields, 4):       # XPlane.event_metadata
                name, stats = "", {}
                for k, v in _fields(md):
                    if k == 2:
                        name = bytes(v).decode()
                    elif k == 5:                    # XStat
                        d = dict(_fields(v))
                        stat = stat_names.get(d.get(1))
                        if 5 in d:
                            stats[stat] = bytes(d[5]).decode()
                        elif 7 in d:                # interned string
                            stats[stat] = stat_names.get(d[7], "")
                        else:
                            stats[stat] = str(d.get(3, d.get(4, "")))
                found = SCOPE.findall(stats.get("tf_op", ""))
                if found:
                    out[(stats.get("program_id", ""), name)] = found[-1]
    return out


def read(path) -> Tuple[Dict[str, list], List[Tuple[str, int, int]]]:
    """Device op events by plane, scoped, and the benchmark's and the
    program's host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    scopes = op_scopes(path)
    device, spans = {}, []
    for plane in data.planes:
        if TR.DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(TR._events(line))
                     for line in plane.lines}
            device[plane.name] = _op_names(lines.get(TR.OPS_LINE, []),
                                           lines.get(TR.MODULES_LINE, []),
                                           scopes)
        elif plane.name.startswith("/host:"):
            spans.extend(host_spans(ev for line in plane.lines
                                    for ev in TR._events(line)))
    return device, spans


def host_spans(events) -> List[Tuple[str, int, int]]:
    """The benchmark's and the program's spans among host events, each
    name cut at ``#``: ``sql.query#rid=7#`` is ``sql.query``."""
    return [(n.split("#", 1)[0], a, b) for n, a, b in events
            if n.startswith(SPAN_PREFIXES)]


def reduce(device: Dict[str, list], spans: List[Tuple[str, int, int]],
           top: int = 10) -> Split:
    windows = [(a, b) for n, a, b in spans if n == TR.WINDOW_SPAN]
    all_ops = [ev for evs in device.values() for ev in evs]
    if windows:
        lo, hi = windows[0]
    elif all_ops:
        lo = min(a for _, a, _ in all_ops)
        hi = max(b for _, _, b in all_ops)
    else:
        raise ValueError("trace holds neither a window span nor device ops")
    used = {p: evs for p, evs in device.items() if TR.clip(
        [(a, b) for _, a, b in evs], lo, hi)}
    n_dev = max(1, len(used))
    busy_ns, op_ns, scope_ns = 0, defaultdict(int), defaultdict(int)
    idle: List[TR.Interval] = []
    for evs in used.values():
        merged = TR.union(TR.clip([(a, b) for _, a, b in evs], lo, hi))
        busy_ns += sum(b - a for a, b in merged)
        idle.extend(TR.gaps(merged, lo, hi))
        for name, a, b, own in nested(evs, lo, hi):
            op_ns[name] += b - a
            parts = name.split(":")
            if len(parts) == 3:
                scope_ns[parts[1]] += own
    if not used:                      # a window in which nothing ran
        idle.append((lo, hi))
    span_ns: Dict[str, list] = defaultdict(lambda: [0, 0, 0])
    for name, a, b, own in nested(spans, lo, hi):
        acc = span_ns[name]
        acc[0] += 1
        acc[1] += b - a
        acc[2] += own

    def ranked(d):
        return [(k, v / n_dev / 1e9) for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return Split(window_s=(hi - lo) / 1e9, busy_s=busy_ns / n_dev / 1e9,
                 devices=len(used), ops=ranked(op_ns),
                 idle_gaps=ranked(charge(idle, spans)),
                 spans={n: SpanTime(c, t / 1e9, o / 1e9)
                        for n, (c, t, o) in span_ns.items()},
                 scopes={k: v / n_dev / 1e9 for k, v in scope_ns.items()})


def nested(events, lo: int, hi: int) -> List[Tuple[str, int, int, int]]:
    """``(name, start, end, self)`` of each event clipped to the window
    ``[lo, hi)``, where self is its length less what the events nested
    directly in it cover.  Events nest as one thread's spans or one
    device's ops do: a later one starts after an earlier one ends, or
    inside it."""
    order = sorted(((max(a, lo), min(b, hi), n) for n, a, b in events
                    if min(b, hi) > max(a, lo)),
                   key=lambda e: (e[0], -e[1]))
    out: List[list] = []
    stack: List[list] = []
    for a, b, name in order:
        while stack and stack[-1][2] <= a:
            stack.pop()
        ev = [name, a, b, b - a]
        if stack:
            stack[-1][3] -= min(b, stack[-1][2]) - a
        stack.append(ev)
        out.append(ev)
    return [tuple(ev) for ev in out]


def charge(idle: List[TR.Interval], spans) -> Dict[str, int]:
    """Nanoseconds of ``idle`` by the innermost span around each gap's
    midpoint, in one sweep: ``chipbench.trace.charge`` with spans that
    start together taken outer first, so the inner one is innermost."""
    order = sorted(((a, b, n) for n, a, b in spans),
                   key=lambda s: (s[0], -s[1]))
    out: Dict[str, int] = defaultdict(int)
    active: list = []
    i = 0
    for a, b in sorted(idle, key=lambda g: g[0] + g[1]):
        t = (a + b) // 2
        while i < len(order) and order[i][0] <= t:
            active.append(order[i])
            i += 1
        active = [s for s in active if s[1] >= t]
        out[active[-1][2] if active else TR.OUTSIDE] += b - a
    return out


def reduce_file(path, top: int = 10) -> Split:
    return reduce(*read(path), top=top)
