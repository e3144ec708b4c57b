"""Reduce a profiler trace of the window to per-device times.

A TPU trace (``*.xplane.pb``) holds, per chip, a plane ``/device:TPU:<n>``
whose line ``XLA Ops`` has one event per executed HLO instruction (a
``while`` event encloses the events of its body) and whose line
``Async XLA Ops`` has the in-flight intervals of asynchronous copies and
collectives. Events are named by the instruction's HLO text
(``%fusion.18 = f32[...] fusion(...)``); they carry no scope, so the scope
comes from the compiled program's own HLO (``op_name`` metadata, see
``system.hlo_scopes``). The harness's host spans (``chipbench/...``) are
on the ``/host:CPU`` plane, on the same clock.

``load`` turns a trace file into plain ``Event`` lists; ``reduce`` does the
arithmetic on those, so that it can be checked on constructed events.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

WINDOW_SPAN = "chipbench/window"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "collective-broadcast")
_SCOPE = re.compile(r"(?:^|/)((?:engine|halo)/.*)$")
_NAME = re.compile(r"^\s*%?([\w.\-]+)")


@dataclasses.dataclass(frozen=True)
class Event:
    start_ns: float
    dur_ns: float
    name: str

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Device:
    ops: list        # Event per executed instruction (line "XLA Ops")
    asyncs: list     # in-flight asynchronous ops (line "Async XLA Ops")


def load(path: str) -> tuple[dict, list]:
    """(``{device plane name: Device}``, host span events) of a trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = Device(
                ops=_events(lines.get("XLA Ops")),
                asyncs=_events(lines.get("Async XLA Ops")))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [e for e in _events(ln)
                         if e.name.startswith("chipbench/")]
    return devices, host


def _events(line) -> list:
    if line is None:
        return []
    return [Event(float(e.start_ns), float(e.duration_ns), e.name)
            for e in line.events]


def instruction(event_name: str) -> str:
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name


def scope_of(op_name: str) -> str:
    """The engine/halo part of an op_name, or ``unscoped``."""
    m = _SCOPE.search(op_name or "")
    return m.group(1) if m else "unscoped"


def is_collective(event_name: str) -> bool:
    head = event_name.split("=", 1)[-1] if "=" in event_name else event_name
    inst = instruction(event_name)
    return any(c in inst or f" {c}" in head for c in COLLECTIVES)


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(events, t0, t1) -> list:
    out = []
    for ev in events:
        s, e = max(ev.start_ns, t0), min(ev.end_ns, t1)
        if e > s:
            out.append(Event(s, e - s, ev.name))
    return out


def self_times(ops) -> list:
    """(event, self ns) per op: its duration less that of the events it
    encloses on the same line (a ``while`` and its body)."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    selft = [e.dur_ns for e in ops]
    stack: list[int] = []
    for i, ev in enumerate(ops):
        while stack and ops[stack[-1]].end_ns <= ev.start_ns:
            stack.pop()
        if stack:
            parent = ops[stack[-1]]
            selft[stack[-1]] -= min(ev.end_ns, parent.end_ns) - ev.start_ns
        stack.append(i)
    return list(zip(ops, selft))


@dataclasses.dataclass
class Reduced:
    window_ns: float
    busy_ns: dict            # device -> union of op intervals
    scope_ns: dict           # device -> {scope: self ns}
    exposed_ns: dict         # device -> collective time with no other op
    top_ops: list            # [(label, ns)] mean over devices, largest first
    gaps: list               # [(label, ns)] longest idle gaps, largest first

    def mean(self, table: dict) -> float:
        return sum(table.values()) / max(len(table), 1)

    def scope_sum(self, prefixes, device=None) -> float:
        """Mean over devices of the self time under scopes starting with
        any of ``prefixes`` (or one device's)."""
        devs = [device] if device else list(self.scope_ns)
        tot = sum(ns for d in devs for sc, ns in self.scope_ns[d].items()
                  if sc.startswith(tuple(prefixes)))
        return tot / max(len(devs), 1)


def reduce(devices: dict, host: list, scopes: dict, top: int = 10) -> Reduced:
    """Reduce a window's trace. The window is the host span ``WINDOW_SPAN``
    (it ends when the device has finished the last step)."""
    win = [e for e in host if e.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    t0, t1 = win[0].start_ns, win[0].end_ns
    spans = sorted((e for e in host if e.name != WINDOW_SPAN),
                   key=lambda e: e.start_ns)
    busy, scope_ns, exposed = {}, {}, {}
    ops_total: dict = defaultdict(float)
    gaps = []
    for dev, d in sorted(devices.items()):
        ops = clip(d.ops, t0, t1)
        busy_iv = union((e.start_ns, e.end_ns) for e in ops)
        busy[dev] = length(busy_iv)
        per = defaultdict(float)
        for ev, ns in self_times(ops):
            inst = instruction(ev.name)
            sc = scope_of(scopes.get(inst, ""))
            per[sc] += ns
            ops_total[f"{inst} [{sc}]"] += ns
        scope_ns[dev] = dict(per)
        coll = union([(e.start_ns, e.end_ns) for e in clip(d.asyncs, t0, t1)
                      if is_collective(e.name)]
                     + [(e.start_ns, e.end_ns) for e in ops
                        if is_collective(e.name)])
        compute = union((e.start_ns, e.end_ns) for e in ops
                        if not is_collective(e.name))
        exposed[dev] = length(subtract(coll, compute))
        for s, e in subtract([[t0, t1]], busy_iv):
            gaps.append((f"{_open_span(spans, (s + e) / 2)} ({dev})", e - s))
    gaps.sort(key=lambda g: -g[1])
    n = max(len(devices), 1)
    top_ops = sorted(((k, v / n) for k, v in ops_total.items()),
                     key=lambda kv: -kv[1])[:top]
    return Reduced(window_ns=t1 - t0, busy_ns=busy, scope_ns=scope_ns,
                   exposed_ns=exposed, top_ops=top_ops, gaps=gaps[:top])


def _open_span(spans, t) -> str:
    """Innermost harness span open at ``t`` (``idle`` if none)."""
    best = None
    for e in spans:
        if e.start_ns <= t < e.end_ns and (best is None
                                          or e.start_ns >= best.start_ns):
            best = e
    return best.name if best else "chipbench/none"
