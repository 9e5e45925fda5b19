"""Reduction of a profiler trace to the events the per-layer metrics read.

`from_xplane` keeps, for the traced window only: the device planes' program
executions ("XLA Modules") and operations ("XLA Ops"), and the host plane's
events (Python calls and runtime annotations). Everything the metrics and the
breakdown need is computed from these three lists, so the same reduction can
be checked on a small trace kept as JSON.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclass
class Trace:
    window: tuple                 # (start_ns, end_ns) of the traced sweep
    modules: list = field(default_factory=list)   # (name, start, dur, chip)
    ops: list = field(default_factory=list)       # (name, start, dur, chip)
    host: list = field(default_factory=list)      # (name, start, dur)
    chips: int = 1

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def to_json(self) -> str:
        return json.dumps({"window": list(self.window), "chips": self.chips,
                           "modules": self.modules, "ops": self.ops,
                           "host": self.host})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(window=tuple(d["window"]), chips=d["chips"],
                   modules=[tuple(e) for e in d["modules"]],
                   ops=[tuple(e) for e in d["ops"]],
                   host=[tuple(e) for e in d["host"]])


def _clip(events, lo, hi):
    """Events overlapping [lo, hi), clipped to it."""
    out = []
    for e in events:
        s, d = e[1], e[2]
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((e[0], a, b - a, *e[3:]))
    return out


def from_xplane(path: str, span: str) -> Trace:
    """Read an `.xplane.pb` file; the window is the host event named
    `span` (the harness's annotation around the traced sweep)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    modules, ops, host, chips = [], [], [], set()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            chips.add(chip)
            for line in plane.lines:
                dst = {MODULE_LINE: modules, OP_LINE: ops}.get(line.name)
                if dst is not None:
                    dst.extend((e.name, e.start_ns, e.duration_ns, chip)
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    marks = [e for e in host if e[0] == span]
    if not marks:
        raise ValueError(f"no host event {span!r} in {path}")
    lo, hi = marks[-1][1], marks[-1][1] + marks[-1][2]
    return Trace(window=(int(lo), int(hi)),
                 modules=_clip(modules, lo, hi), ops=_clip(ops, lo, hi),
                 host=_clip(host, lo, hi), chips=max(1, len(chips)))


def matching(events, needle: str) -> list:
    """Events whose name holds `needle` (program and kernel names are
    matched as the compiler prints them)."""
    return [e for e in events if needle in e[0]]


def inside(events, programs) -> list:
    """Events that lie within one of `programs`' executions on their chip."""
    spans = [(p[3], p[1], p[1] + p[2]) for p in programs]
    return [e for e in events
            if any(c == e[3] and a <= e[1] and e[1] + e[2] <= b
                   for c, a, b in spans)]


def total_s(events) -> float:
    return sum(e[2] for e in events) / 1e9


def merged(intervals) -> list:
    """Union of (start, end) intervals, as disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    chips: the union of each chip's op intervals (program executions where
    a trace has no op events)."""
    events = tr.ops or tr.modules
    per_chip = {}
    for e in events:
        per_chip.setdefault(e[3], []).append((e[1], e[1] + e[2]))
    total = sum(sum(b - a for a, b in merged(iv)) for iv in per_chip.values())
    return total / 1e9 / tr.chips


def gaps(tr: Trace, chip: int = 0) -> list:
    """Idle intervals (start, end) of one chip inside the window."""
    events = tr.ops or tr.modules
    busy = merged((e[1], e[1] + e[2]) for e in events if e[3] == chip)
    out, t = [], tr.window[0]
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if tr.window[1] > t:
        out.append((t, tr.window[1]))
    return out


def host_label(tr: Trace, lo: int, hi: int) -> str:
    """What the host was doing in [lo, hi): of the host events shorter than
    the gap (calls and transfers made inside it), the name whose events
    overlap it longest in sum; where none is shorter, the shortest event
    that overlaps it (the innermost call it falls in)."""
    inner, outer = {}, []
    for name, s, d in tr.host:
        ov = min(s + d, hi) - max(s, lo)
        if ov <= 0:
            continue
        if d < hi - lo:
            inner[name] = inner.get(name, 0) + ov
        else:
            outer.append((d, name))
    if inner:
        return max(inner.items(), key=lambda kv: kv[1])[0]
    return min(outer)[1] if outer else "(no host event)"


def idle_gaps(tr: Trace, top: int = 10, least_ns: int = 1000) -> list:
    """The longest idle gaps of chip 0 (of at least `least_ns`: shorter ones
    are the seams between back-to-back operations), each named by its host
    event."""
    longest = sorted((g for g in gaps(tr) if g[1] - g[0] >= least_ns),
                     key=lambda g: g[0] - g[1])[:top]
    return [[host_label(tr, a, b), (b - a) / 1e9] for a, b in longest]


def short(name: str) -> str:
    """An operation's or program's name without its HLO text or hash:
    '%fusion.12 = f32[...] fusion(...)' -> '%fusion.12',
    'jit__fleet_scan(1234)' -> 'jit__fleet_scan'."""
    return name.split(" = ")[0].split("(")[0]


def self_times(events) -> list:
    """(event, self ns): an event's duration less that of the events nested
    in it on the same chip (a while loop holds its body's operations)."""
    out = []
    for chip in {e[3] for e in events}:
        stack = []
        for e in sorted((e for e in events if e[3] == chip),
                        key=lambda e: (e[1], -e[2])):
            while stack and e[1] >= stack[-1][0][1] + stack[-1][0][2]:
                out.append(tuple(stack.pop()))
            if stack:
                stack[-1][1] -= e[2]
            stack.append([e, e[2]])
        out.extend(tuple(s) for s in stack)
    return out


def top_ops(tr: Trace, top: int = 10) -> list:
    """Device operations by self time, summed over their events and named
    '<program>/<op>'."""
    progs = sorted((m[3], m[1], m[1] + m[2], short(m[0])) for m in tr.modules)
    tot = {}
    for e, ns in self_times(tr.ops or tr.modules):
        prog = next((p[3] for p in progs
                     if p[0] == e[3] and p[1] <= e[1] < p[2]), None)
        key = f"{prog}/{short(e[0])}" if prog else short(e[0])
        tot[key] = tot.get(key, 0) + ns
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9 / tr.chips] for name, ns in ranked]
