"""Profiler trace → per-device operation intervals, and interval algebra.

A traced run brackets its measured window with the host annotation
``bench:window``; :func:`read_xplane` keeps each TPU's executed HLO ops
and the harness's own ``bench:*`` host spans, in seconds on the trace's
clock.  The metric readers work on :class:`Trace` alone, so a small
recorded trace (``tests/data``) exercises them without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: the line of a TPU plane that holds one event per executed HLO op,
#: named by the op's HLO text: "%psum.249 = f32[...]{...} all-reduce(...)"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"
_HEAD = re.compile(r"%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
#: opcodes that move data between chips (async pairs included)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv)")
#: opcodes whose event encloses the events of the ops they run
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    """One executed HLO op: its instruction name (the whole HLO text for
    a custom call, whose kernel is named there), start, end, opcode."""
    name: str
    start: float
    end: float
    kind: str = ""


def parse_hlo(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an op event's HLO text."""
    m = _HEAD.match(text)
    if not m:
        return text, ""
    k = _OPCODE.search(text, m.end())
    kind = k.group(1) if k else ""
    return (text if kind == "custom-call" else m.group(1)), kind


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Op]]
    host: Dict[str, List[Interval]]
    window: Interval
    _clipped: Dict[int, List[Op]] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def ops(self, dev: int) -> List[Op]:
        """Device ``dev``'s ops, clipped to the window."""
        if dev not in self._clipped:
            lo, hi = self.window
            self._clipped[dev] = [
                Op(o.name, max(o.start, lo), min(o.end, hi), o.kind)
                for o in self.devices[dev] if o.end > lo and o.start < hi]
        return self._clipped[dev]

    def to_json(self) -> Dict:
        return {"window": list(self.window),
                "host": {k: [list(i) for i in v]
                         for k, v in self.host.items()},
                "devices": {str(d): [[o.name, o.start, o.end, o.kind]
                                     for o in ops]
                            for d, ops in self.devices.items()}}

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        return cls(
            devices={int(k): [Op(*o) for o in v]
                     for k, v in d["devices"].items()},
            host={k: [tuple(i) for i in v] for k, v in d["host"].items()},
            window=tuple(d["window"]))


def newest_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def read_xplane(path: str, devices: Iterable[int]) -> Trace:
    """Device ops of ``devices`` and the harness's host spans."""
    from jax.profiler import ProfileData

    want = set(devices)
    dev_ops: Dict[int, List[Op]] = {d: [] for d in want}
    host: Dict[str, List[Interval]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in want:
            ops = dev_ops[int(m.group(1))]
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, kind = parse_hlo(e.name)
                    t0 = e.start_ns * 1e-9
                    ops.append(Op(name, t0, t0 + e.duration_ns * 1e-9, kind))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        t0 = e.start_ns * 1e-9
                        host.setdefault(e.name[len(HOST_PREFIX):], []).append(
                            (t0, t0 + e.duration_ns * 1e-9))
    win = host.get("window")
    if not win:
        raise ValueError("trace holds no bench:window annotation")
    for ops in dev_ops.values():
        ops.sort(key=lambda o: o.start)
    return Trace(dev_ops, host, win[0])


# ----------------------------------------------------------------------
# interval algebra
# ----------------------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The parts of ``a`` that ``b`` does not cover."""
    out: List[Interval] = []
    cut = union(b)
    i = 0
    for lo, hi in union(a):
        while i < len(cut) and cut[i][1] <= lo:
            i += 1
        cur, j = lo, i
        while j < len(cut) and cut[j][0] < hi:
            if cut[j][0] > cur:
                out.append((cur, cut[j][0]))
            cur = max(cur, cut[j][1])
            j += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: Iterable[Interval], window: Interval) -> List[Interval]:
    return subtract([window], busy)


# ----------------------------------------------------------------------
# reductions shared by several metrics
# ----------------------------------------------------------------------
def busy_s(trace: Trace, dev: int) -> float:
    return length((o.start, o.end) for o in trace.ops(dev))


def mean_busy_s(trace: Trace) -> float:
    devs = sorted(trace.devices)
    return sum(busy_s(trace, d) for d in devs) / len(devs)


def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE.match(op.kind))


def collective_intervals(ops: List[Op]) -> List[Interval]:
    """Collective time: each collective op, and for an async pair the
    whole span from ``<kind>-start`` to the next ``<kind>-done``."""
    out: List[Interval] = []
    open_: Dict[str, List[float]] = {}
    for o in ops:
        m = COLLECTIVE.match(o.kind)
        if not m:
            continue
        if o.kind.endswith("-start"):
            open_.setdefault(m.group(1), []).append(o.start)
        elif o.kind.endswith("-done") and open_.get(m.group(1)):
            out.append((open_[m.group(1)].pop(0), o.end))
        else:
            out.append((o.start, o.end))
    return out


def exposed_collective_s(trace: Trace, dev: int) -> float:
    """Collective time on ``dev`` during which no other op (a loop's
    enclosing event aside) runs there."""
    ops = trace.ops(dev)
    compute = [(o.start, o.end) for o in ops
               if not is_collective(o) and o.kind not in CONTAINERS]
    return length(subtract(collective_intervals(ops), compute))


def op_seconds(trace: Trace, pattern: str) -> Tuple[float, int]:
    """Device seconds and count of the ops whose name matches
    ``pattern``, per device (mean over the devices)."""
    rx = re.compile(pattern)
    tot, n = 0.0, 0
    for d in trace.devices:
        for o in trace.ops(d):
            if rx.search(o.name):
                tot += o.end - o.start
                n += 1
    k = len(trace.devices)
    return tot / k, n // k


def _label(op: Op) -> str:
    if op.kind == "custom-call":
        m = re.search(r'custom_call_target="([^"]+)"', op.name)
        k = re.search(r'"?kernel_name"?\s*[:=]\s*\\?"([\w.\-]+)', op.name)
        return ("custom-call:" + (k.group(1) if k else
                                  m.group(1) if m else "?"))
    return re.sub(r"\.\d+$", "", op.name) + ":" + op.kind


def self_seconds(ops: List[Op]) -> Dict[str, float]:
    """Each op's time less the time of the ops nested inside it (a
    loop's body ops inside its event), summed by op label."""
    tot: Dict[str, float] = {}
    stack: List[List] = []   # [end, label, self time]

    def close(entry):
        tot[entry[1]] = tot.get(entry[1], 0.0) + entry[2]

    for o in ops:
        while stack and stack[-1][0] <= o.start:
            close(stack.pop())
        dur = o.end - o.start
        if stack and o.end <= stack[-1][0]:
            stack[-1][2] -= dur
        stack.append([o.end, _label(o), dur])
    while stack:
        close(stack.pop())
    return tot


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` ops (labelled by instruction name without its number,
    and opcode) with the most self time, in seconds per device."""
    tot: Dict[str, float] = {}
    for d in trace.devices:
        for k, v in self_seconds(trace.ops(d)).items():
            tot[k] = tot.get(k, 0.0) + v
    k = len(trace.devices)
    return [[name, s / k] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest device-idle gaps (lowest device), each named by
    the innermost harness span that covers the gap's middle."""
    busy = [(o.start, o.end) for o in trace.ops(min(trace.devices))]
    out = []
    for a, b in gaps(busy, trace.window):
        mid, best, width = 0.5 * (a + b), "host:untraced", float("inf")
        for name, spans in trace.host.items():
            if name == "window":
                continue
            for s0, s1 in spans:
                if s0 <= mid <= s1 and s1 - s0 < width:
                    best, width = "host:" + name, s1 - s0
        out.append([best, b - a])
    out.sort(key=lambda g: -g[1])
    return out[:n]


def save(trace: Trace, path) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load(path) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
