"""The program's own instrumentation in a profiler trace.

The program (``repro.tracing``) writes two things into a trace:

* host spans named ``repro:<name>`` with metadata (step, request id,
  token index, rows), on the same clock as the device's operations;
* a name-stack scope on every HLO op (``jax.named_scope``), which XLA
  keeps as the op's ``op_name`` metadata.  The trace carries each
  compiled module's HLO (``/host:metadata``); :func:`read` joins every
  executed op to its ``op_name`` by module and instruction name.

:func:`read` returns a :class:`ProgramTrace`; the reductions below work
on it alone, so a small recorded trace (``tests/data``) exercises them
without a chip.  A program without this instrumentation gives a trace
with no ``repro:`` span and no scope, and every reduction returns None.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import json
import re
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import trace as tr

SPAN_PREFIX = "repro:"
#: the program's device scopes (``repro.tracing.SCOPES``)
SCOPES = ("embed", "attention", "mlp", "moe", "ssm", "ssd", "rglru",
          "head_loss", "weight_cast", "lambda_decode", "codec",
          "optimizer")
#: scopes that are not the model's forward or backward pass
STEP_SCOPES = ("optimizer", "lambda_decode", "codec")
MODEL_SCOPES = tuple(s for s in SCOPES if s not in STEP_SCOPES)
#: the train step's host spans that keep the device waiting
TRAIN_HOST = ("train.pattern", "train.build_batch", "train.put",
              "train.dispatch")
#: the line of a TPU plane with one event per executed module, named
#: ``<module>(<program id>)`` as the module's HLO in the trace is
MODULES_LINE = "XLA Modules"
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")

Span = Tuple[float, float, Dict]


@dataclasses.dataclass
class ScopedOp:
    """One executed op and the ``op_name`` its HLO instruction carries."""
    name: str
    start: float
    end: float
    kind: str = ""
    op_name: str = ""


@dataclasses.dataclass
class ProgramTrace:
    devices: Dict[int, List[ScopedOp]]
    spans: Dict[str, List[Span]]     # repro: spans, by name without prefix
    window: tr.Interval
    #: the harness's own bench: spans, as :class:`trace.Trace` holds them
    harness: Dict[str, List[tr.Interval]] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _cache: Dict = dataclasses.field(default_factory=dict, repr=False,
                                     compare=False)

    def ops(self, dev: int) -> List[ScopedOp]:
        """Device ``dev``'s ops, clipped to the window."""
        if dev not in self._cache:
            lo, hi = self.window
            self._cache[dev] = [
                dataclasses.replace(o, start=max(o.start, lo),
                                    end=min(o.end, hi))
                for o in self.devices[dev] if o.end > lo and o.start < hi]
        return self._cache[dev]

    def in_window(self, name: str) -> List[Span]:
        """Spans ``name`` that end inside the window."""
        lo, hi = self.window
        return [s for s in self.spans.get(name, []) if lo < s[1] <= hi]

    def harness_trace(self) -> tr.Trace:
        """The same ops and the harness's spans as a :class:`trace.Trace`,
        for the harness's own reductions."""
        return tr.Trace({d: [tr.Op(o.name, o.start, o.end, o.kind)
                             for o in ops]
                         for d, ops in self.devices.items()},
                        dict(self.harness, window=[self.window]),
                        self.window)

    def to_json(self) -> Dict:
        """Times as whole ns after the window's start, each op_name once."""
        t0 = self.window[0]
        names = sorted({o.op_name for ops in self.devices.values()
                        for o in ops})
        idx = {n: i for i, n in enumerate(names)}

        def ns(t):
            return round((t - t0) * 1e9)

        return {"t0": t0, "window": [0, ns(self.window[1])],
                "op_names": names,
                "spans": {k: [[ns(a), ns(b), m] for a, b, m in v]
                          for k, v in self.spans.items()},
                "devices": {str(d): [[o.name, ns(o.start), ns(o.end), o.kind,
                                      idx[o.op_name]] for o in ops]
                            for d, ops in self.devices.items()}}

    @classmethod
    def from_json(cls, d: Dict) -> "ProgramTrace":
        t0, names = d["t0"], d["op_names"]

        def s(ns):
            return t0 + ns * 1e-9

        return cls(
            devices={int(k): [ScopedOp(n, s(a), s(b), kind, names[i])
                              for n, a, b, kind, i in v]
                     for k, v in d["devices"].items()},
            spans={k: [(s(a), s(b), m) for a, b, m in v]
                   for k, v in d["spans"].items()},
            window=(s(d["window"][0]), s(d["window"][1])))


def save(t: ProgramTrace, path) -> None:
    with open(path, "w") as f:
        json.dump(t.to_json(), f, separators=(",", ":"))


def load(path) -> ProgramTrace:
    with open(path) as f:
        return ProgramTrace.from_json(json.load(f))


# ----------------------------------------------------------------------
# the xplane: protobuf wire format, as far as the module HLO needs
# ----------------------------------------------------------------------
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message; length-delimited values
    are memoryviews, the others ints (fixed-width ones skipped)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} unsupported")


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _op_names(hlo_proto) -> Dict[str, str]:
    """{instruction name: op_name} of a serialized ``HloProto``."""
    out: Dict[str, str] = {}
    for f, module in _fields(hlo_proto):
        if f != 1:                                   # hlo_module
            continue
        for f2, comp in _fields(module):
            if f2 != 3:                              # computations
                continue
            for f3, instr in _fields(comp):
                if f3 != 2:                          # instructions
                    continue
                name = op_name = ""
                for f4, v in _fields(instr):
                    if f4 == 1:
                        name = _str(v)
                    elif f4 == 7:                    # metadata
                        for f5, w in _fields(v):
                            if f5 == 2:              # op_name
                                op_name = _str(w)
                if op_name:
                    out[name] = op_name
    return out


def module_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{module: {instruction: op_name}} of every module whose HLO the
    trace holds: event metadata of ``/host:metadata`` named
    ``<module>(<program id>)``, as the device's ``XLA Modules`` events
    are, with a ``Hlo Proto`` stat."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and _str(v) == "/host:metadata"
                   for k, v in fields):
            continue
        proto_stat = None
        for k, v in fields:                          # stat_metadata
            if k == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                if _str(meta.get(2, b"")) == "Hlo Proto":
                    proto_stat = meta.get(1, 0)
        for k, v in fields:                          # event_metadata
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            name = next((_str(w) for f2, w in meta if f2 == 2), "")
            for f2, stat in meta:
                if f2 != 5:
                    continue
                s = dict(_fields(stat))
                if s.get(1, 0) == proto_stat and 6 in s:
                    out[name] = _op_names(s[6])
    return out


def read(path: str, devices, window: Optional[tr.Interval] = None
         ) -> ProgramTrace:
    """The ``repro:`` spans and the devices' ops, each with its op_name.

    ``window`` defaults to the harness's ``bench:window`` span."""
    from jax.profiler import ProfileData

    names = module_op_names(path)
    want = set(devices)
    dev_ops: Dict[int, List[ScopedOp]] = {d: [] for d in want}
    spans: Dict[str, List[Span]] = {}
    harness: Dict[str, List[tr.Interval]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in want:
            _device_ops(plane, names, dev_ops[int(m.group(1))])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    t0 = e.start_ns * 1e-9
                    t1 = t0 + e.duration_ns * 1e-9
                    if e.name.startswith(SPAN_PREFIX):
                        spans.setdefault(e.name[len(SPAN_PREFIX):], []).append(
                            (t0, t1, dict(e.stats)))
                    elif e.name.startswith(tr.HOST_PREFIX):
                        harness.setdefault(e.name[len(tr.HOST_PREFIX):],
                                           []).append((t0, t1))
    for ops in dev_ops.values():
        ops.sort(key=lambda o: o.start)
    window = window or harness.pop("window", [None])[0]
    if window is None:
        raise ValueError("no window given and no bench:window span")
    harness.pop("window", None)
    return ProgramTrace(dev_ops, spans, window, harness)


def _device_ops(plane, names: Dict[str, Dict[str, str]],
                out: List[ScopedOp]) -> None:
    """A TPU plane's op events, each joined to the op_names of the
    module whose ``XLA Modules`` event encloses it."""
    modules = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                      e.name)
                     for line in plane.lines if line.name == MODULES_LINE
                     for e in line.events)
    starts = [m[0] for m in modules]
    for line in plane.lines:
        if line.name != tr.OPS_LINE:
            continue
        for e in line.events:
            name, kind = tr.parse_hlo(e.name)
            t0 = e.start_ns * 1e-9
            op = ScopedOp(name, t0, t0 + e.duration_ns * 1e-9, kind)
            i = bisect.bisect_right(starts, t0) - 1
            if i >= 0 and t0 <= modules[i][1]:
                key = name.split(" ", 1)[0].lstrip("%")
                op.op_name = names.get(modules[i][2], {}).get(key, "")
            out.append(op)


# ----------------------------------------------------------------------
# scopes
# ----------------------------------------------------------------------
def components(op_name: str) -> List[str]:
    """Name-stack components with ``jit(..)``, ``jvp(..)``,
    ``transpose(..)`` and like wrappers stripped."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        out.append(part)
    return out


@functools.lru_cache(maxsize=None)
def scope_of(op_name: str) -> Tuple[str, bool]:
    """(innermost scope or "", whether the op is in a backward pass:
    under ``transpose(``, remat's recompute included)."""
    inner = ""
    for part in components(op_name):
        if part in SCOPES:
            inner = part
    backward = any(p.startswith("transpose(") for p in op_name.split("/"))
    return inner, backward


def self_times(ops: List[ScopedOp]) -> List[float]:
    """Each op's time less that of the ops nested inside it (a loop's
    body inside its event), as :func:`trace.self_seconds` counts it."""
    out = [0.0] * len(ops)
    stack: List[Tuple[float, int]] = []   # (end, index)
    for i, o in enumerate(ops):
        while stack and stack[-1][0] <= o.start:
            stack.pop()
        dur = o.end - o.start
        if stack and o.end <= stack[-1][0]:
            out[stack[-1][1]] -= dur
        out[i] += dur
        stack.append((o.end, i))
    return out


def scope_seconds(t: ProgramTrace) -> Dict[str, float]:
    """Device self time per chip (mean over chips) by class: each scope
    split into ``<scope>.fwd`` and ``<scope>.bwd``, and ``unscoped``."""
    if "seconds" not in t._cache:
        tot: Dict[str, float] = {}
        for d in t.devices:
            ops = t.ops(d)
            for o, s in zip(ops, self_times(ops)):
                scope, bwd = scope_of(o.op_name)
                key = (scope + (".bwd" if bwd else ".fwd")) if scope \
                    else "unscoped"
                tot[key] = tot.get(key, 0.0) + s
        k = len(t.devices)
        t._cache["seconds"] = {key: v / k for key, v in tot.items()}
    return t._cache["seconds"]


def layer_ms(t: ProgramTrace, span: str, scopes=MODEL_SCOPES,
             backward=None) -> Optional[float]:
    """ms per ``span`` in the window of self time under ``scopes``
    (innermost scope), in the backward pass, the forward, or both
    (``backward`` None); None where no op carries a scope."""
    secs = scope_seconds(t)
    n = len(t.in_window(span))
    if not n or set(secs) <= {"unscoped"}:
        return None
    return 1e3 * sum(
        v for key, v in secs.items()
        if key.split(".")[0] in scopes
        and (backward is None or key.endswith(".bwd") == backward)) / n


def span_ms(t: ProgramTrace, names, per: str) -> Optional[float]:
    """Host ms in spans ``names`` per ``per`` span, in the window."""
    n = len(t.in_window(per))
    if not n:
        return None
    return 1e3 * sum(b - a for name in names
                     for a, b, _ in t.in_window(name)) / n


def idle_gaps(t: ProgramTrace, n: int = 10) -> List[List]:
    """The ``n`` longest device-idle gaps (lowest device), each named by
    the innermost ``repro:`` span that covers its middle."""
    busy = [(o.start, o.end) for o in t.ops(min(t.devices))]
    out = []
    for a, b in tr.gaps(busy, t.window):
        mid, best, width = 0.5 * (a + b), "host:untraced", float("inf")
        for name, spans in t.spans.items():
            for s0, s1, _ in spans:
                if s0 <= mid <= s1 and s1 - s0 < width:
                    best, width = SPAN_PREFIX + name, s1 - s0
        out.append([best, b - a])
    out.sort(key=lambda g: -g[1])
    return out[:n]


def top_unscoped(t: ProgramTrace, n: int = 10) -> List[List]:
    """The unscoped ops with the most self time (s per chip), labelled
    as :func:`trace.top_ops` labels them, with one op_name each."""
    tot: Dict[str, List] = {}
    for d in t.devices:
        ops = t.ops(d)
        for o, s in zip(ops, self_times(ops)):
            if scope_of(o.op_name)[0]:
                continue
            label = tr._label(tr.Op(o.name, o.start, o.end, o.kind))
            e = tot.setdefault(label, [0.0, o.op_name])
            e[0] += s
    k = len(t.devices)
    return [[label, s / k, op_name] for label, (s, op_name) in
            sorted(tot.items(), key=lambda kv: -kv[1][0])[:n]]
