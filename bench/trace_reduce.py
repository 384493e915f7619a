"""Reduce a profiler trace (`.xplane.pb`) to the numbers the per-layer
readers take.

What is read:
  - device planes (`/device:TPU:<n>`), line "XLA Ops": one event per device
    operation, with its start and duration in ns; line "XLA Modules": one
    event per execution of a compiled program;
  - host spans: events on any host line whose name starts with `bench.`
    (the `jax.profiler.TraceAnnotation`s of the window loop). The span
    `bench.window` bounds the traced window.

An op event is named by its HLO instruction ("%fusion.3 = f32[...] ...");
ops nest (a `while` holds its body's ops), so sums take each op's self
time, its duration less that of the ops it directly holds. An op belongs to
a scope (`bench.env_step`, `bench.policy`) when its `tf_op` stat or, failing
that, the `op_name` metadata of its instruction in the compiled program's
HLO text contains the scope's name. A Pallas kernel call is named by the
kernel function whose name its serialized body holds.
"""
from __future__ import annotations

import base64
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: an HLO instruction with its op_name metadata
_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                       r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
#: what a compiled program holds where a Pallas kernel runs on the TPU
KERNEL_CALL = "tpu_custom_call"
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
#: the instruction name that leads an op event's name
_EVENT_NAME = re.compile(r"^%?([\w.\-]+) = ")


class Op(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    op_name: str  # the scope path of the op ("" where unknown)
    kernel: str = ""  # the Pallas kernel's name where the op is one
    self_ns: float = 0.0  # duration less the ops it directly holds

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


def find_xplane(directory: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler.trace` directory."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def kernel_of(hlo_line: str, kernels: Sequence[str]) -> str:
    """The name, among `kernels`, of the Pallas kernel an HLO line calls:
    "" when the line calls none, `KERNEL_CALL` when the kernel is none of
    those."""
    if KERNEL_CALL not in hlo_line:
        return ""
    m = _BODY.search(hlo_line)
    body = base64.b64decode(m.group(1)) if m else b""
    for k in kernels:
        if k.encode() in body:
            return k
    return KERNEL_CALL


def hlo_op_names(hlo_texts: Iterable[str], kernels: Sequence[str] = ()
                 ) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> (`op_name` metadata, kernel name or "") over
    compiled HLO texts."""
    out: Dict[str, Tuple[str, str]] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = _HLO_LINE.match(line)
            if m:
                out.setdefault(m.group(1), (m.group(2),
                                            kernel_of(line, kernels)))
    return out


def with_self_time(ops: List[Op]) -> List[Op]:
    """`ops` of one device with `self_ns` set: nesting is read from the
    intervals (an op that starts inside another and ends by its end is
    held by it)."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.end_ns))
    held = [0.0] * len(ops)
    stack: List[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end_ns <= o.start_ns:
            stack.pop()
        if stack and o.end_ns <= ops[stack[-1]].end_ns:
            held[stack[-1]] += o.dur_ns
        stack.append(i)
    return [o._replace(self_ns=o.dur_ns - h) for o, h in zip(ops, held)]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: Iterable[Tuple[float, float]], t0: float,
              t1: float) -> List[Tuple[float, float]]:
    """The stretches of [t0, t1) that no interval covers."""
    gaps, cursor = [], t0
    for s, e in sorted(intervals):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < t1:
        gaps.append((cursor, t1))
    return gaps


class Trace:
    """The device ops and host spans of one traced window."""

    def __init__(self, devices: Dict[str, List[Op]], spans: List[Span],
                 modules: Sequence[Span] = ()):
        windows = [s for s in spans if s.name == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"no {WINDOW_SPAN!r} host span in the trace")
        w = windows[0]
        self.t0, self.t1 = w.start_ns, w.end_ns
        self.spans = [s for s in spans if s.name != WINDOW_SPAN]
        self.devices = {
            d: with_self_time([
                o._replace(start_ns=max(o.start_ns, self.t0),
                           end_ns=min(o.end_ns, self.t1))
                for o in ops if o.end_ns > self.t0 and o.start_ns < self.t1])
            for d, ops in devices.items()}
        if not self.devices:
            raise ValueError("no device plane in the trace")
        #: program executions inside the window, over all devices
        self.modules = [m for m in modules
                        if m.start_ns >= self.t0 and m.end_ns <= self.t1]

    @classmethod
    def from_profile(cls, pd, hlo_texts: Sequence[str] = (),
                     kernels: Sequence[str] = ()) -> "Trace":
        """Build from a `jax.profiler.ProfileData`, naming ops through the
        compiled programs' HLO texts and the Pallas kernels `kernels`."""
        op_names = hlo_op_names(hlo_texts, kernels)
        devices: Dict[str, List[Op]] = {}
        spans: List[Span] = []
        modules: List[Span] = []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                ops = devices.setdefault(plane.name, [])
                for line in plane.lines:
                    if line.name == MODULES_LINE:
                        modules.extend(Span(ev.name, ev.start_ns, ev.end_ns)
                                       for ev in line.events)
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        m = _EVENT_NAME.match(ev.name)
                        name = m.group(1) if m else ev.name
                        hlo_name, kernel = op_names.get(name, ("", ""))
                        op_name = str(dict(ev.stats).get("tf_op") or
                                      hlo_name)
                        ops.append(Op(name, ev.start_ns, ev.end_ns, op_name,
                                      kernel))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append(Span(ev.name, ev.start_ns,
                                              ev.end_ns))
        return cls(devices, spans, modules)

    @classmethod
    def from_file(cls, path: str, hlo_texts: Sequence[str] = (),
                  kernels: Sequence[str] = ()) -> "Trace":
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_file(path), hlo_texts,
                                kernels)

    # -- reductions ------------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        return sum(union_ns((o.start_ns, o.end_ns) for o in ops)
                   for ops in self.devices.values()) * 1e-9 / self.n_devices

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def ops(self, *, scope: Optional[str] = None,
            kernel: Optional[str] = None) -> List[Op]:
        """Ops of every device, filtered by scope and/or kernel name (""
        selects the ops that are no kernel)."""
        out = []
        for ops in self.devices.values():
            for o in ops:
                if scope is not None and scope not in o.op_name:
                    continue
                if kernel is not None and kernel != o.kernel:
                    continue
                out.append(o)
        return out

    def seconds(self, ops: Iterable[Op]) -> float:
        """Summed self seconds of `ops`, averaged over the devices."""
        return sum(o.self_ns for o in ops) * 1e-9 / self.n_devices

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ops that took most device self time (summed by name, with
        the kernel's name where the op is a kernel call, averaged over the
        devices)."""
        by_name: Dict[str, float] = {}
        for ops in self.devices.values():
            for o in ops:
                key = f"{o.name} ({o.kernel})" if o.kernel else o.name
                by_name[key] = by_name.get(key, 0.0) + o.self_ns
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v * 1e-9 / self.n_devices) for k, v in top]

    def named_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps of the first device, each named by the host
        span that covers its middle ("host" where none does)."""
        ops = self.devices[sorted(self.devices)[0]]
        gaps = idle_gaps(((o.start_ns, o.end_ns) for o in ops),
                         self.t0, self.t1)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = 0.5 * (s + e)
            covering = [sp for sp in self.spans
                        if sp.start_ns <= mid < sp.end_ns]
            # the innermost span: the one that started last
            name = (max(covering, key=lambda sp: sp.start_ns).name
                    if covering else "host")
            out.append((name, (e - s) * 1e-9))
        return out
