"""Reduction of a profiler trace to the benchmark's device numbers.

A traced run wraps its measured window in the host span ``bench.window`` and
each call, CG set and operand draw in a ``bench.*`` span of its own
(``jax.profiler.TraceAnnotation``).  The profiler puts those spans and the
device's operations on one clock.  From them this module takes:

  * the window: the ``bench.window`` span;
  * busy time: the union of the intervals in which an operation ran on a
    device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped
    to the window and averaged over the devices;
  * kernel time: the summed device time of a kernel's operations, named
    ``<name>`` or ``<name>.<n>`` in the compiled program (a Pallas kernel's
    custom call takes the name of the jitted function around its
    ``pallas_call``: ``gemm_hilo.1``, ``spmv_bell.1``).  A TPU trace gives
    an operation the whole text of its HLO instruction,
    ``%gemm_hilo.1 = f32[...] custom-call(...), custom_call_target=...``;
    the reduction names it by the instruction's name, and the breakdown by
    that name, its result type, its opcode and a custom call's target;
  * the device operations that took most time, and the longest idle gaps,
    each named by what the host was doing in it: the innermost host event at
    the gap's middle on the thread that ran the window, under its ``bench.*``
    span.

Times are in seconds.  Nothing here imports a TPU library, so the reduction
is tested on the CPU against a trace recorded on the chip
(``bench/tests/data``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns on the profile's clock
    end: float
    label: str = ""       # a device op's result type and opcode, for the breakdown


_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_LAYOUT = re.compile(r"\{[^{}]*\}")
_RESULT = re.compile(r"(\(.*?\)|\S+)\s+([\w-]+)\(")


def device_event(text: str, start: float, end: float) -> Event:
    """A device operation named by its HLO instruction's name, where the
    trace gives the instruction's whole text (``%fusion.3 = f32[...] ...``),
    and labelled by its result type and opcode with the layouts left out:
    ``fusion.3 = f32[64,7] fusion``, a custom call's target after them."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return Event(text, start, end)
    name = head.split()[-1].lstrip("%")        # drops a leading ``ROOT``
    label = name
    result = _RESULT.match(_LAYOUT.sub("", rest))
    if result:
        label += f" = {result.group(1)} {result.group(2)}"
    target = _TARGET.search(rest)
    if target:
        label += f" {target.group(1)}"
    return Event(name, start, end, label)


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


class Trace:
    """Device operations per chip and host events, reduced to one window."""

    def __init__(self, devices: List[List[Event]], host: Dict[str, List[Event]]):
        self.devices = devices
        self.host = host
        spans = [(tid, ev) for tid, evs in host.items() for ev in evs
                 if ev.name == WINDOW_SPAN]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span, found {len(spans)}")
        self.thread, w = spans[0]
        self.lo, self.hi = w.start, w.end
        self._busy = []
        for evs in devices:
            clipped = [c for ev in evs
                       if (c := _clip(ev.start, ev.end, self.lo, self.hi))]
            self._busy.append(_union(clipped))

    # -- loading --------------------------------------------------------

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        """Read an ``.xplane.pb`` file (or the newest one under a directory)."""
        from jax.profiler import ProfileData

        if os.path.isdir(path):
            found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                     recursive=True), key=os.path.getmtime)
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = found[-1]
        data = ProfileData.from_file(path)
        devices: List[List[Event]] = []
        host: Dict[str, List[Event]] = {}
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        devices.append([device_event(ev.name, *_span(ev))
                                        for ev in line.events])
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    host[line.name] = [Event(ev.name, *_span(ev))
                                       for ev in line.events]
        return cls(devices, host)

    # -- numbers ----------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def chips(self) -> int:
        return len(self.devices)

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran on a device, averaged over
        the devices; 0 when the trace holds no device."""
        if not self._busy:
            return 0.0
        return sum(e - s for b in self._busy for s, e in b) * 1e-9 / len(self._busy)

    def kernel_s(self, name: str) -> float:
        """Device seconds of the operations named ``name`` or ``name.<n>``,
        inside the window, averaged over the devices."""
        if not self.devices:
            return 0.0
        op = re.compile(re.escape(name) + r"(\.\d+)?")
        total = 0.0
        for evs in self.devices:
            for ev in evs:
                if op.fullmatch(ev.name):
                    c = _clip(ev.start, ev.end, self.lo, self.hi)
                    if c:
                        total += c[1] - c[0]
        return total * 1e-9 / len(self.devices)

    def top_device_ops(self, n: int = 10) -> List[List]:
        """[[operation name, device seconds], ...], most time first, summed
        over the window and averaged over the devices, each named by its
        label where it has one."""
        acc: Dict[str, float] = {}
        for evs in self.devices:
            for ev in evs:
                c = _clip(ev.start, ev.end, self.lo, self.hi)
                if c:
                    key = ev.label or ev.name
                    acc[key] = acc.get(key, 0.0) + (c[1] - c[0]) * 1e-9
        k = max(1, len(self.devices))
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s / k] for name, s in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest intervals of the window in which the first
        device ran nothing, [[what the host was doing, seconds], ...]."""
        if not self._busy:
            return []
        edges = [self.lo] + [t for s, e in self._busy[0] for t in (s, e)] + [self.hi]
        gaps = [(b - a, a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        gaps.sort(reverse=True)
        return [[self._host_label((a + b) / 2), g * 1e-9] for g, a, b in gaps[:n]]

    def _host_label(self, t: float) -> str:
        """``<bench span>/<innermost host event>`` at time ``t`` on the
        thread that ran the window."""
        span, inner = "", ""
        best_span = best_inner = float("inf")
        for ev in self.host.get(self.thread, ()):
            if ev.start <= t <= ev.end and ev.name != WINDOW_SPAN:
                d = ev.end - ev.start
                if ev.name.startswith(SPAN_PREFIX):
                    if d < best_span:
                        span, best_span = ev.name, d
                elif d < best_inner:
                    inner, best_inner = ev.name, d
        label = "/".join(x for x in (span, inner) if x)
        return label or "host:none"


def _span(ev) -> Tuple[float, float]:
    return float(ev.start_ns), float(ev.start_ns + ev.duration_ns)

