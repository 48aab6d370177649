"""Device time by the program's named scopes.

The program names the phases of the emulation with ``jax.named_scope``
(``repro.obs.spans.SCOPES``): each op traced under a scope carries it as a
segment of its HLO ``op_name`` metadata, ``jit(spmv_bell)/spmv.gather/gather``.
A TPU trace names a device op only by the text of its HLO instruction; the
``op_name`` is in the program's compiled HLO, which the profiler keeps in the
``/host:metadata`` plane, one ``Hlo Proto`` per program under the program's
name (``jit_spmv_bell(12)``).  The device's ``XLA Modules`` line says which
program ran when.  So a device op on the ``XLA Ops`` line is named by

    the program running at its start -> its instruction of that name in the
    program's HLO -> that instruction's op_name.

``scope_seconds`` sums device time by scope from those three pieces;
``from_file`` reads them from an ``.xplane.pb``.  The HLO is read with a small
protobuf decoder, so nothing here imports a TPU or TensorFlow library.  A
fusion carries the op_name XLA gave it (that of one of the ops it fused), so
a fusion that straddles two scopes counts under that one.

Times are in seconds.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterator, List, Sequence, Tuple

from bench.trace import DEVICE_PLANE, OPS_LINE, _union

MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"

# (name, start ns, end ns)
Timed = Tuple[str, float, float]


# -- protobuf wire format -------------------------------------------------------

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field (fixed-width fields are
    skipped)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _instruction_op_names(hlo_proto: memoryview) -> Dict[str, str]:
    """{instruction name: op_name} of every computation of an ``HloProto``
    (hlo_module=1; computations=3; instructions=2; name=1, metadata=7;
    OpMetadata op_name=2)."""
    out: Dict[str, str] = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f, comp in _fields(module):
            if f != 3:
                continue
            for f, inst in _fields(comp):
                if f != 2:
                    continue
                name = op_name = ""
                for g, v in _fields(inst):
                    if g == 1:
                        name = _text(v)
                    elif g == 7:
                        op_name = next((_text(m) for h, m in _fields(v) if h == 2), "")
                if name and op_name:
                    out[name] = op_name
    return out


def program_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{program name: {instruction name: op_name}} from the ``Hlo Proto``s
    of a serialized XSpace's ``/host:metadata`` plane; {} where it has none
    (XSpace planes=1; XPlane name=2, event_metadata=4, stat_metadata=5;
    XEventMetadata name=2, stats=5; XStat metadata_id=1, bytes_value=6)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if next((_text(v) for g, v in fields if g == 2), "") != METADATA_PLANE:
            continue
        stat_names = {}
        for g, entry in fields:
            if g == 5:
                md = dict(_fields(entry))
                stat_names[md.get(1, 0)] = next(
                    (_text(v) for h, v in _fields(md[2]) if h == 2), "") if 2 in md else ""
        for g, entry in fields:
            if g != 4:
                continue
            md = dict(_fields(entry)).get(2)
            if md is None:
                continue
            name, protos = "", []
            for h, v in _fields(md):
                if h == 2:
                    name = _text(v)
                elif h == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT and 6 in stat:
                        protos.append(stat[6])
            for proto in protos:
                out.setdefault(name, {}).update(_instruction_op_names(proto))
    return out


# -- device time by scope --------------------------------------------------------

_INSTRUCTION = re.compile(r"^(?:ROOT\s+)?%?([\w.-]+)(?:\s+=|$)")


def instruction_name(text: str) -> str:
    """The instruction's name in a device op's trace name, which may be the
    whole instruction text (``%fusion.1 = s32[...] fusion(...)``)."""
    m = _INSTRUCTION.match(text.strip())
    return m.group(1) if m else text


def has_scope(op_name: str, scope: str) -> bool:
    return scope in op_name.split("/")


def scope_seconds(devices: Sequence[Tuple[Sequence[Timed], Sequence[Timed]]],
                  op_names: Dict[str, Dict[str, str]], scopes: Sequence[str],
                  lo: float, hi: float) -> Dict[str, float]:
    """Device seconds in [lo, hi] in which an op under each scope ran, the
    union of its ops' intervals (a loop's op spans the ops of its body),
    averaged over the devices; ``""`` collects the ops under none of them.

    ``devices`` holds, per device, its ops and its programs, each as
    (name, start ns, end ns); ``op_names`` is ``program_op_names``."""
    total = {s: 0.0 for s in list(scopes) + [""]}
    for ops, modules in devices:
        modules = sorted(modules, key=lambda m: m[1])
        starts = [m[1] for m in modules]
        intervals: Dict[str, List[Tuple[float, float]]] = {s: [] for s in total}
        for text, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            k = bisect.bisect_right(starts, s) - 1
            table = op_names.get(modules[k][0], {}) if k >= 0 and s < modules[k][2] else {}
            op_name = table.get(instruction_name(text), "")
            scope = next((sc for sc in scopes if has_scope(op_name, sc)), "")
            intervals[scope].append((s, e))
        for scope, iv in intervals.items():
            total[scope] += sum(e - s for s, e in _union(iv))
    n = max(1, len(devices))
    return {k: v * 1e-9 / n for k, v in total.items()}


def device_spans(path: str) -> List[Tuple[List[Timed], List[Timed]]]:
    """Per TPU device of an ``.xplane.pb``, its ops and its programs."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: [(ev.name, float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns))
                             for ev in line.events]
                 for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)}
        out.append((lines.get(OPS_LINE, []), lines.get(MODULES_LINE, [])))
    return out


def from_file(path: str, scopes: Sequence[str], lo: float, hi: float) -> Dict[str, float]:
    """``scope_seconds`` of an ``.xplane.pb`` over the window [lo, hi] ns."""
    with open(path, "rb") as fh:
        op_names = program_op_names(fh.read())
    return scope_seconds(device_spans(path), op_names, scopes, lo, hi)
