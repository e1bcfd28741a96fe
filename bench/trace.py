"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes an XSpace (``*.xplane.pb``); ``jax.profiler.
ProfileData`` reads it.  Device planes are ``/device:TPU:<n>``; on each,
the ``XLA Ops`` line holds one event per operation the device ran, with
its start and duration in ns on the same clock as the host planes.  An
event's name is the operation's HLO text, ``%<instruction> = <shape>
<opcode>(...)``; a Pallas kernel is a ``custom-call`` whose
``custom_call_target`` is ``tpu_custom_call`` and whose instruction is
named after the jitted function that calls it.  Control flow nests: a
``while`` or ``conditional`` event spans the events of its body.  The
window is the host span the harness opens around the measured period
(``bench.window``).

* busy: the union of the op intervals inside the window, averaged over
  the device planes;
* per-op device time: each op's self time inside the window (its
  duration less that of the ops nested in it), by instruction and
  kind;
* breakdown: the ten ops that took most self time, and the ten longest
  gaps between ops on the first device, each named by the host span
  that overlaps it most (what the host was doing meanwhile).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
PALLAS_TARGET = "tpu_custom_call"
TOP = 10
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_SUFFIX = re.compile(r"\.\d+$")


def op_key(hlo: str) -> Tuple[str, str]:
    """(instruction, kind) of an op event's HLO text: ``("_flash.5",
    "tpu_custom_call")``, ``("fusion.290", "fusion")``.  A custom call's
    kind is its target, any other op's its opcode."""
    name, _, rest = hlo.partition(" = ")
    target = _TARGET.search(rest)
    if target:
        kind = target.group(1)
    else:
        opcode = _OPCODE.search(rest)
        kind = opcode.group(1) if opcode else ""
    return name.strip().lstrip("%"), kind


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    ops: Dict[Tuple[str, str], Tuple[float, int]]  # -> (self s, count)
    breakdown: dict

    def kernel_time(self, names) -> Tuple[float, int]:
        """(seconds, calls) of the Pallas kernels whose instruction is
        named after one of ``names`` (the jitted functions that call
        them)."""
        secs, calls = 0.0, 0
        for (instr, kind), (s, n) in self.ops.items():
            if kind == PALLAS_TARGET and _SUFFIX.sub("", instr) in names:
                secs += s
                calls += n
        return secs, calls


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def self_times(events: List[Tuple[str, float, float]]) -> List[float]:
    """Each event's duration less the durations of the events directly
    nested in it (events nest; they never overlap in part)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [b - a for _, a, b in events]
    stack: List[int] = []
    for i in order:
        _, a, b = events[i]
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def reduce_profile(profile, window: str) -> Reduced:
    devices, host = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if ops:
                devices.append(list(_events(ops[0])))
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                host.extend(_events(ln))
    if not devices:
        names = [p.name for p in profile.planes]
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in "
                         f"the trace; planes: {names}")
    spans = [(a, b) for name, a, b in host if name == window]
    if not spans:
        raise ValueError(f"no host span {window!r} in the trace")
    w0, w1 = min(a for a, _ in spans), max(b for _, b in spans)
    host = [(n, a, b) for n, a, b in host if n != window and b > w0
            and a < w1]

    busy, ops, gaps = [], {}, []
    for i, evs in enumerate(devices):
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in evs
                   if b > w0 and a < w1]
        merged = _union([(a, b) for _, a, b in clipped])
        busy.append(sum(b - a for a, b in merged))
        for (n, _, _), own in zip(clipped, self_times(clipped), strict=True):
            key = op_key(n)
            s, c = ops.get(key, (0.0, 0))
            ops[key] = (s + own * 1e-9, c + 1)
        if i == 0:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][0])[:TOP]
    breakdown = {
        "device_ops": [[f"{instr} ({kind})", s]
                       for (instr, kind), (s, _) in top_ops],
        "idle_gaps": [[_host_label(host, a, b), (b - a) * 1e-9]
                      for a, b in gaps]}
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9,
                   ops=ops, breakdown=breakdown)


def _host_label(host, a: float, b: float) -> str:
    best, name = 0.0, "no host span"
    for n, ha, hb in host:
        over = min(b, hb) - max(a, ha)
        if over > best:
            best, name = over, n
    return name


def reduce(path: str, window: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), window)
