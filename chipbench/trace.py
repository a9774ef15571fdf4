"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

It reads the trace with ``jax.profiler.ProfileData`` and keeps the window
between the benchmark's two host markers (``spans.WINDOW_OPEN`` and
``WINDOW_CLOSE``). From each TPU plane it takes:

* busy time: the union of the intervals of the ``XLA Ops`` line's events;
* per-module device time and executions, from the ``XLA Modules`` line,
  for each module asked for (``jit__decode`` matches ``jit__decode(17)``),
  summed over the planes;
* the device operations that took most time of their own (a ``while``
  less the operations of its body), named by module and operation
  (``short_op_name``);
* idle gaps, the complement of busy time, each named by what the host was
  doing in most of it (``_label``): a block or a warm-up (the benchmark's
  host annotations ``spans.BLOCK`` and ``spans.WARMUP``), waiting between
  them with a job in the system, or no job in the system.

Busy time counts the ``XLA Ops`` line only: the ``Async XLA Ops`` line's
copies overlap the computation and do not make the device busy alone.

Busy time is averaged over the TPU planes that ran anything.
A module that was asked for and never ran is reported as 0 executions, so
that a reader that expected it can fail loudly rather than read 0.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

from chipbench.spans import BLOCK, WARMUP, WINDOW_CLOSE, WINDOW_OPEN

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
TOP = 10


class TraceError(RuntimeError):
    """The trace lacks what the reduction needs."""


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    module_s: Dict[str, float] = field(default_factory=dict)
    module_n: Dict[str, int] = field(default_factory=dict)
    ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    idle_by_label: Dict[str, float] = field(default_factory=dict)

    def module_mean_s(self, module: str) -> float:
        n = self.module_n.get(module, 0)
        if n == 0:
            raise TraceError(f"module {module!r} never ran in the traced "
                             f"window; modules seen: {self.module_n}")
        return self.module_s[module] / n

    def breakdown(self) -> Dict:
        return {"device_ops": [[n, s] for n, s in self.ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            yield from line.events


def _module_of(name: str, modules: Sequence[str]):
    for m in modules:
        if name == m or name.startswith(m + "("):
            return m
    return None


def _markers(planes) -> Tuple[float, float]:
    found: Dict[str, float] = {}
    for plane in planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in (WINDOW_OPEN, WINDOW_CLOSE):
                    found[ev.name] = ev.start_ns
    if len(found) < 2:
        raise TraceError(f"window markers missing from the trace: found "
                         f"{sorted(found)}")
    return found[WINDOW_OPEN], found[WINDOW_CLOSE]


def _host_spans(planes, lo: float, hi: float):
    spans = []
    for plane in planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in (BLOCK, WARMUP):
                    c = _clip(ev.start_ns, ev.end_ns, lo, hi)
                    if c:
                        spans.append((c[0], c[1], ev.name.split(".")[-1]))
    return sorted(spans)


def _label(gap: Tuple[float, float], spans, starts: List[float],
           in_system: List[Tuple[float, float]]) -> str:
    """What the host was doing in most of ``gap``: the host span that
    overlaps it most (``block``, ``warmup``), or, where no span covers
    most of it, ``waiting`` if a job was in the system at its middle and
    ``no job`` if none was. Spans are sorted by start and come one after
    another from the service's driver thread, so the search stops at the
    first that ends before the gap."""
    best, label, covered = 0.0, None, 0.0
    i = bisect.bisect_left(starts, gap[1]) - 1
    while i >= 0:
        a, b, name = spans[i]
        overlap = min(b, gap[1]) - max(a, gap[0])
        if overlap > 0:
            covered += overlap
            if overlap > best:
                best, label = overlap, name
        if b <= gap[0]:
            break
        i -= 1
    if label is not None and best >= (gap[1] - gap[0]) - covered:
        return label
    mid = (gap[0] + gap[1]) / 2
    return "waiting" if any(a <= mid <= b for a, b in in_system) \
        else "no job"


def short_op_name(name: str) -> str:
    """``%convert.19 = bf16[8,4096]{...} convert(...)`` as
    ``%convert.19 convert bf16[8,4096]``; a tuple's type as ``(...)``."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:80]
    if rest.startswith("("):
        depth = 0
        for k, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        typ, rest = "(...)", rest[k + 1:]
    else:
        typ, _, rest = rest.partition(" ")
        typ = typ.split("{")[0]
    opcode = rest.strip().split("(")[0].split(" ")[0]
    return f"{head} {opcode} {typ}"


def _self_times(named):
    """Events sorted by start, and the time of each that none of the
    events nested in it covers (a ``while`` holds its body's operations)."""
    named = sorted(named, key=lambda e: (e[0], -e[1]))
    own = [b - a for a, b, _ in named]
    stack: List[int] = []
    for i, (a, b, _) in enumerate(named):
        while stack and named[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    return named, own


def _module_at(modules, starts: List[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][1] >= t:
        return modules[i][2]
    return "?"


def reduce(profile, modules: Sequence[str],
           in_system: Sequence[Tuple[float, float]] = ()) -> Reduction:
    """Reduce a ``ProfileData`` to the window between the markers.
    ``in_system`` holds the intervals, in seconds after the window opened,
    in which some job was in the system (they name idle gaps)."""
    planes = list(profile.planes)
    lo, hi = _markers(planes)
    window_s = (hi - lo) / 1e9
    spans = _host_spans(planes, lo, hi)
    jobs_in = sorted((lo + a * 1e9, lo + b * 1e9) for a, b in in_system)
    busy_planes, gaps = [], []
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    ops: Dict[str, float] = defaultdict(float)
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    if not devices:
        raise TraceError(f"no TPU plane in the trace: "
                         f"{[p.name for p in planes]}")
    for plane in devices:
        runs = sorted((ev.start_ns, ev.end_ns, ev.name.split("(")[0])
                      for ev in _events(plane, MODULES_LINE))
        run_starts = [r[0] for r in runs]
        for a, b, name in runs:
            m = _module_of(name, modules)
            if m is not None and lo <= a < hi:
                module_s[m] += (b - a) / 1e9
                module_n[m] += 1
        named = []
        for ev in _events(plane, OPS_LINE):
            c = _clip(ev.start_ns, ev.end_ns, lo, hi)
            if c:
                named.append((c[0], c[1], ev.name))
        if not named:
            continue
        intervals = [(a, b) for a, b, _ in named]
        for (a, _, name), t in zip(*_self_times(named)):
            key = (_module_at(runs, run_starts, a) + ": "
                   + short_op_name(name))
            ops[key] += t / 1e9
        busy = _union(intervals)
        busy_planes.append(sum(b - a for a, b in busy) / 1e9)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    if not busy_planes:
        raise TraceError("no device operation ran in the traced window")
    n = len(busy_planes)
    starts = [a for a, _, _ in spans]
    labelled = sorted(((b - a) / 1e9, _label((a, b), spans, starts, jobs_in),
                       (a - lo) / 1e9) for a, b in gaps)[::-1]
    idle_by_label: Dict[str, float] = defaultdict(float)
    for s, label, _ in labelled:
        idle_by_label[label] += s / n
    return Reduction(
        window_s=window_s, busy_s=sum(busy_planes) / n,
        module_s={m: module_s[m] for m in modules},
        module_n={m: module_n[m] for m in modules},
        ops=sorted(((k, v / n) for k, v in ops.items()),
                   key=lambda kv: -kv[1])[:TOP],
        idle_gaps=[(f"{label} at {at:.3f}s", s)
                   for s, label, at in labelled[:TOP]],
        idle_by_label=dict(idle_by_label))


def xplane_files(directory: str) -> List[str]:
    return sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True))


def reduce_dir(directory: str, modules: Sequence[str],
               in_system: Sequence[Tuple[float, float]] = ()) -> Reduction:
    from jax.profiler import ProfileData
    files = xplane_files(directory)
    if not files:
        raise TraceError(f"no .xplane.pb under {directory}")
    return reduce(ProfileData.from_file(files[-1]), modules, in_system)
