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

Device time by named scope (``Reduction.scope_s``): each operation's event
is named by its HLO instruction, and the module run that holds it names
the compiled module (``jit__decode(1939267968782906557)``); the
``/host:metadata`` plane keeps one ``Hlo Proto`` for each such run, whose
instructions carry the scope path of the program's source
(``metadata.op_name``, ``jit(_decode)/while/body/...``); one that the
compiler made with no source operation (a copy, a convert hoisted out of
a loop) has an empty path and falls outside every scope. The protos are
parsed on the first ``scope_s`` call only, with the protobuf modules of
``XPLANE_PB2`` and ``HLO_PB2``; a reduction that is asked for no scope
never imports them.
"""

from __future__ import annotations

import bisect
import glob
import importlib
import os
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench.spans import BLOCK, WARMUP, WINDOW_CLOSE, WINDOW_OPEN

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
METADATA_PLANE = "/host:metadata"
HLO_PROTO = "Hlo Proto"
XPLANE_PB2 = "tensorflow.tsl.profiler.protobuf.xplane_pb2"
HLO_PB2 = "tensorflow.compiler.xla.service.hlo_pb2"
TOP = 10
#: Share of a module's time that may go unattributed to an instruction
#: before the breakdown names it.
UNATTRIBUTED_SHARE = 0.01


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
    #: TPU planes that ran anything (``ops`` and busy time average over
    #: them).
    planes: int = 1
    #: Self time, summed over the planes, of each (module run, instruction)
    #: of each module asked for: what ``scope_s`` attributes.
    instructions: Dict[str, Dict[Tuple[str, str], float]] = field(
        default_factory=dict, repr=False)
    #: The serialized trace, whose HLO protos ``scope_s`` reads.
    xspace: Optional[bytes] = field(default=None, repr=False)
    _op_names: Optional[Dict[str, Dict[str, str]]] = field(
        default=None, repr=False)

    def module_mean_s(self, module: str) -> float:
        n = self.module_n.get(module, 0)
        if n == 0:
            raise TraceError(f"module {module!r} never ran in the traced "
                             f"window; modules seen: {self.module_n}")
        return self.module_s[module] / n

    def _by_scope(self, module: str):
        """(op name or None where no proto or instruction answers, total
        self time) of each instruction of ``module`` that ran."""
        self.module_mean_s(module)  # raises for a module that never ran
        if self._op_names is None:
            if self.xspace is None:
                raise TraceError("the reduction holds no serialized trace "
                                 "to read HLO protos from")
            runs = {run for ops in self.instructions.values()
                    for run, _ in ops}
            self._op_names = hlo_op_names(self.xspace, runs)
        for (run, inst), t in self.instructions[module].items():
            yield self._op_names.get(run, {}).get(inst), t

    def scope_s(self, module: str, scope: str) -> float:
        """Mean device self time per execution of ``module`` of the
        operations whose HLO ``op_name`` holds the path segment ``scope``
        (``while`` holds a ``jax.lax`` loop's body, a ``jax.named_scope``
        its name). Parses the trace's HLO protos on the first call."""
        total = sum(t for name, t in self._by_scope(module)
                    if name is not None and scope in name.split("/"))
        return total / self.module_n[module]

    @property
    def unattributed_s(self) -> Dict[str, float]:
        """For each module asked for that ran: the mean device self time
        per execution of its operations that no HLO proto or instruction
        answers, which no scope can hold. Parses the protos, as
        ``scope_s`` does."""
        return {m: sum(t for name, t in self._by_scope(m) if name is None)
                / self.module_n[m]
                for m in self.instructions if self.module_n[m]}

    @property
    def scopes_read(self) -> bool:
        """Whether the HLO protos were parsed (by ``scope_s``)."""
        return self._op_names is not None

    def breakdown(self) -> Dict:
        """The top device operations and idle gaps. Once a scope was read,
        a module's time that no instruction answers is named among the
        operations where it passes ``UNATTRIBUTED_SHARE`` of the module's."""
        ops = self.ops
        if self.scopes_read:
            extra = [(f"{m}: no HLO instruction", s * self.module_n[m]
                      / self.planes)
                     for m, s in self.unattributed_s.items()
                     if s > UNATTRIBUTED_SHARE * self.module_mean_s(m)]
            if extra:
                ops = sorted(ops[:TOP - len(extra)] + extra,
                             key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops],
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


def _instruction_name(name: str) -> str:
    """``%convert.19 = bf16[...] convert(...)`` as ``convert.19``."""
    return name.partition(" = ")[0].strip().lstrip("%")


def _module_at(modules, starts: List[float], t: float) -> str:
    """The full name of the module run holding ``t``, or ``?``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][1] >= t:
        return modules[i][2]
    return "?"


def _proto_module(name: str):
    try:
        return importlib.import_module(name)
    except ImportError as exc:
        raise TraceError(f"cannot import {name}, which reads the trace's "
                         f"HLO protos: {exc}") from exc


def hlo_op_names(xspace: bytes, runs: Iterable[str]
                 ) -> Dict[str, Dict[str, str]]:
    """For each module run named in ``runs`` whose ``Hlo Proto`` the
    trace's metadata plane holds: its instructions' ``metadata.op_name``
    by instruction name."""
    xplane_pb2 = _proto_module(XPLANE_PB2)
    hlo_pb2 = _proto_module(HLO_PB2)
    runs = set(runs)
    out: Dict[str, Dict[str, str]] = {}
    for plane in xplane_pb2.XSpace.FromString(xspace).planes:
        if plane.name != METADATA_PLANE:
            continue
        proto_ids = {k for k, m in plane.stat_metadata.items()
                     if m.name == HLO_PROTO}
        for meta in plane.event_metadata.values():
            if meta.name not in runs:
                continue
            for stat in meta.stats:
                if stat.metadata_id in proto_ids:
                    module = hlo_pb2.HloProto.FromString(
                        stat.bytes_value).hlo_module
                    out[meta.name] = {
                        i.name: i.metadata.op_name
                        for c in module.computations
                        for i in c.instructions}
    return out


def reduce(profile, modules: Sequence[str],
           in_system: Sequence[Tuple[float, float]] = (),
           xspace: Optional[bytes] = None) -> Reduction:
    """Reduce a ``ProfileData`` to the window between the markers.
    ``in_system`` holds the intervals, in seconds after the window opened,
    in which some job was in the system (they name idle gaps); ``xspace``
    is the serialized trace, which ``Reduction.scope_s`` reads."""
    planes = list(profile.planes)
    lo, hi = _markers(planes)
    window_s = (hi - lo) / 1e9
    spans = _host_spans(planes, lo, hi)
    jobs_in = sorted((lo + a * 1e9, lo + b * 1e9) for a, b in in_system)
    busy_planes, gaps = [], []
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    ops: Dict[str, float] = defaultdict(float)
    instructions: Dict[str, Dict[Tuple[str, str], float]] = {
        m: defaultdict(float) for m in modules}
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    if not devices:
        raise TraceError(f"no TPU plane in the trace: "
                         f"{[p.name for p in planes]}")
    for plane in devices:
        runs = sorted((ev.start_ns, ev.end_ns, ev.name)
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
            run = _module_at(runs, run_starts, a)
            key = run.split("(")[0] + ": " + short_op_name(name)
            ops[key] += t / 1e9
            m = _module_of(run, modules)
            if m is not None:
                instructions[m][run, _instruction_name(name)] += t / 1e9
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
        idle_by_label=dict(idle_by_label), planes=n,
        instructions={m: dict(v) for m, v in instructions.items()},
        xspace=xspace)


def xplane_files(directory: str) -> List[str]:
    return sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True))


def reduce_dir(directory: str, modules: Sequence[str],
               in_system: Sequence[Tuple[float, float]] = ()) -> Reduction:
    from jax.profiler import ProfileData
    files = xplane_files(directory)
    if not files:
        raise TraceError(f"no .xplane.pb under {directory}")
    raw = Path(files[-1]).read_bytes()
    return reduce(ProfileData.from_serialized_xspace(raw), modules,
                  in_system, xspace=raw)
