"""From a profiler trace of the timed window to the numbers the per-layer
metrics read: each device's operations, its busy time, the time of named
kernels, the part of a collective during which nothing else ran, and the
device's idle gaps with the host span that was open during each.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Each TPU
chip is one plane named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO operation (Pallas kernels included, as custom
calls), on the same nanosecond clock as the host threads of the
``/host:CPU`` plane, where the benchmark's own spans
(``jax.profiler.TraceAnnotation``) appear.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

Interval = tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str      # the HLO instruction's name, e.g. "fusion.12"
    start: float   # ns
    end: float     # ns
    self_ns: float = -1.0   # the time no nested operation covers
    leaf: bool = True       # no operation nests inside it

    def __post_init__(self):
        if self.self_ns < 0:
            self.self_ns = self.end - self.start


@dataclasses.dataclass
class Trace:
    """Device operations per chip and the benchmark's host spans."""

    ops: dict[int, list[Op]]
    spans: list[Op]

    def __post_init__(self):
        for ops in self.ops.values():
            _nest(ops)
        self.spans.sort(key=lambda o: o.start)

    def chips(self) -> list[int]:
        return sorted(self.ops)


def short_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``: the kind of operation."""
    return re.sub(r"(\.\d+|\.clone)+$", "", name)


def _nest(ops: list[Op]) -> None:
    """Sort ``ops`` and mark nesting: a loop's operation (``while``) spans
    the operations of its body.  Each op's ``self_ns`` loses the time of
    the ops nested directly inside it."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: list[Op] = []
    for o in ops:
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].leaf = False
            stack[-1].self_ns -= o.end - o.start
        stack.append(o)


def from_profile(pd, span_names: tuple[str, ...]) -> Trace:
    """Read a ``jax.profiler.ProfileData``: every TPU plane's ``XLA Ops``
    events, and the host events named in ``span_names``."""
    ops: dict[int, list[Op]] = {}
    spans: list[Op] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.extend(Op(short_name(e.name), e.start_ns,
                                  e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in span_names)
    return Trace(ops, spans)


def load(path: str, span_names: tuple[str, ...]) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path), span_names)


def dump(trace: Trace, path: str, until_ns: float) -> None:
    """Write the trace's first ``until_ns`` (from its first operation) as
    gzipped JSON: the compact form the tests read."""
    t0 = min(o.start for ops in trace.ops.values() for o in ops)
    keep = lambda o: o.start - t0 < until_ns
    row = lambda o: [o.name, o.start - t0, o.end - t0]
    data = {"ops": {str(c): [row(o) for o in ops if keep(o)]
                    for c, ops in trace.ops.items()},
            "spans": [row(o) for o in trace.spans if keep(o)]}
    with gzip.open(path, "wt") as f:
        json.dump(data, f, separators=(",", ":"))


def load_dump(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return Trace({int(c): [Op(*r) for r in ops]
                  for c, ops in data["ops"].items()},
                 [Op(*r) for r in data["spans"]])


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def merge(intervals) -> list[Interval]:
    """The union of intervals as sorted, disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def uncovered(target, cover) -> float:
    """Length of the union of ``target`` not covered by the union of
    ``cover``."""
    tgt, cov = merge(target), merge(cover)
    total, j = 0.0, 0
    for s, e in tgt:
        while j < len(cov) and cov[j][1] <= s:
            j += 1
        pos, k = s, j
        while k < len(cov) and cov[k][0] < e:
            if cov[k][0] > pos:
                total += cov[k][0] - pos
            pos = max(pos, cov[k][1])
            k += 1
        if pos < e:
            total += e - pos
    return total


def gaps(intervals, start: float, end: float) -> list[Interval]:
    """The parts of [start, end] that no interval covers."""
    out, pos = [], start
    for s, e in merge(intervals):
        if s > pos:
            out.append((pos, min(s, end)))
        pos = max(pos, e)
        if pos >= end:
            break
    if pos < end:
        out.append((pos, end))
    return [(s, e) for s, e in out if e > s]


# ---------------------------------------------------------------------------
# what the metrics read
# ---------------------------------------------------------------------------
def busy_ns(trace: Trace, chip: int) -> float:
    return length((o.start, o.end) for o in trace.ops.get(chip, []))


def kernel_ns(trace: Trace, chip: int, pattern: str) -> float:
    """Summed device time of the operations whose name matches
    ``pattern`` (a regular expression, matched at the start)."""
    rx = re.compile(pattern)
    return sum(o.end - o.start for o in trace.ops.get(chip, [])
               if rx.match(o.name))


def exposed_ns(trace: Trace, chip: int, pattern: str) -> float:
    """Time of the operations matching ``pattern`` during which no other
    operation ran on the chip (a loop around it does not count as one)."""
    rx = re.compile(pattern)
    ops = trace.ops.get(chip, [])
    hit = [(o.start, o.end) for o in ops if rx.match(o.name)]
    rest = [(o.start, o.end) for o in ops
            if o.leaf and not rx.match(o.name)]
    return uncovered(hit, rest)


def top_ops(trace: Trace, n: int = 10) -> list[tuple[str, float]]:
    """The kinds of operation with the most device time of their own, in
    seconds summed over the chips and divided by their number."""
    tot: dict[str, float] = {}
    for ops in trace.ops.values():
        for o in ops:
            k = base_name(o.name)
            tot[k] = tot.get(k, 0.0) + o.self_ns
    k = max(len(trace.ops), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / k / 1e9) for name, ns in best]


def idle_gaps(trace: Trace, chip: int, n: int = 10,
              other: str = "host") -> list[tuple[str, float]]:
    """The ``n`` longest stretches of the traced window in which the chip
    ran nothing, each named by the benchmark span that overlapped it most
    (``other`` where none did), in seconds."""
    ops = trace.ops.get(chip, [])
    if not ops:
        return []
    start = min([o.start for o in ops] + [s.start for s in trace.spans])
    end = max([o.end for o in ops] + [s.end for s in trace.spans])
    out = []
    for s, e in gaps([(o.start, o.end) for o in ops], start, end):
        best, name = 0.0, other
        for sp in trace.spans:
            ov = min(e, sp.end) - max(s, sp.start)
            if ov > best:
                best, name = ov, sp.name
        out.append((name, (e - s) / 1e9))
    return sorted(out, key=lambda t: -t[1])[:n]
