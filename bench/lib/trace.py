"""Reduce a JAX profiler trace to the benchmark's device numbers.

A traced run records a span of its window under one host annotation
(the window span). From the ``.xplane.pb`` that ``jax.profiler`` writes,
this module takes, per device used, the operations on its ``XLA Ops``
line, clipped to the window span, and gives:

* busy seconds: the length of the union of those operations' intervals;
* self seconds and launches per operation: the line nests operations
  (a ``while`` holds its body's operations, a ``conditional`` its
  branch's), so an operation's own time is its time less that of the
  operations inside it, and the self times add up to the busy time;
* the idle gaps inside the window, each named by what the host was
  doing (see :func:`name_gaps`); these take the device and host clocks
  to be the profiler's common clock.

Times in the trace are in nanoseconds.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"


class Interval(NamedTuple):
    start: float
    end: float
    name: str


class DeviceTrace(NamedTuple):
    busy_s: float
    op_s: Dict[str, float]          # own seconds per operation name
    op_n: Dict[str, int]            # launches per operation name
    gaps: List[Tuple[float, float]]
    labels: Dict[str, str]          # operation name -> its text stats


class Summary(NamedTuple):
    window_s: float
    devices: List[DeviceTrace]      # one per device used
    gap_names: List[Tuple[str, float]]   # idle gaps of device 0, named

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices used."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def op_seconds(self) -> Dict[str, float]:
        """Seconds per operation name, averaged over the devices used."""
        out: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for k, v in d.op_s.items():
                out[k] += v / len(self.devices)
        return dict(out)

    def launches(self, name: str) -> float:
        """Launches of operation ``name`` per device used."""
        return sum(d.op_n.get(name, 0) for d in self.devices) / len(
            self.devices)

    def labels(self) -> Dict[str, str]:
        """Operation name -> its name and text stats (the HLO op's long
        name, which names a Pallas kernel)."""
        out: Dict[str, str] = {}
        for d in self.devices:
            out.update(d.labels)
        return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_gaps(intervals: Sequence[Tuple[float, float]], lo: float,
               hi: float) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``, and
    the gaps inside ``[lo, hi]`` that no interval covers."""
    busy, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return busy, gaps


FRAME = 10      # a host event this many times longer than a gap frames it


def name_gaps(gaps: Sequence[Tuple[float, float]],
              host: Sequence[Interval], skip: Sequence[str]
              ) -> List[Tuple[str, float]]:
    """Each gap with the host event that overlaps it most, among those at
    most ``FRAME`` times its length: a longer one (a thread blocked on the
    device all through a slab, the window itself) frames the gap rather
    than says what the host did in it, and names it only where nothing
    shorter overlaps it. Events named in ``skip`` never name a gap."""
    host = sorted((h for h in host if h.name not in skip),
                  key=lambda h: h.start)
    starts = [h.start for h in host]
    longest = max((h.end - h.start for h in host), default=0.0)
    out = []
    for gs, ge in gaps:
        best = {True: ("none", 0.0), False: ("none", 0.0)}
        i = bisect.bisect_left(starts, gs - longest)
        while i < len(host) and host[i].start < ge:
            h = host[i]
            overlap = min(h.end, ge) - max(h.start, gs)
            short = h.end - h.start <= FRAME * (ge - gs)
            if overlap > best[short][1]:
                best[short] = (h.name, overlap)
            i += 1
        name = best[True][0] if best[True][1] > 0 else best[False][0]
        out.append((name, (ge - gs) * 1e-9))
    return out


def self_times(ops: Sequence[Interval], lo: float, hi: float
               ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """Nanoseconds of each operation name's own time inside ``[lo, hi]``
    (an operation's clipped time less that of the operations nested in
    it), and its launches that start inside ``[lo, hi)``."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    inner = [0.0] * len(ops)
    stack: List[int] = []

    def clip(o: Interval) -> float:
        return max(0.0, min(o.end, hi) - max(o.start, lo))

    for i in order:
        o = ops[i]
        while stack and ops[stack[-1]].end < o.end:
            stack.pop()         # ended before ``o``, or does not hold it
        if stack:
            inner[stack[-1]] += clip(o)
        stack.append(i)
    own: Dict[str, float] = defaultdict(float)
    launches: Dict[str, int] = defaultdict(int)
    for i, o in enumerate(ops):
        t = clip(o) - inner[i]
        if t > 0:
            own[o.name] += t
        if lo <= o.start < hi:
            launches[o.name] += 1
    return dict(own), dict(launches)


def reduce(xplane_path: str, window: str, n_devices: int,
           skip_names: Sequence[str] = ()) -> Summary:
    """Reduce the trace at ``xplane_path``; ``window`` names the host
    annotation around the span that counts."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    host: List[Interval] = []
    devices: Dict[int, list] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(Interval(e.start_ns, e.end_ns, e.name)
                            for e in line.events)
        elif m and int(m.group(1)) < n_devices:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops, labels = [], {}
                    for e in line.events:
                        ops.append(Interval(e.start_ns, e.end_ns, e.name))
                        if e.name not in labels:
                            labels[e.name] = " ".join(
                                [e.name] + [str(v) for _, v in e.stats
                                            if isinstance(v, str)])
                    devices[int(m.group(1))] = (ops, labels)
    spans = [h for h in host if h.name == window]
    if len(spans) != 1:
        raise ValueError(f"expected one host span {window!r}, "
                         f"found {len(spans)}")
    lo, hi = spans[0].start, spans[0].end
    if sorted(devices) != list(range(n_devices)):
        seen = {p.name: [ln.name for ln in p.lines] for p in data.planes}
        raise ValueError(f"trace has {OPS_LINE!r} lines of devices "
                         f"{sorted(devices)}, expected {n_devices}: {seen}")
    traces = []
    for d in range(n_devices):
        ops, labels = devices[d]
        busy, gaps = union_gaps([(o.start, o.end) for o in ops], lo, hi)
        if busy <= 0:
            raise ValueError(f"no operation of device {d} ran inside the "
                             f"window span ({len(ops)} in the trace)")
        own, launches = self_times(ops, lo, hi)
        traces.append(DeviceTrace(
            busy * 1e-9, {k: v * 1e-9 for k, v in own.items()}, launches,
            gaps, {k: labels[k] for k in set(own) | set(launches)}))
    named = name_gaps(traces[0].gaps, host, [window, *skip_names])
    return Summary((hi - lo) * 1e-9, traces, named)


HLO = re.compile(r"^%?(?P<name>[\w.\-]+) = (?P<rest>.*)$", re.S)


def describe(op: str) -> str:
    """A short name for an HLO operation's trace name, which is its whole
    HLO text: ``copy.455 copy s32[16,16384,4,2]`` (a tuple-shaped result
    is written ``(...)``)."""
    m = HLO.match(op)
    if not m:
        return op[:120]
    rest = m["rest"]
    if rest.startswith("("):
        depth = 0
        for k, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, tail = "(...)", rest[k + 1:]
    else:
        shape, _, tail = rest.partition(" ")
        shape = re.sub(r"\{.*", "", shape)
    opcode = re.match(r"\s*([\w\-]+)", tail)
    return f"{m['name']} {opcode[1] if opcode else '?'} {shape}"


def top(pairs, n: int = 10) -> List[list]:
    """The ``n`` largest ``(name, seconds)`` pairs, largest first."""
    return [[k, v] for k, v in sorted(pairs, key=lambda kv: -kv[1])[:n]]


def breakdown(summary: Summary, n: int = 10) -> dict:
    """The operations with the most own time, by their short names, and
    the longest idle gaps with what the host was doing."""
    ops: Dict[str, float] = defaultdict(float)
    for op, s in summary.op_seconds().items():
        ops[describe(op)] += s
    return {"device_ops": top(ops.items(), n),
            "idle_gaps": top(summary.gap_names, n)}
