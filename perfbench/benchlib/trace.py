"""Reduce a JAX profiler trace to what the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Each chip is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
that ran, named by the operation's HLO text (``%name = shape opcode(...)``).
Control flow nests: a ``while`` event spans every operation of its body,
so only operations that contain no other operation of positive length
(``leaves``) count as work.  The benchmark's own host spans (``bench.*``,
``jax.profiler.TraceAnnotation``) are on a host plane, on the same clock.

Times are in nanoseconds until the functions that return seconds."""
from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")


@dataclass
class Op:
    start: int
    end: int
    text: str      # the HLO instruction text

    @property
    def name(self) -> str:
        """The instruction's name, without its '%'."""
        return self.text.split(" = ", 1)[0].lstrip("%")

    @property
    def opcode(self) -> str:
        return opcode(self.text)

    @property
    def label(self) -> str:
        """name and opcode, with a custom call's target."""
        op = self.opcode
        if op == "custom-call":
            m = re.search(r'custom_call_target="([^"]+)"', self.text)
            op = m.group(1) if m else op
        return f"{self.name} {op}"


def opcode(text: str) -> str:
    """The opcode of ``%name = shape opcode(operands), attributes``; a
    tuple shape is parenthesized."""
    _, sep, rest = text.partition(" = ")
    if not sep:
        return text
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest[rest.find(" "):]
    m = re.match(r"\s*([\w-]+)\(", rest)
    return m.group(1) if m else "?"


def leaves(ops: Sequence[Op]) -> List[Op]:
    """The operations that contain no other operation of positive length
    (a ``while`` spans its body's operations)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start,
                                                    -ops[i].end))
    stack: List[int] = []
    container = set()
    for i in order:
        o = ops[i]
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if o.end > o.start and stack and ops[stack[-1]].end >= o.end:
            container.add(stack[-1])
        stack.append(i)
    return [o for i, o in enumerate(ops) if i not in container]


@dataclass
class Trace:
    window: Tuple[int, int]
    devices: Dict[int, List[Op]]
    spans: List[Tuple[int, int, str]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def read_trace(logdir: str) -> Trace:
    """The ``bench.window`` span, every chip's operations inside it, and
    the benchmark's host spans."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    return from_planes(data.planes)


def from_planes(planes) -> Trace:
    devices: Dict[int, List[Op]] = {}
    spans: List[Tuple[int, int, str]] = []
    for plane in planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name == OPS_LINE:
                ops = devices.setdefault(int(dev.group(1)), [])
                for e in line.events:
                    s = int(e.start_ns)
                    ops.append(Op(s, s + int(e.duration_ns), e.name))
            elif not dev:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        spans.append((s, s + int(e.duration_ns), e.name))
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0, w1, _ = windows[0]
    clipped = {d: sorted((Op(max(o.start, w0), min(o.end, w1), o.text)
                          for o in leaves(ops)
                          if o.end > w0 and o.start < w1),
                         key=lambda o: o.start)
               for d, ops in devices.items()}
    return Trace((w0, w1), clipped,
                 [s for s in spans if s[2] != WINDOW_SPAN])


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint, sorted cover of ``intervals``."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(ivs) -> int:
    return sum(e - s for s, e in ivs)


def busy_s(trace: Trace) -> Dict[int, float]:
    """Per chip, seconds in which some operation ran."""
    return {d: _length(union([(o.start, o.end) for o in ops])) * 1e-9
            for d, ops in trace.devices.items()}


def idle_share(trace: Trace) -> float:
    """Worst chip's share of the window in which no operation ran."""
    w = trace.window_s
    return max(1.0 - b / w for b in busy_s(trace).values())


def matching(trace: Trace, pattern: str) -> Dict[int, List[Op]]:
    """Per chip, the operations whose HLO text matches ``pattern``."""
    rx = re.compile(pattern)
    return {d: [o for o in ops if rx.search(o.text)]
            for d, ops in trace.devices.items()}


def op_seconds(ops: Sequence[Op]) -> float:
    return sum(o.end - o.start for o in ops) * 1e-9


def _subtract(ivs, cover) -> int:
    """Length of ``ivs`` (disjoint, sorted) not covered by ``cover``
    (disjoint, sorted)."""
    total, j = 0, 0
    for s, e in ivs:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                total += cover[k][0] - cur
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def exposed_collective_s(trace: Trace) -> Optional[float]:
    """Worst chip's seconds of collective operations (by opcode, their
    ``-start``/``-done`` halves included) during which no other operation
    ran on that chip; None when the trace has no collective."""
    worst = None
    for ops in trace.devices.values():
        coll = [(o.start, o.end) for o in ops if COLLECTIVE.match(o.opcode)]
        if not coll:
            continue
        other = union([(o.start, o.end) for o in ops
                       if not COLLECTIVE.match(o.opcode)])
        x = _subtract(union(coll), other) * 1e-9
        worst = x if worst is None else max(worst, x)
    return worst


def _label(s: int, e: int, spans) -> str:
    """The host span that covers most of [s, e); innermost on a tie."""
    best, best_len = "host: no span", 0
    for a, b, name in spans:
        ov = min(b, e) - max(a, s)
        if ov > best_len or (ov == best_len and ov > 0):
            best, best_len = name, ov
    return best


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The operations that took most device time in the window, summed
    over their calls and averaged over the chips (work operations only:
    a loop is not counted apart from its body)."""
    tot: Dict[str, int] = collections.Counter()
    for ops in trace.devices.values():
        for o in ops:
            tot[o.label] += o.end - o.start
    k = max(1, len(trace.devices))
    return [[name, t * 1e-9 / k] for name, t in tot.most_common(n)]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The longest stretches of the window in which chip 0 (the first
    chip) ran nothing, each named by the host span that covers it."""
    d0 = min(trace.devices)
    busy = union([(o.start, o.end) for o in trace.devices[d0]])
    w0, w1 = trace.window
    gaps, cur = [], w0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        gaps.append((cur, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_label(s, e, trace.spans), (e - s) * 1e-9] for s, e in gaps[:n]]
