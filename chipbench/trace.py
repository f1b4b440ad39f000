"""Reduction of a profiler trace to device busy/idle time, top operations and idle-gap labels.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into plain tuples
(needs nothing but JAX).  Everything after that is arithmetic on ``(name, start, duration)``
in nanoseconds, and is what ``tests/test_trace.py`` checks on a small recorded trace.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start ns, duration ns

#: host annotations written by the harness's probes carry this prefix
MARK = "cb:"
ITER = MARK + "iter"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{"device": {plane: ops}, "host": {"marks": harness annotations}} from one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: Dict[str, List[Event]] = {}
    marks: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            # "XLA Ops" holds one event per executed HLO operation; the other lines
            # ("Steps", "XLA Modules", "XLA TraceMe") nest around them
            line = lines.get("XLA Ops")
            if line is None:
                continue
            device[plane.name] = [(short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(MARK):
                        marks.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return {"device": device, "host": {"marks": sorted(marks, key=lambda e: e[1])}}


def short_name(hlo: str) -> str:
    """``%fusion.956 = (f32[], ...) fusion(...)`` -> ``fusion.956``: the trace names an op by its whole HLO line."""
    return hlo.split(" = ", 1)[0].lstrip("%")[:120]


def self_times(events: Sequence[Event]) -> Dict[str, int]:
    """Nanoseconds per op name, a container's (``while``, ``conditional``) children taken out of it:
    the events of one line nest, and the ranking must not count a loop's body twice."""
    out: Dict[str, int] = {}
    stack: List[List[int]] = []  # [end, index into names]
    names: List[str] = []
    own: List[int] = []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= dur
        names.append(name)
        own.append(dur)
        stack.append([start + dur, len(names) - 1])
    for name, ns in zip(names, own):
        out[name] = out.get(name, 0) + max(ns, 0)
    return out


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    merged: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(events: Sequence[Event], t0: int, t1: int) -> List[Event]:
    out = []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            out.append((name, s, e - s))
    return out


def steady_span(marks: Sequence[Event]) -> Optional[Tuple[int, int]]:
    """The traced steady window: from the start of the first whole iteration mark to the end of the last."""
    iters = [(s, s + d) for name, s, d in marks if name == ITER]
    if not iters:
        return None
    return min(s for s, _ in iters), max(e for _, e in iters)


def label_gap(gap: Tuple[int, int], marks: Sequence[Event]) -> str:
    """The innermost harness mark (not the iteration itself) that covers most of the gap."""
    best, best_cover = "loop", 0
    for name, start, dur in marks:
        if name == ITER:
            continue
        cover = min(gap[1], start + dur) - max(gap[0], start)
        if cover > best_cover:
            best, best_cover = name[len(MARK):], cover
    return best if best_cover * 2 >= gap[1] - gap[0] else "loop"


def reduce(trace: Dict[str, Dict[str, List[Event]]], top: int = 10) -> Optional[Dict[str, object]]:
    """busy_s (mean over the device planes), window_s, top device ops, longest idle gaps by label."""
    marks = trace["host"]["marks"]
    span = steady_span(marks)
    if span is None or not trace["device"]:
        return None
    t0, t1 = span
    busy_ns: List[int] = []
    op_ns: Dict[str, int] = {}
    gap_ns: Dict[str, int] = {}
    for ops in trace["device"].values():
        ops = clip(ops, t0, t1)
        merged = union([(s, s + d) for _, s, d in ops])
        busy_ns.append(sum(e - s for s, e in merged))
        for name, ns in self_times(ops).items():
            op_ns[name] = op_ns.get(name, 0) + ns
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                label = label_gap((a, b), marks)
                gap_ns[label] = gap_ns.get(label, 0) + (b - a)
    n = len(busy_ns)
    ranked = lambda d: [[k, v / n / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
    return {
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "device_ops": ranked(op_ns),
        "idle_gaps": ranked(gap_ns),
    }
