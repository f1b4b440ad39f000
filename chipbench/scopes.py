"""Device time by named scope and idle gaps by program span, from one ``.xplane.pb``.

    python -m chipbench.scopes <trace dir or .xplane.pb> [--top 12] [--json]

What the program writes into a profiler trace since PR 28: a ``jax.named_scope`` around each
part of its train phases (``SCOPES``, the one list of them: the program's tests import it) and a
``TraceAnnotation`` for every host span (the iteration a ``StepTraceAnnotation`` named ``iter``).
A host span is known by the ``span`` stat the program gives each of its annotations (the id of the
span's record in ``SPANS.records()``), not from a list of names, so a span the program gains later
labels its gaps unasked; JAX's own marks on the same thread's line (``PjitFunction(...)``, ``copy``,
the Python tracer's ``$file:line``) carry none.  Only the loop's thread counts, the one that holds
``iter``: a writer thread's ``ckpt.snapshot`` runs beside the loop and holds up no dispatch.
This reduces a trace to two tables:

* device self time by scope, ops outside any scope under ``unscoped`` (with the ops that make it up);
* idle time of the device by program span: every part of a gap goes to the innermost span (not
  ``iter``) open over it, else to ``iter``, else to ``outside``; a gap over 1 ms is counted and listed
  under the span that holds most of it.

Where the scope of an op is written, on this runtime (v5e, jax 0.9.0; looked up by hand in the traces
of both cells): not in a stat of the events of the ``XLA Ops`` line, which carry the op's HLO text as
their name and only ``device_offset_ps``/``device_duration_ps`` as stats, but in the ``tf_op`` stat of
each event's METADATA on the device plane, beside ``program_id``, ``source``, ``hlo_category``, ``flops``
and ``bytes_accessed``.  It holds the op's ``op_name``, the scope path:
``jit(anakin_phase)/while/body/closed_call/rollout.observe/...`` or, in the backward pass,
``.../transpose(jvp(update.loss))/...``.  ``program_id`` is the number in the name of the program's
events on the ``XLA Modules`` line (``jit_anakin_phase(12719869893039600841)``), which keeps two programs'
``copy.1`` apart.  (The ``/host:metadata`` plane's ``Hlo Proto`` holds the same names, but only for
programs built while the session recorded: not for a train phase.)  ``jax.profiler.ProfileData`` does
not hand out event metadata, so the few protobuf fields needed are read here from the wire format
(``_fields``); lines and events come from ``ProfileData`` as in ``trace.py``.

A program served from a compile-cache entry that an older checkout wrote runs with that entry's
metadata (JAX leaves debug metadata out of the cache's key): its ops then all read ``unscoped``.
Take such a trace with ``JAX_COMPILATION_CACHE_DIR`` pointing at an empty directory.

Reuses ``trace.py``'s ``union``, ``clip``, ``self_times``, ``short_name``, ``find_xplane``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.trace import Event, clip, find_xplane, self_times, short_name, union  # noqa: E402

#: ``jax.named_scope`` names of the program's train phases: stable strings that a refactor keeps
#: (``data/device_replay.py``, ``algos/dreamer_v3/dreamer_v3.py``, ``envs/jax/anakin.py``,
#: ``algos/ppo``).  The one list: ``tests/test_telemetry`` holds the lowered programs to it.
SCOPES: Tuple[str, ...] = (
    "replay.write", "replay.sample_index", "replay.gather",
    "wm.encoder", "wm.rssm", "wm.heads", "wm.optim",
    "behavior.imagine", "actor.loss", "actor.optim", "critic.loss", "critic.optim",
    "player.step",
    "rollout.policy", "rollout.env_step", "rollout.observe",
    "gae", "update.gather", "update.loss", "update.optim",
)
SPAN_STAT = "span"  # on every annotation of ``sheeprl_tpu/telemetry/spans.py``
ITER = "iter"
UNSCOPED = "unscoped"
OUTSIDE = "outside"  # an idle gap that not even an iteration span covers
GAP_MIN_NS = 1_000_000  # gaps over 1 ms are counted and listed one by one


# ----------------------------------------------------------------------------
# the protobuf wire format, as far as it is needed
# ----------------------------------------------------------------------------

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: memoryview) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message: an int for a varint or a fixed width,
    a memoryview for a length-delimited field (a string, bytes or a message)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not a protobuf message")
        yield number, wire, value


def _first(buf: memoryview, number: int) -> Any:
    return next((v for n, _, v in _fields(buf) if n == number), None)


def _text(value: Any) -> str:
    return bytes(value).decode(errors="replace")


def program_op_names(xspace: memoryview) -> Dict[int, Dict[str, str]]:
    """{program id: {instruction name: op_name}} from the event metadata of the device planes: the
    ``program_id`` and ``tf_op`` stats of every op that ran.  XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4 and .stat_metadata = 5 (maps: entry.key = 1, entry.value = 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
    .uint64_value = 3, .int64_value = 4, .str_value = 5, .ref_value = 7 (a string kept once, as the name
    of another stat metadata)."""
    out: Dict[int, Dict[str, str]] = {}
    for n, wire, plane in _fields(xspace):
        if n != 1 or wire != 2:
            continue
        name = _first(plane, 2)
        if name is None or not _text(name).startswith("/device:TPU:"):
            continue
        stat_names: Dict[int, str] = {}
        metadata: List[memoryview] = []
        for m, wire2, entry in _fields(plane):
            if wire2 != 2 or m not in (4, 5):
                continue
            value = _first(entry, 2)
            if value is None:
                continue
            if m == 4:
                metadata.append(value)
            else:
                stat_name = _first(value, 2)
                stat_names[_first(entry, 1) or 0] = _text(stat_name) if stat_name is not None else ""
        for event_metadata in metadata:
            instruction = program = op_name = None
            for k, _, v in _fields(event_metadata):
                if k == 2:
                    instruction = short_name(_text(v))
                elif k == 5:
                    stat = {kk: vv for kk, _, vv in _fields(v)}
                    which = stat_names.get(stat.get(1, 0))
                    if which == "program_id":
                        program = stat.get(3, stat.get(4))
                    elif which == "tf_op":
                        op_name = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7, 0), "")
            if instruction is not None and program is not None:
                out.setdefault(program, {})[instruction] = (op_name or "").rstrip(":")
    return out


# ----------------------------------------------------------------------------
# reading one trace
# ----------------------------------------------------------------------------

_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def read_xplane(path: str) -> Dict[str, Any]:
    """{"device": {plane: {"ops": events, "modules": events}}, "spans": host annotations of the
    program, "op_names": {module: {instruction: op_name}}}; times in ns on the trace's one clock."""
    from jax.profiler import ProfileData

    raw = open(path, "rb").read()
    data = ProfileData.from_serialized_xspace(raw)
    device: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            device[plane.name] = {
                "ops": [(short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)) for ev in lines["XLA Ops"].events],
                "modules": [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in (lines["XLA Modules"].events if "XLA Modules" in lines else ())
                ],
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks = [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events
                    if not ev.name.startswith("$") and any(k == SPAN_STAT for k, _ in ev.stats)
                ]
                if any(name == ITER for name, _, _ in marks):  # the loop's thread
                    spans.extend(marks)
    by_program = program_op_names(memoryview(raw))
    op_names: Dict[str, Dict[str, str]] = {}
    for dev in device.values():
        for module, _, _ in dev["modules"]:
            program = _PROGRAM_ID.search(module)  # ``jit_anakin_phase(12719869893039600841)``
            if module not in op_names and program is not None:
                op_names[module] = by_program.get(int(program.group(1)), {})
    return {"device": device, "spans": sorted(spans, key=lambda e: e[1]), "op_names": op_names}


# ----------------------------------------------------------------------------
# arithmetic on (name, start, duration)
# ----------------------------------------------------------------------------

_SPLIT = re.compile(r"[/()]")


def scope_of(op_name: str) -> str:
    """The innermost named scope of the program on an op's path, through ``jvp(...)`` and ``transpose(...)``."""
    for part in reversed(_SPLIT.split(op_name)):
        if part in SCOPES:
            return part
    return UNSCOPED


def module_of(start: int, modules: Sequence[Event]) -> Optional[str]:
    """The program running on the device at ``start`` (the modules of one device do not overlap)."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][1] <= start:
            lo = mid + 1
        else:
            hi = mid
    if lo and start < modules[lo - 1][1] + modules[lo - 1][2]:
        return modules[lo - 1][0]
    return None


def split_gap(gap: Tuple[int, int], spans: Sequence[Event]) -> Dict[str, int]:
    """{label: ns} of one idle gap: every part of it goes to the innermost program span (the shortest
    one, ``iter`` apart) that is open over it; else to ``iter``; else to ``outside``."""
    a, b = gap
    over = []
    for name, start, dur in spans:
        if start >= b:
            break  # sorted by start
        if start + dur > a:
            over.append((name, max(start, a), min(start + dur, b), dur))
    out: Dict[str, int] = {}
    cuts = sorted({a, b}.union(*[(s, e) for _, s, e, _ in over]))
    for lo, hi in zip(cuts, cuts[1:]):
        label, shortest = OUTSIDE, None
        for name, s, e, dur in over:
            if s <= lo and e >= hi:
                if name == ITER:
                    label = ITER if shortest is None else label
                elif shortest is None or dur < shortest:
                    label, shortest = name, dur
        out[label] = out.get(label, 0) + hi - lo
    return out


def steady_span(spans: Sequence[Event], device: Dict[str, Dict[str, List[Event]]]) -> Optional[Tuple[int, int]]:
    """From the start of the first iteration span to the end of the last; without any, the extent of the device's ops."""
    iters = [(s, s + d) for name, s, d in spans if name == ITER]
    if not iters:
        iters = [(s, s + d) for dev in device.values() for _, s, d in dev["ops"]]
    if not iters:
        return None
    return min(s for s, _ in iters), max(e for _, e in iters)


def reduce(trace: Dict[str, Any], top: int = 12) -> Optional[Dict[str, Any]]:
    spans = trace["spans"]
    window = steady_span(spans, trace["device"])
    if window is None or not trace["device"]:
        return None
    t0, t1 = window
    n = len(trace["device"])
    busy = 0
    scope_ns: Dict[str, int] = {}
    unscoped_ns: Dict[str, int] = {}
    gap_ns: Dict[str, List[int]] = {}  # label -> [all gaps ns, gaps over 1 ms: count, ns]
    long_gaps: List[List[Any]] = []
    for dev in trace["device"].values():
        modules = sorted(dev["modules"], key=lambda e: e[1])
        ops = clip(dev["ops"], t0, t1)
        merged = union([(s, s + d) for _, s, d in ops])
        busy += sum(e - s for s, e in merged)
        # self times per (module, op): a `while`'s body is not counted twice, and two programs' `copy.1` stay apart
        keyed = [(f"{module_of(s, modules)}\t{name}", s, d) for name, s, d in ops]
        for key, ns in self_times(keyed).items():
            module, name = key.split("\t", 1)
            scope = scope_of(trace["op_names"].get(module, {}).get(name, ""))
            scope_ns[scope] = scope_ns.get(scope, 0) + ns
            if scope == UNSCOPED:
                label = f"{module.split('(')[0]}: {name}"
                unscoped_ns[label] = unscoped_ns.get(label, 0) + ns
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            parts = split_gap((a, b), spans)
            for label, ns in parts.items():
                gap_ns.setdefault(label, [0, 0, 0])[0] += ns
            if b - a > GAP_MIN_NS:  # a long gap is counted and listed under the span that holds most of it
                label = max(parts, key=parts.get)
                gap_ns[label][1] += 1
                gap_ns[label][2] += b - a
                long_gaps.append([label, (a - t0) / 1e9, (b - a) / 1e9])
    ranked = lambda d: [[k, v / n / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]  # noqa: E731
    busy_s = busy / n / 1e9
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_s,
        "iterations": sum(1 for name, _, _ in spans if name == ITER),
        "by_scope": ranked(scope_ns),
        "unscoped_share": scope_ns.get(UNSCOPED, 0) / n / 1e9 / busy_s if busy_s else 0.0,
        "unscoped_ops": ranked(unscoped_ns)[:top],
        "idle_gaps": [[k, v[0] / n / 1e9, v[1], v[2] / n / 1e9] for k, v in sorted(gap_ns.items(), key=lambda kv: -kv[1][0])],
        "long_gaps": sorted(long_gaps, key=lambda g: -g[2])[:top],
    }


# ----------------------------------------------------------------------------
# the table
# ----------------------------------------------------------------------------

def table(out: Dict[str, Any]) -> str:
    busy, window = out["busy_s"], out["window_s"]
    rows = [
        f"window {window:.4f} s over {out['iterations']} iteration span(s); device busy {busy:.4f} s "
        f"({100 * busy / window:.1f}%), idle {window - busy:.4f} s ({100 * (1 - busy / window):.1f}%)",
        "",
        "device self time by scope                 s      % of busy",
    ]
    for name, s in out["by_scope"]:
        rows.append(f"  {name:<34} {s:>10.4f} {100 * s / busy if busy else 0.0:>10.1f}")
    if out["unscoped_ops"]:
        rows += ["", f"ops outside any scope ({100 * out['unscoped_share']:.1f}% of busy), largest first"]
        rows += [f"  {name:<60} {s:>10.4f}" for name, s in out["unscoped_ops"]]
    rows += ["", "idle gaps by program span                 s  % of idle   gaps >1ms      s in them"]
    idle = window - busy
    for name, s, count, s_long in out["idle_gaps"]:
        rows.append(f"  {name:<34} {s:>10.4f} {100 * s / idle if idle else 0.0:>10.1f} {count:>11d} {s_long:>14.4f}")
    if out["long_gaps"]:
        rows += ["", "longest gaps: span, seconds into the window, seconds long"]
        rows += [f"  {name:<34} {at:>10.4f} {length:>10.4f}" for name, at, length in out["long_gaps"]]
    return "\n".join(rows)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("trace", help="a directory jax.profiler wrote a trace into, or one .xplane.pb")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--json", action="store_true", help="print the reduction as one JSON line instead of the table")
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".pb") else find_xplane(args.trace)
    if path is None or not os.path.exists(path):
        print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
        return 2
    out = reduce(read_xplane(path), top=args.top)
    if out is None:
        print("the trace holds no device ops", file=sys.stderr)
        return 1
    print(json.dumps(out) if args.json else table(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
