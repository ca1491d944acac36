"""Reduction of a device trace to numbers.

``jax.profiler`` writes an ``.xplane.pb``; on a TPU each chip is a plane
``/device:TPU:<n>`` whose line "XLA Modules" holds one event per executed
program (``jit_<function>(<fingerprint>)``) and whose line "XLA Ops" holds one
event per HLO op, NESTED (a ``%while`` spans the ops of its body), named by
the op's HLO text (``%flash_fwd.4 = (...) custom-call(...)``). Everything
below works on the small neutral form ``{device: {line: [(name, start_ns,
duration_ns), ...]}}``, which is also how the recorded fixture is stored, so
the reduction is checked on the CPU against a real chip's trace.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]
Trace = Dict[str, Dict[str, List[Event]]]

MODULES, OPS = "XLA Modules", "XLA Ops"
_CONTAINERS = ("while", "conditional", "call")
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
)


def load_xplane(trace_dir: str) -> Trace:
    """Device planes of the newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        return {}
    trace: Trace = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            if line.name in (MODULES, OPS):
                lines[line.name] = [
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events
                ]
    return trace


def load_fixture(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {
        device: {line: [tuple(ev) for ev in events]
                 for line, events in lines.items()}
        for device, lines in raw.items()
    }


def module_name(event_name: str) -> str:
    """``jit_accumulate_step(184088...)`` -> ``accumulate_step``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


_OP_RE = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|$)")


def op_name(event_name: str) -> str:
    """``%flash_fwd.4 = (bf16[...``  -> ``flash_fwd``."""
    match = _OP_RE.match(event_name)
    return match.group(1) if match else event_name[:40]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def device_busy(trace: Trace) -> Dict[str, Tuple[float, float]]:
    """Per device: (busy seconds, window seconds). Busy is the union of the
    intervals in which an op ran; the window runs from the first op's start
    to the last op's end."""
    out = {}
    for device, lines in trace.items():
        events = lines.get(OPS) or lines.get(MODULES) or []
        if not events:
            continue
        spans = [(s, s + d) for _n, s, d in events]
        window = max(e for _s, e in spans) - min(s for s, _e in spans)
        out[device] = (union_ns(spans) / 1e9, window / 1e9)
    return out


def module_durations(trace: Trace, names: Iterable[str]) -> Dict[str, List[float]]:
    """Per device: device seconds of every execution of the named programs."""
    wanted = set(names)
    return {
        device: [
            d / 1e9 for n, _s, d in lines.get(MODULES, [])
            if module_name(n) in wanted
        ]
        for device, lines in trace.items()
    }


def op_durations(trace: Trace, name: str) -> List[float]:
    """Device seconds of every event of one op (e.g. a Pallas kernel), all
    devices together."""
    return [
        d / 1e9
        for lines in trace.values()
        for n, _s, d in lines.get(OPS, [])
        if op_name(n) == name
    ]


def _leaf_ops(lines) -> List[Event]:
    return [
        ev for ev in lines.get(OPS, [])
        if not op_name(ev[0]).startswith(_CONTAINERS)
    ]


def top_ops(trace: Trace, k: int = 10) -> List[List[object]]:
    """The ops that took most device time (containers such as ``while`` left
    out, their bodies counted), summed over devices: [[name, seconds], ...]."""
    totals: Dict[str, float] = {}
    for lines in trace.values():
        for n, _s, d in _leaf_ops(lines):
            key = op_name(n)
            totals[key] = totals.get(key, 0.0) + d / 1e9
    return [[n, s] for n, s in sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def collective_seconds(trace: Trace) -> Dict[str, float]:
    """Per device: union of the intervals of collective ops."""
    out = {}
    for device, lines in trace.items():
        spans = [
            (s, s + d) for n, s, d in lines.get(OPS, [])
            if op_name(n).startswith(_COLLECTIVES)
        ]
        out[device] = union_ns(spans) / 1e9
    return out


def idle_gaps(trace: Trace, accumulate: str, k: int = 10,
              major_ns: float = 1e5) -> List[List[object]]:
    """Idle time between consecutive MAJOR program executions (those of at
    least ``major_ns``; the helpers a dispatch scatters between them —
    ``convert_element_type``, ``_threefry_seed`` — are microseconds and are
    left inside the gap), on the device that idled most, classified by what
    the device trace itself shows on either side: between two accumulate
    programs the device waited for the host (dispatch or data); around any
    other program it waited inside the boundary. Summed per class."""
    best: Optional[Dict[str, float]] = None
    for lines in trace.values():
        events = sorted(
            (ev for ev in lines.get(MODULES, []) if ev[2] >= major_ns),
            key=lambda ev: ev[1],
        )
        gaps: Dict[str, float] = {}
        frontier = prev = None
        for n, s, d in events:
            name = module_name(n)
            if frontier is not None and s > frontier:
                kind = (
                    "host dispatch/data"
                    if prev == accumulate and name == accumulate
                    else "boundary"
                )
                label = f"{prev}->{name} ({kind})"
                gaps[label] = gaps.get(label, 0.0) + (s - frontier) / 1e9
            if frontier is None or s + d > frontier:
                frontier, prev = s + d, name
        if best is None or sum(gaps.values()) > sum(best.values()):
            best = gaps
    return [
        [n, s] for n, s in sorted((best or {}).items(), key=lambda kv: -kv[1])[:k]
    ]
