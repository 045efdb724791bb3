"""Reduction of a profiler trace to device busy time, op times and gaps.

The JAX profiler writes one ``.xplane.pb`` per traced window.  On a TPU
it holds one plane per chip (``/device:TPU:<n>``), whose ``XLA Ops`` line
has an event for each operation the chip ran and whose ``XLA Modules``
line has one for each compiled program run (``jit_<name>(<fingerprint>)``),
and a ``/host:CPU`` plane with a line per host thread, where
``jax.profiler.TraceAnnotation`` spans appear by name.  All events share
one time base, in nanoseconds.

The harness marks its measured window with the span :data:`WINDOW` and
each tool body with ``tool:<step>`` (see ``bodies.py``).  Everything here
is clipped to the window span.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench:window"
TOOL_PREFIX = "tool:"
OUTSIDE = "outside any tool"
_FINGERPRINT = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]        # (start_ns, end_ns)


@dataclass
class Trace:
    """What the reduction reads: plain intervals, no profiler objects."""
    window: Interval
    ops: Dict[str, List[Interval]] = field(default_factory=dict)
    modules: Dict[str, List[Interval]] = field(default_factory=dict)
    tools: List[Tuple[str, Interval]] = field(default_factory=list)
    n_devices: int = 1


def module_name(event_name: str) -> str:
    """``jit_train_step(8999...)`` -> ``jit_train_step``."""
    return _FINGERPRINT.sub("", event_name)


def read_xplane(path: str) -> Trace:
    """Load one ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window: Optional[Interval] = None
    ops: Dict[str, List[Interval]] = defaultdict(list)
    modules: Dict[str, List[Interval]] = defaultdict(list)
    tools: List[Tuple[str, Interval]] = []
    devices = set()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.add(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops[f"{plane.name}|{ev.name}"].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules[module_name(ev.name)].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == WINDOW:
                        window = span
                    elif ev.name.startswith(TOOL_PREFIX):
                        tools.append((ev.name[len(TOOL_PREFIX):], span))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span in the trace")
    return Trace(window, dict(ops), dict(modules), tools,
                 n_devices=max(1, len(devices)))


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{log_dir}: want one .xplane.pb, found {paths}")
    return paths[0]


def clip(iv: Interval, window: Interval) -> Optional[Interval]:
    s, e = max(iv[0], window[0]), min(iv[1], window[1])
    return (s, e) if e > s else None


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_intervals(tr: Trace) -> List[Interval]:
    """Union of every device operation inside the window (all chips;
    intervals of different chips merge, so use one chip per trace or
    divide by :attr:`Trace.n_devices` knowingly)."""
    ivs = []
    for spans in tr.ops.values():
        for iv in spans:
            c = clip(iv, tr.window)
            if c:
                ivs.append(c)
    return union(ivs)


def window_s(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran on a chip, averaged over chips."""
    total = 0.0
    by_dev: Dict[str, List[Interval]] = defaultdict(list)
    for key, spans in tr.ops.items():
        dev = key.split("|", 1)[0]
        for iv in spans:
            c = clip(iv, tr.window)
            if c:
                by_dev[dev].append(c)
    for ivs in by_dev.values():
        total += sum(e - s for s, e in union(ivs))
    return total / 1e9 / max(tr.n_devices, 1)


def idle_share(tr: Trace) -> float:
    """1 - busy / window, as a fraction."""
    return 1.0 - busy_s(tr) / window_s(tr)


def module_runs(tr: Trace, name: str) -> List[Interval]:
    """Runs of one compiled program that start inside the window."""
    lo, hi = tr.window
    return [iv for iv in tr.modules.get(name, []) if lo <= iv[0] < hi]


def device_ops(tr: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """Device seconds per compiled program inside the window, largest
    first."""
    secs = []
    for name, spans in tr.modules.items():
        t = 0.0
        for iv in spans:
            c = clip(iv, tr.window)
            if c:
                t += c[1] - c[0]
        if t > 0:
            secs.append((name, t / 1e9))
    secs.sort(key=lambda x: -x[1])
    return secs[:top]


def idle_gaps(tr: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """The longest device-idle gaps inside the window, each named after
    the tool span that covers most of it, or :data:`OUTSIDE` (engine or
    codec work on the host) where no tool span covers half of it."""
    busy = busy_intervals(tr)
    edges = [tr.window[0]] + [x for iv in busy for x in iv] + [tr.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for g in gaps[:top]:
        best, cover = OUTSIDE, 0.0
        for step, span in tr.tools:
            c = clip(span, g)
            if c and c[1] - c[0] > cover:
                best, cover = f"{TOOL_PREFIX}{step}", c[1] - c[0]
        if cover < (g[1] - g[0]) / 2:
            best = OUTSIDE
        out.append((best, (g[1] - g[0]) / 1e9))
    return out
