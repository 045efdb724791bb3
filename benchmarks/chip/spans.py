"""Reductions of the engine's own spans to per-layer metrics.

While a JAX profiler session runs (the traced run), the engine records
spans at each layer boundary (``repro.core.tracing``) and hands them out
with each workflow's result as ``RunResult.spans``.  A result without
spans (tracing off, or an engine that records none) gives no number:
every reader here returns ``None``.

``idle_dataplane_pct`` puts the spans on the device trace's clock.  The
reduced trace keeps the benchmark's own annotations only, so the offset
comes from anchors that both records hold: each ``tool:<step>`` span of
the trace is paired with the ``bodies.Body`` stamp of the same body (the
host clock of the spans), in order of start within each step.  The
offset is the median of the pairs'; where the pairs' offsets spread (from
the first to the third quartile) by more than :data:`MAX_SPREAD_NS`,
there is no number.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from benchmarks.chip import trace as tr

#: the spans in which a host thread moves or converts a token
DATAPLANE = ("sf:decode", "sf:encode", "sf:cas_put", "sf:transfer")
#: the most the anchors' offsets may spread, ns
MAX_SPREAD_NS = 1e6


def spans_of(f) -> Optional[list]:
    return getattr(f.result, "spans", None)


def traced_runs(obs) -> list:
    """The window's completed workflows that carry spans."""
    return [f for f in obs.runs if f.ok and spans_of(f) is not None]


def total_ms(obs, names: Tuple[str, ...]) -> Optional[float]:
    """Time in the named spans, every thread summed, per completed
    workflow; None where there is no such span."""
    runs = traced_runs(obs)
    ns = [s.end_ns - s.start_ns for f in runs for s in f.result.spans
          if s.name in names]
    return sum(ns) / 1e6 / len(runs) if ns else None


def engine_self_ms(obs) -> Optional[float]:
    """Time in the engine loop's iterations (``sf:tick``) per completed
    invocation."""
    runs = traced_runs(obs)
    n = sum(1 for f in runs for e in f.result.events
            if e.status == "completed")
    ticks = [s.end_ns - s.start_ns for f in runs for s in f.result.spans
             if s.name == "sf:tick"]
    return sum(ticks) / 1e6 / n if ticks and n else None


def work_spans(f) -> Dict[Tuple[str, int], object]:
    """A workflow's ``sf:work`` spans by (run key, attempt)."""
    return {s.key[1:]: s for s in f.result.spans if s.name == "sf:work"}


def run_key(e) -> str:
    """The key under which the engine ran a ``JobEvent``'s invocation."""
    return f"{e.step}#spec{e.attempt}" if e.speculative else e.step


def harvest_lag_ms(obs) -> Optional[float]:
    """Mean over completed invocations of the loop's harvest (the end of
    the ``JobEvent``) minus the end of the worker's ``sf:work``."""
    lags = []
    for f in traced_runs(obs):
        works = work_spans(f)
        for e in f.result.events:
            w = works.get((run_key(e), e.attempt))
            if e.status == "completed" and w is not None:
                lags.append(e.end * 1e9 - w.end_ns)
    return statistics.fmean(lags) / 1e6 if lags else None


# ------------------------------------------------------------ device clock
def anchor_offsets(obs) -> List[float]:
    """Trace time minus host time, ns, for each tool body found in both
    the trace and the window's body stamps."""
    if obs.trace is None:
        return []
    traced: Dict[str, List[float]] = defaultdict(list)
    for step, (start, _end) in obs.trace.tools:
        traced[step].append(start)
    stamped: Dict[str, List[float]] = defaultdict(list)
    for f in obs.runs:
        for b in f.bodies:
            stamped[b.step].append(b.start * 1e9)
    out = []
    for step, starts in traced.items():
        if len(starts) != len(stamped.get(step, ())):
            continue                 # bodies that cannot be paired one to one
        out += [t - h for t, h in zip(sorted(starts), sorted(stamped[step]))]
    return out


def offset_spread_ns(offsets: List[float]) -> float:
    """First to third quartile of the anchors' offsets."""
    if len(offsets) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(offsets, n=4)
    return q3 - q1


def trace_offset_ns(obs) -> Optional[float]:
    """The offset that puts host times on the trace's clock, or None
    where there are no anchors or they disagree."""
    offsets = anchor_offsets(obs)
    if not offsets or offset_spread_ns(offsets) > MAX_SPREAD_NS:
        return None
    return statistics.median(offsets)


def _length(ivs: List[tr.Interval]) -> float:
    return sum(e - s for s, e in ivs)


def _intersect(a: List[tr.Interval], b: List[tr.Interval]
               ) -> List[tr.Interval]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_dataplane_pct(obs) -> Optional[float]:
    """Share of the traced window in which the chip ran nothing while a
    host thread was in a data-plane span, %."""
    runs = traced_runs(obs)
    off = trace_offset_ns(obs) if runs else None
    if off is None:
        return None
    ivs = []
    for f in runs:
        for s in f.result.spans:
            if s.name in DATAPLANE:
                c = tr.clip((s.start_ns + off, s.end_ns + off),
                            obs.trace.window)
                if c:
                    ivs.append(c)
    plane = tr.union(ivs)
    idle = _length(plane) - _length(_intersect(plane,
                                               tr.busy_intervals(obs.trace)))
    return 100.0 * idle / (obs.trace.window[1] - obs.trace.window[0])
