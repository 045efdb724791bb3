"""Reductions from a window's runs and trace to metric values.

Each metric's file under ``metrics/`` binds its ``read(obs)`` to one of
these (or to its own).  A reader returns ``None`` where it finds nothing
to read, and never 0 in place of a share it could not measure.
"""
from __future__ import annotations

import bisect
import statistics
from typing import List, Optional

from benchmarks.chip import flops, trace as tr
from benchmarks.chip.traffic import quantile
from repro.core.workflow import INVOCATION_SEP


def _completed(obs):
    return [f for f in obs.runs if f.ok]


# ----------------------------------------------------------- end to end
def makespan_s(obs) -> Optional[float]:
    """Mean over the window's workflows of submit to terminal state."""
    done = _completed(obs)
    if not done:
        return None
    return sum(f.finished - f.submitted for f in done) / len(done)


def _run_latencies(obs) -> List[float]:
    """Scheduled send to terminal state, for every run sent; a run that
    never finished counts with the end of the drain."""
    return [f.finished - f.due for f in obs.runs]


def run_p50_s(obs) -> Optional[float]:
    lat = _run_latencies(obs)
    return quantile(lat, 0.5) if lat else None


def run_p95_s(obs) -> Optional[float]:
    lat = _run_latencies(obs)
    return quantile(lat, 0.95) if lat else None


def setup_s(obs) -> float:
    return obs.setup_s


# ----------------------------------------------------------- service
def admit_wait_ms(obs) -> Optional[float]:
    """Mean of ``RunInfo.started_at - submitted_at``."""
    waits = [f.started - f.submitted for f in obs.runs
             if f.started is not None]
    return 1e3 * statistics.fmean(waits) if waits else None


# ----------------------------------------------------------- engine
def engine_gap_ms(obs) -> Optional[float]:
    """Mean over invocations of the engine's latency from launchable to
    launched: launch minus the later of the latest harvest among the
    invocations whose tokens it consumes and the latest harvest on the
    replica it was launched on (its slot coming free).  Waiting for a
    busy replica is the workload's, not the loop's, and is left out."""
    done = _completed(obs)
    ends: dict = {}                     # resource -> harvest times
    for f in done:
        for e in f.result.events:
            ends.setdefault(e.resource, []).append(e.end)
    for v in ends.values():
        v.sort()
    gaps = []
    for f in done:
        ev = {e.step: e for e in f.result.events if e.status == "completed"}
        for path, e in ev.items():
            preds = obs.plan.predecessors(path)
            if not preds or not all(p in ev for p in preds):
                continue
            ready = max(ev[p].end for p in preds)
            i = bisect.bisect_right(ends[e.resource], e.start)
            if i:
                ready = max(ready, ends[e.resource][i - 1])
            gaps.append(e.start - ready)
    return 1e3 * statistics.fmean(gaps) if gaps else None


def outside_body_ms(obs) -> Optional[float]:
    """Mean over invocations of the engine's launch-to-harvest time minus
    the tool body's own."""
    out = []
    for f in _completed(obs):
        body = {}
        for b in f.bodies:
            path = b.step if not b.tag else (
                b.step + INVOCATION_SEP + ".".join(map(str, b.tag)))
            body[path] = b.end - b.start
        for e in f.result.events:
            if e.status == "completed" and e.step in body:
                out.append((e.end - e.start) - body[e.step])
    return 1e3 * statistics.fmean(out) if out else None


def transfer_ms(obs) -> Optional[float]:
    """The data manager's transfer seconds per completed workflow."""
    done = _completed(obs)
    if not done:
        return None
    total = sum(s.get("seconds", 0.0) for f in done
                for s in f.transfers.values())
    return 1e3 * total / len(done)


# ----------------------------------------------------------- device
def idle_share_pct(obs) -> Optional[float]:
    if obs.trace is None:
        return None
    return 100.0 * tr.idle_share(obs.trace)


def module_mean_ms(obs, module: str) -> Optional[float]:
    """Mean device time of one compiled program's runs in the window."""
    if obs.trace is None:
        return None
    runs = tr.module_runs(obs.trace, module)
    if not runs:
        return None
    return 1e3 * statistics.fmean(e - s for s, e in runs) / 1e9


def model_flops_share_pct(obs, per_module: dict) -> Optional[float]:
    """Model FLOPs of the listed programs' runs in the window over the
    window times the chips' bf16 peak.  ``per_module`` maps a program's
    name to the FLOPs of one run."""
    if obs.trace is None:
        return None
    n = {m: len(tr.module_runs(obs.trace, m)) for m in per_module}
    if not sum(n.values()):
        return None
    work = sum(per_module[m] * n[m] for m in per_module)
    peak = flops.peaks(obs.device_kind)["bf16_flops_per_s"]
    return 100.0 * work / (tr.window_s(obs.trace) * peak
                           * obs.trace.n_devices)
