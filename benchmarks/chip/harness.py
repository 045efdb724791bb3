"""The chip benchmark's harness: one cell, one run, one result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout:

- the cell (``workloads``) names a configuration and a traffic mix;
- the configuration's ``file`` is its JSON file of sizes; the directory
  of the same stem beside it holds its StreamFlow document, its tools, its
  plain reference and ``cell.py``, whose ``deployment()`` sizes the
  document for the mix and checks what the window produced;
- the traffic mix is ``<paths[0]>/traffic/<traffic>.json``, read by the
  one generator in ``traffic.py``;
- each metric, end-to-end or per layer, is read by
  ``<paths[0]>/metrics/<name>.py``, whose ``read(obs)`` returns a number
  or ``None`` (nothing to read: the metric is left out of the line).

A later configuration, mix or metric is new files and new entries; no
file here names one.

A run: find the chip (there is no CPU fallback), point JAX's persistent
compilation cache at ``.jax_cache/`` in the checkout, build the
deployment, warm its programs with one small workflow of the cell, then
measure for ``--seconds``: with ``--trace 0`` the end-to-end metrics, with
``--trace 1`` the per-layer metrics from a profiler trace of the same
window.  Then the deployment's check compares what the window produced
with the plain reference, and the last line of standard output is the
result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from benchmarks.chip import bodies, trace as tr
from benchmarks.chip.common import WARMUP, Check
from benchmarks.chip.traffic import Mix, load_mix

ROOT = Path(__file__).resolve().parents[2]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ finding
@dataclass
class Cell:
    name: str
    chips: int
    config_file: Path
    config: dict
    mix: Mix
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path


def load_cell(root: Path, workload: str) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench_dir = root / spec["paths"][0]
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    config_file = root / conf["file"]
    return Cell(name=workload, chips=int(w["chips"]), config_file=config_file,
                config=json.loads(config_file.read_text()),
                mix=load_mix(bench_dir / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def load_module(path: Path):
    """Import a file by its path (metric readers and cell modules are
    named after benchmark names, which need not be identifiers)."""
    name = "bench_" + re.sub(r"\W", "_", str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_module(cell: Cell):
    return load_module(cell.config_file.with_suffix("") / "cell.py")


def metric_reader(cell: Cell, name: str) -> Callable:
    return load_module(cell.bench_dir / "metrics" / f"{name}.py").read


# ------------------------------------------------------------------ device
def require_chips(n: int):
    """The accelerator this run measures; no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     f"this benchmark has no CPU fallback")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs


def use_checkout_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program cached, set before anything compiles."""
    import jax
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------------ window
@dataclass
class Finished:
    """One workflow or service run of the window."""
    index: int
    tenant: str
    due: float                       # when it was meant to be sent
    submitted: float
    started: Optional[float] = None
    finished: Optional[float] = None
    state: str = "COMPLETE"
    run_id: Optional[str] = None
    error: Optional[str] = None
    result: Any = None               # the engine's RunResult
    inputs: Dict[str, Any] = field(default_factory=dict)
    bodies: List[bodies.Body] = field(default_factory=list)
    transfers: Dict[str, Dict[str, float]] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return self.state == "COMPLETE" and self.result is not None


@dataclass
class Obs:
    """What metric readers read: the window's runs, the cell, the trace."""
    cell: Cell
    seconds: float
    setup_s: float
    runs: List[Finished]
    plan: Any                        # the workflow's InvocationPlan
    trace: Optional[tr.Trace] = None
    device_kind: str = ""


def run_executor(cfg, entry, dep, k: int, due: float) -> Finished:
    """One workflow through ``StreamFlowExecutor.run``, submit to end."""
    from repro.core import StreamFlowExecutor
    inputs = dep.inputs(k)
    t0 = time.time()
    ex = StreamFlowExecutor.from_config(cfg)
    fin = Finished(k, "default", due, t0, started=t0, inputs=inputs)
    try:
        fin.result = ex.run(entry.workflow, entry.bindings, inputs)
    except Exception as e:                     # noqa: BLE001 — a failed run
        fin.state, fin.error = "EXECUTOR_ERROR", f"{type(e).__name__}: {e}"
    fin.finished = time.time()
    fin.bodies = bodies.RECORDER.take()
    fin.transfers = ex.data.transfer_summary()
    return fin


def closed_loop(cfg, entry, dep, seconds: float) -> List[Finished]:
    """Workflows back to back from the window's start; every workflow
    sent inside the window is run to its end."""
    runs: List[Finished] = []
    t0 = time.time()
    while time.time() - t0 < seconds:
        runs.append(run_executor(cfg, entry, dep, len(runs), time.time()))
    return runs


def open_loop(svc, dep, mix: Mix, seed: int, seconds: float,
              late: List[float]) -> List[Finished]:
    """Runs sent to the service on the mix's schedule; after the window,
    runs still in flight get ``drain_s`` to finish."""
    sends = mix.schedule(seed, seconds)
    runs: List[Finished] = []
    t0 = time.time()
    for s in sends:
        due = t0 + s.at
        wait = due - time.time()
        if wait > 0:
            time.sleep(wait)
        late.append(max(0.0, time.time() - due))
        inputs = dep.inputs(s.index)
        rid = svc.submit_document(dep.document, inputs=inputs,
                                  tenant=s.tenant)
        runs.append(Finished(s.index, s.tenant, due, time.time(),
                             inputs=inputs, run_id=rid))
    rest = t0 + seconds - time.time()
    if rest > 0:
        time.sleep(rest)
    deadline = t0 + seconds + mix.drain_s
    for fin in runs:
        rid = fin.run_id
        try:
            svc.wait(rid, timeout=max(0.0, deadline - time.time()))
        except TimeoutError:
            pass
        info = svc.status(rid)
        fin.state, fin.error = info.state, info.error
        fin.submitted, fin.started = info.submitted_at, info.started_at
        fin.finished = info.finished_at
        if info.state == "COMPLETE":
            fin.result = svc.result(rid)
        else:
            fin.state = info.state if info.terminal else "LATE"
            fin.finished = deadline
    return runs


def warm_up(cfg, dep, mix: Mix):
    """One small workflow of the cell through the cell's own entry, so
    every program the window runs is compiled before it opens.  Returns
    the service for a service mix: the window keeps the warm one."""
    from repro.core import (StreamFlowExecutor, WorkflowService,
                            load_streamflow_file)
    if mix.entry == "executor":
        warm = load_streamflow_file(dep.warmup_document, check=True)
        (entry,) = warm.workflows.values()
        StreamFlowExecutor.from_config(warm).run(
            entry.workflow, entry.bindings, dep.inputs(WARMUP))
        return None
    svc = WorkflowService(cfg)
    svc.result(svc.submit_document(dep.warmup_document,
                                   inputs=dep.inputs(WARMUP)), timeout=600)
    return svc


# ------------------------------------------------------------------ run
def _value(x):
    return None if x is None or not math.isfinite(x) else float(x)


def measure(root: Path, workload: str, seed: int, seconds: float,
            traced: bool, *, process_start: float,
            require_chip: bool = True, log=print) -> dict:
    """One run of one cell; returns the result object (not printed)."""
    cell = load_cell(root, workload)
    import jax
    if require_chip:
        require_chips(cell.chips)
    use_checkout_cache(root)
    from repro.core import load_streamflow_file

    dep = cell_module(cell).deployment(cell.config_file, cell.config,
                                       cell.mix.workload, seed)
    cfg = load_streamflow_file(dep.document, check=True)
    (entry,) = cfg.workflows.values()
    plan = entry.workflow.expand()
    svc = warm_up(cfg, dep, cell.mix)
    bodies.RECORDER.take()
    gc.collect()

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    late: List[float] = []
    t_window = time.time()
    setup_s = t_window - process_start
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        bodies.RECORDER.annotate = True
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            if cell.mix.entry == "executor":
                runs = closed_loop(cfg, entry, dep, seconds)
            else:
                runs = open_loop(svc, dep, cell.mix, seed, seconds, late)
    finally:
        if traced:
            bodies.RECORDER.annotate = False
            jax.profiler.stop_trace()
    if svc is not None:
        svc.close(cancel_pending=True, timeout=60)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs)}
    obs = Obs(cell, seconds, setup_s, runs, plan,
              device_kind=devs[0].device_kind)
    breakdown = None
    if traced:
        obs.trace = tr.read_xplane(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s(obs.trace)
        device["window_s"] = tr.window_s(obs.trace)
        breakdown = {"device_ops": [list(x) for x in tr.device_ops(obs.trace)],
                     "idle_gaps": [list(x) for x in tr.idle_gaps(obs.trace)]}

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = _value(metric_reader(cell, m["name"])(obs))
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the check runs once the window's programs are done with the chip
    gc.collect()
    checks: List[Check] = dep.check(runs, seed)
    dep.close()
    bad_runs = {f.index for f in runs if not f.ok}
    for c in checks:
        bad_runs.update(c.failed_runs)
    correct = all(c.ok for c in checks) and not bad_runs
    if late:
        log(f"generator: {len(late)} sends, latest {max(late) * 1e3:.3f} ms "
            f"behind schedule")
    for fin in runs:
        if not fin.ok:
            log(f"run {fin.index}: {fin.state} {fin.error or ''}")
    result = {"correct": bool(correct), "attempted": len(runs),
              "failed": len(bad_runs), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # a reading that gave no number prints as null, never as NaN
    result["checks"] = {c.name: {"value": _value(c.value), "limit": c.limit}
                        for c in checks}
    return result


def process_start_time() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/self/stat") as fh:
            start = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as fh:
            btime = next(int(l.split()[1]) for l in fh if l.startswith("btime"))
        return btime + start / ticks
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    start = process_start_time()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)
    try:
        result = measure(ROOT, a.workload, a.seed, a.seconds, bool(a.trace),
                         process_start=start, log=log)
    except NoChip as e:
        log(f"chip benchmark: {e}")
        return 3
    for name, c in result["checks"].items():
        log(f"{name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0
