"""``sched_probes_per_launch.batch``: jobs the policy was asked about per
job placed, on hand-made spans and on the span log of a traced run on the
CPU; no number from spans that do not carry the counts."""
from types import SimpleNamespace

import jax
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.tiny import ROOT
from repro.core import FaultConfig, ModelSpec, StreamFlowExecutor
from repro.core.executor import RunResult
from repro.core.streamflow_file import Binding
from repro.core.tracing import Span
from repro.core.workflow import Step, Workflow

NAME = "sched_probes_per_launch.batch"


def read(obs):
    return harness.load_module(ROOT / "benchmarks" / "chip" / "metrics"
                               / f"{NAME}.py").read(obs)


def obs_of(*runs):
    """One completed workflow per list of ``sf:schedule`` attributes."""
    out = []
    for attrs in runs:
        made = [Span("sf:schedule", 0, 1, id=i, attrs=a)
                for i, a in enumerate(attrs)]
        made.append(Span("sf:tick", 0, 2, id=len(made)))
        out.append(SimpleNamespace(ok=True, result=RunResult(
            {}, [], [], [], 1.0, spans=made)))
    return SimpleNamespace(runs=out)


def test_probed_over_placed():
    obs = obs_of([{"waiting": 9, "probed": 9, "placed": 3},
                  {"waiting": 6, "skipped": True},
                  {"waiting": 0}],
                 [{"waiting": 4, "probed": 3, "placed": 1}])
    assert read(obs) == pytest.approx(12 / 4)


def test_no_counts_no_number():
    # the spans of an engine that records no counts
    assert read(obs_of([{}, {}])) is None
    assert read(obs_of([])) is None
    # counted passes that placed nothing
    assert read(obs_of([{"waiting": 3, "probed": 3, "placed": 0}])) is None
    obs = obs_of([{"probed": 1, "placed": 1}])
    obs.runs[0].result.spans = None
    assert read(obs) is None
    obs.runs[0].result = SimpleNamespace(events=[])
    assert read(obs) is None


def test_recorded_span_log_of_a_wide_fan_out(tmp_path):
    """The span log of a 1 -> 200 -> 1 run on 8 one-job replicas, traced
    on the CPU: each placement cost about one probe."""
    n = 200
    wf = Workflow("fan")
    wf.add_step(Step("/src", lambda i, c: {f"x{k}": k for k in range(n)},
                     {"s": "seed"}, tuple(f"x{k}" for k in range(n))))
    for k in range(n):
        wf.add_step(Step(f"/w{k}", lambda i, c, k=k: {f"y{k}": i["x"]},
                         {"x": f"x{k}"}, (f"y{k}",)))
    wf.add_step(Step("/sink", lambda i, c: {"z": sum(i.values())},
                     {f"y{k}": f"y{k}" for k in range(n)}, ("z",)))
    ex = StreamFlowExecutor(
        {"site": ModelSpec("site", "local",
                           {"services": {"svc": {"replicas": 8}}})},
        fault=FaultConfig(speculative=False))
    with jax.profiler.trace(str(tmp_path)):
        res = ex.run(wf, [Binding("/", "site", "svc")], {"seed": 0})
    obs = SimpleNamespace(runs=[SimpleNamespace(ok=True, result=res)])
    passes = [s for s in res.spans if s.name == "sf:schedule"]
    probed = sum(s.attrs.get("probed", 0) for s in passes)
    assert read(obs) == pytest.approx(probed / (n + 2))
    assert 1.0 <= read(obs) <= 3.0
