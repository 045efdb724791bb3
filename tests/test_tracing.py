"""Spans inside the engine (``repro.core.tracing``): off, at the cost of
one check, without a JAX profiler session; inside one, each span at the
place its work happens, kept with the run's result and written to the
profiler's own trace."""
import builtins
import glob
import pickle
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro.configs import recovery_demo
from repro.core import (CheckpointConfig, FaultConfig, ModelSpec,
                        StreamFlowExecutor, tracing)
from repro.core.streamflow_file import Binding
from repro.core.workflow import Step, Workflow

SITE = {"site": ModelSpec("site", "local",
                          {"services": {"svc": {"replicas": 4}}})}
BIND = [Binding("/", "site", "svc")]
NS = 1e9


def _diamond():
    return recovery_demo.build_workflow(n_blocks=3, block_rows=32, rounds=3)


def _jit_workflow():
    """One step whose body compiles a program of its own."""
    def fn(inputs, ctx):
        y = jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0) + inputs["x"])
        return {"y": float(y.sum())}
    wf = Workflow("jit")
    wf.add_step(Step("/jit", fn, {"x": "x"}, ("y",)))
    return wf


def _run(wf, inputs, **kw):
    ex = StreamFlowExecutor(SITE, fault=FaultConfig(speculative=False), **kw)
    return ex.run(wf, BIND, inputs)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A diamond with its journal on, and a body that compiles, run
    inside one profiler session."""
    d = tmp_path_factory.mktemp("traced")
    journal = d / "journal.jsonl"
    with jax.profiler.trace(str(d / "trace")):
        diamond = _run(_diamond(), {"seed": 3}, checkpoint=CheckpointConfig(
            journal_path=str(journal), fsync=False))
        compiled = _run(_jit_workflow(), {"x": 2.0})
    (xplane,) = glob.glob(str(d / "trace" / "**" / "*.xplane.pb"),
                          recursive=True)
    return SimpleNamespace(diamond=diamond, compiled=compiled,
                           journal=journal, xplane=xplane)


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _root(s, by_id):
    while s.parent in by_id:
        s = by_id[s.parent]
    return s


def test_off_without_a_profiler_session(monkeypatch):
    made = []
    for name in ("_Open", "_Bind", "Span", "SpanLog"):
        real = getattr(tracing, name)
        monkeypatch.setattr(
            tracing, name,
            lambda *a, _real=real, _n=name, **kw: made.append(_n)
            or _real(*a, **kw))
    assert not tracing.enabled()
    res = _run(_diamond(), {"seed": 3})
    assert res.spans is None
    assert made == []


def test_off_where_the_profiler_cannot_be_imported(monkeypatch, tmp_path):
    real = builtins.__import__

    def no_profiler(name, *a, **kw):
        if name.startswith("jax._src.lib"):
            raise ImportError(name)
        return real(name, *a, **kw)
    monkeypatch.setattr(builtins, "__import__", no_profiler)
    probe, trace_me = tracing._probe()
    monkeypatch.setattr(builtins, "__import__", real)
    with jax.profiler.trace(str(tmp_path)):
        assert tracing._probe()[0]()
        assert trace_me is None and not probe()


def test_one_work_span_inside_each_job_event(traced):
    res = traced.diamond
    by_id = {s.id: s for s in res.spans}
    works = {s.key[1:]: s for s in _named(res.spans, "sf:work")}
    done = [e for e in res.events if e.status == "completed"]
    assert len(done) == len(works) == len(_named(res.spans, "sf:work")) == 5
    for e in done:
        w = works[(e.step, e.attempt)]
        assert w.parent is None and w.key[0] == ""
        # time.time() and time.time_ns() are one clock; floats round
        assert e.start * NS - 1e3 <= w.start_ns <= w.end_ns <= e.end * NS + 1e3
    names = set()
    for s in res.spans:
        w = _root(s, by_id)
        if w.name != "sf:work" or s is w:
            continue
        names.add(s.name)
        assert s.key == w.key
        assert w.start_ns <= s.start_ns <= s.end_ns <= w.end_ns
        assert s.cpu_ns is not None and s.cpu_ns >= 0
    assert {"sf:stage_in", "sf:body", "sf:decode", "sf:encode",
            "sf:cas_put"} <= names


def test_ticks_hold_fire_schedule_and_harvest(traced):
    spans = traced.diamond.spans
    by_id = {s.id: s for s in spans}
    ticks = _named(spans, "sf:tick")
    assert ticks and all(t.parent is None and t.key is None for t in ticks)
    for name in ("sf:fire", "sf:schedule", "sf:harvest"):
        got = _named(spans, name)
        assert len(got) == len(ticks)
        assert all(by_id[s.parent].name == "sf:tick" for s in got)
    assert {(s.attrs["model"], s.attrs["event"])
            for s in _named(spans, "sf:deploy")} == {("site", "deploy"),
                                                     ("site", "undeploy")}


def test_bytes_are_the_payloads(traced):
    res = traced.diamond
    by_parent = {}
    for s in res.spans:
        by_parent.setdefault(s.parent, []).append(s)
    for w in _named(res.spans, "sf:work"):
        kids = sorted(by_parent.get(w.id, []), key=lambda s: s.start_ns)
        enc = [s.attrs["bytes"] for s in kids if s.name == "sf:encode"]
        put = [s.attrs["bytes"] for s in kids if s.name == "sf:cas_put"]
        assert enc and enc == put
        if w.key[1] == "/reduce":
            assert enc == [len(pickle.dumps(res.outputs["combined"],
                                            pickle.HIGHEST_PROTOCOL))]
    moved = sorted((s.attrs["kind"], s.attrs["bytes"])
                   for s in _named(res.spans, "sf:transfer"))
    assert moved == sorted((r.kind, r.bytes) for r in res.transfers
                           if r.kind != "collect")
    assert any(b > 0 for _, b in moved)


def test_one_journal_span_per_journal_line(traced):
    lines = traced.journal.read_text().splitlines()
    spans = _named(traced.diamond.spans, "sf:journal")
    assert len(spans) == len(lines) > 10
    assert {s.attrs["kind"] for s in spans} >= {"run_begin", "step",
                                                "run_end"}


def test_a_compile_names_its_invocation(traced):
    spans = traced.compiled.spans
    by_id = {s.id: s for s in spans}
    (work,) = _named(spans, "sf:work")
    compiles = [s for s in _named(spans, "sf:compile")
                if s.attrs["fun"] == "jit(<lambda>)"]
    assert compiles
    for c in compiles:
        assert c.key == work.key and by_id[c.parent].name == "sf:body"
        assert work.start_ns <= c.start_ns <= c.end_ns <= work.end_ns


def test_spans_reach_the_profilers_trace(traced):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(traced.xplane)
    names = {ev.name for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert {"sf:tick", "sf:work", "sf:body", "sf:journal"} <= names
