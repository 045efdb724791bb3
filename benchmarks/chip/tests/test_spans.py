"""The readers of the engine's spans: the anchor alignment and the idle
data-plane share on a hand-made trace, no number without spans, and a
number from every reader in the tiny cells traced on the CPU."""
import json
import time
from types import SimpleNamespace

import pytest

from benchmarks.chip import harness, spans as sp, trace as tr
from benchmarks.chip.bodies import Body
from benchmarks.chip.tests.tiny import LIMITS, ROOT, tiny_root
from repro.core.executor import JobEvent, RunResult
from repro.core.tracing import Span

MS = 1e6
HOST0 = 1.7e18                   # host clock of the window's start, ns
OFFSET = -HOST0 + 5 * MS         # trace time minus host time
NEW = ("engine_self_ms.batch", "harvest_lag_ms.batch", "codec_ms.batch",
       "cas_put_ms.batch", "journal_ms.batch", "idle_dataplane_pct.batch")


def host(ms):
    return HOST0 + ms * MS


def made(jitter_ms=(0.0, 0.0, 0.0)):
    """One workflow: tool bodies of /a at 10-20 and 30-40 ms and of /b at
    60-70 ms (host clock); on the trace, the chip busy 0-12 and 50-100 ms
    of a 0-100 ms window.  Data-plane spans at 5-25 ms (encode) and 20-55
    ms (transfer), host clock: the chip idles under them 12-50 ms.
    ``jitter_ms`` moves each tool span on the trace."""
    spans = [Span("sf:tick", int(host(0)), int(host(4)), id=1),
             Span("sf:tick", int(host(40)), int(host(42)), id=2),
             Span("sf:work", int(host(8)), int(host(21)), id=3,
                  key=("", "/a", 0)),
             Span("sf:encode", int(host(5)), int(host(25)), id=4),
             Span("sf:transfer", int(host(20)), int(host(55)), id=5),
             Span("sf:cas_put", int(host(22)), int(host(23)), id=6),
             Span("sf:journal", int(host(1)), int(host(2)), id=7)]
    events = [JobEvent("/a", "m", "r", host(7) / 1e9, host(22) / 1e9, 0,
                       "completed"),
              JobEvent("/b", "m", "r", host(50) / 1e9, host(71) / 1e9, 0,
                       "completed")]
    result = RunResult({}, events, [], [], 1.0, spans=spans)
    bodies = [Body("/a", (), host(10) / 1e9, host(20) / 1e9),
              Body("/a", (), host(30) / 1e9, host(40) / 1e9),
              Body("/b", (), host(60) / 1e9, host(70) / 1e9)]
    tools = [(step, (host(s) + OFFSET + j * MS, host(e) + OFFSET + j * MS))
             for (step, s, e), j in zip((("/a", 10, 20), ("/a", 30, 40),
                                         ("/b", 60, 70)), jitter_ms)]
    trace = tr.Trace(window=(host(0) + OFFSET, host(100) + OFFSET),
                     ops={"/device:TPU:0|f": [
                         (host(0) + OFFSET, host(12) + OFFSET),
                         (host(50) + OFFSET, host(100) + OFFSET)]},
                     tools=tools)
    run = SimpleNamespace(ok=True, result=result, bodies=bodies)
    return SimpleNamespace(runs=[run], trace=trace)


def test_anchors_put_spans_on_the_trace_clock():
    obs = made()
    assert sp.trace_offset_ns(obs) == pytest.approx(OFFSET, abs=1e3)
    # encode 5-25 and transfer 20-55 cover 5-55; busy until 12 and from 50
    assert sp.idle_dataplane_pct(obs) == pytest.approx(38.0, abs=1e-3)


def test_disagreeing_anchors_give_no_number():
    assert sp.trace_offset_ns(made((0.0, 0.4, 0.2))) is not None
    obs = made((0.0, 3.0, 6.0))
    assert sp.offset_spread_ns(sp.anchor_offsets(obs)) > sp.MAX_SPREAD_NS
    assert sp.trace_offset_ns(obs) is None
    assert sp.idle_dataplane_pct(obs) is None


def test_span_sums():
    obs = made()
    ms = dict(abs=1e-3)               # the host clock's floats round
    assert sp.engine_self_ms(obs) == pytest.approx(3.0, **ms)  # 6 ms / 2
    assert sp.harvest_lag_ms(obs) == pytest.approx(1.0, **ms)  # /a only
    assert sp.total_ms(obs, ("sf:encode", "sf:decode")) \
        == pytest.approx(20.0, **ms)
    assert sp.total_ms(obs, ("sf:cas_put",)) == pytest.approx(1.0, **ms)
    assert sp.total_ms(obs, ("sf:journal",)) == pytest.approx(1.0, **ms)
    assert sp.total_ms(obs, ("sf:deploy",)) is None


@pytest.mark.parametrize("name", NEW)
def test_no_spans_no_number(name):
    read = harness.load_module(ROOT / "benchmarks" / "chip" / "metrics"
                               / f"{name}.py").read
    obs = made()
    obs.runs[0].result.spans = None
    assert read(obs) is None
    # an engine whose results have no spans at all
    obs.runs[0].result = SimpleNamespace(events=obs.runs[0].result.events)
    assert read(obs) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", ["sc-minicpm.batch", "montage.batch"])
def test_tiny_cells_traced_give_every_new_metric(root, cell, monkeypatch):
    c = harness.load_cell(root, cell)
    for k, v in LIMITS.get(c.config_file.stem, {}).items():
        monkeypatch.setitem(harness.cell_module(c).LIMITS, k, v)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in spec["per_layer"]
              if m["name"] in NEW and cell in m["workloads"]}
    assert listed
    r = harness.measure(root, cell, 2**31 + 99, 0.5, True,
                        process_start=time.time(), require_chip=False,
                        log=lambda m: None)
    assert r["correct"], r["checks"]
    assert listed <= set(r["metrics"]), sorted(r["metrics"])
