"""``resident_pct.batch``: the share of token bytes held on the device,
on hand-made spans, no number from an engine that holds nothing, and a
number from both tiny cells traced on the CPU."""
import json
import time
from types import SimpleNamespace

import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.tiny import LIMITS, ROOT, tiny_root
from repro.core.executor import RunResult
from repro.core.tracing import Span

NAME = "resident_pct.batch"


def read(obs):
    return harness.load_module(ROOT / "benchmarks" / "chip" / "metrics"
                               / f"{NAME}.py").read(obs)


def obs_of(*runs):
    """One completed workflow per list of (span name, bytes)."""
    out = []
    for spans in runs:
        made = [Span(name, 0, 1, id=i, attrs={"bytes": n})
                for i, (name, n) in enumerate(spans)]
        out.append(SimpleNamespace(ok=True, result=RunResult(
            {}, [], [], [], 1.0, spans=made)))
    return SimpleNamespace(runs=out)


def test_held_over_held_and_encoded():
    obs = obs_of([("sf:hold", 600), ("sf:encode", 100),
                  ("sf:cas_put", 100), ("sf:decode", 100)],
                 [("sf:hold", 300), ("sf:encode", 200)])
    assert read(obs) == pytest.approx(75.0)
    assert read(obs_of([("sf:hold", 10)])) == pytest.approx(100.0)


def test_no_hold_no_number():
    # an engine that pickles every token records encodes and no hold
    assert read(obs_of([("sf:encode", 100), ("sf:cas_put", 100)])) is None
    assert read(obs_of([])) is None
    obs = obs_of([("sf:hold", 10)])
    obs.runs[0].result.spans = None
    assert read(obs) is None
    obs.runs[0].result = SimpleNamespace(events=[])
    assert read(obs) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", ["sc-minicpm.batch", "montage.batch"])
def test_tiny_cells_traced_give_the_share(root, cell, monkeypatch):
    c = harness.load_cell(root, cell)
    for k, v in LIMITS.get(c.config_file.stem, {}).items():
        monkeypatch.setitem(harness.cell_module(c).LIMITS, k, v)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert cell in entry["workloads"]
    r = harness.measure(root, cell, 2**31 + 77, 0.5, True,
                        process_start=time.time(), require_chip=False,
                        log=lambda m: None)
    assert r["correct"], r["checks"]
    assert 0.0 < r["metrics"][NAME]["value"] <= 100.0
