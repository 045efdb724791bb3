"""A configuration, a traffic mix and a per-layer metric added as new
files are found by name: no file of the harness names them."""
import json
import time

from benchmarks.chip import harness

CELL = '''
from benchmarks.chip.common import Check


class Deployment:
    planned = 2

    def __init__(self, workload):
        doc = {
            "version": "v1.0",
            "models": {"here": {"type": "local", "config": {"services": {
                "svc": {"replicas": 1, "cores": 1, "memory_gb": 1}}}}},
            "tools": {"double": {
                "inputs": {"x": "int"}, "outputs": {"y": "int"},
                "requirements": {"cores": 1, "memory_gb": 1},
                "implementation": {"module": "toy_tools",
                                   "factory": "double_tool",
                                   "args": {"times": workload["times"]}}}},
            "workflows": {"toy": {
                "type": "declarative", "inputs": {"x": "int"},
                "steps": {"/a": {"tool": "double", "in": {"x": "x"},
                                 "out": {"y": "m"}},
                          "/b": {"tool": "double", "in": {"x": "m"}}},
                "bindings": [{"step": "/", "target": {
                    "model": "here", "service": "svc"}}]}}}
        self.document = self.warmup_document = doc
        self.times = workload["times"]

    def inputs(self, k):
        return {"x": k}

    def check(self, runs, seed):
        bad = [f.index for f in runs
               if f.result.outputs["y"] != f.inputs["x"] * self.times ** 2]
        return [Check("wrong_answers", float(len(bad)), 0.0, tuple(bad))]

    def control(self, seed):
        return {}

    def close(self):
        pass


def deployment(config_file, config, workload, seed):
    return Deployment(workload)
'''

TOOLS = '''
def double_tool(times):
    def fn(inputs, ctx):
        return {"y": inputs["x"] * times}
    return fn
'''

METRIC = '''
def read(obs):
    return float(sum(len(f.result.events) for f in obs.runs if f.ok))
'''


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    (bench / "configs" / "toy").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "metrics").mkdir()
    (bench / "configs" / "toy.json").write_text('{"name": "toy"}')
    (bench / "configs" / "toy" / "cell.py").write_text(CELL)
    (bench / "traffic" / "toy-closed.json").write_text(json.dumps(
        {"entry": "executor", "arrival": "closed",
         "workload": {"times": 3}}))
    (bench / "metrics" / "events.toy.py").write_text(METRIC)
    (bench / "metrics" / "setup_s.py").write_text(
        "def read(obs):\n    return obs.setup_s\n")
    (tmp_path / "toy_tools.py").write_text(TOOLS)
    monkeypatch.syspath_prepend(str(tmp_path))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "toy", "source": "https://example.org",
                     "file": "bench/configs/toy.json", "reduced": [],
                     "why": "a toy"}],
        "workloads": [{"name": "toy.batch", "config": "toy",
                       "traffic": "toy-closed", "chips": 1, "why": "toy"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "events.toy", "unit": "1", "better": "lower",
                       "source": "program_counter", "layer": "engine loop",
                       "moves": "setup_s", "workloads": ["toy.batch"]}]}))

    cell = harness.load_cell(tmp_path, "toy.batch")
    assert [m["name"] for m in cell.per_layer] == ["events.toy"]
    assert cell.mix.workload == {"times": 3}
    for traced in (False, True):
        r = harness.measure(tmp_path, "toy.batch", 2**31 + 7, 0.3, traced,
                            process_start=time.time(), require_chip=False,
                            log=lambda m: None)
        assert r["correct"] and r["attempted"] >= 1 and r["failed"] == 0
        assert list(r["checks"]) == ["wrong_answers"]
        if traced:
            assert r["metrics"]["events.toy"]["value"] >= 2
        else:
            assert set(r["metrics"]) == {"setup_s"}
