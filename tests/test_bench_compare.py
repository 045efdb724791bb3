"""The CI benchmark-regression gate: metric extraction + pass/fail
semantics against the committed baseline."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks import compare  # noqa: E402


def _bench(*, scratch=3.0, resumed=1.0,
           scratch_steps=13, resumed_steps=10,
           mgmt_direct=100, mgmt_baseline=100_000, mk_direct=0.7,
           mk_mgmt=1.0, direct_n=8,
           mk_unrolled=2.4, mk_scatter=2.3, scatter_sites=2,
           scatter_planned=50, scatter_done=50,
           tput_pooled=140.0, tput_perrun=100.0,
           p99_pooled=0.03, p99_perrun=0.6,
           mk_cold=2.0, mk_warm=0.1, bytes_cold=1_000_000, bytes_warm=40,
           warm_memoized=34, warm_invocations=34,
           mk_static=0.8, mk_elastic=0.26, wasted=2, useful=16,
           lb_ratio_unrolled=1.2, lb_ratio_scatter=1.8):
    return {"results": {
        "recovery_makespan": [
            {"phase": "from-scratch", "makespan_s": scratch,
             "steps_executed": scratch_steps},
            {"phase": "resumed", "makespan_s": resumed,
             "steps_executed": resumed_steps},
        ],
        "routing_data_plane": [
            {"mode": "management", "makespan_s": mk_mgmt,
             "mgmt_bytes": mgmt_baseline, "direct_n": 0},
            {"mode": "direct", "makespan_s": mk_direct,
             "mgmt_bytes": mgmt_direct, "direct_n": direct_n},
        ],
        "scatter_width": [
            {"mode": "hand-unrolled", "makespan_s": mk_unrolled,
             "count_sites": 1, "planned": 49, "invocations": 49},
            {"mode": "scatter", "makespan_s": mk_scatter,
             "count_sites": scatter_sites, "planned": scatter_planned,
             "invocations": scatter_done},
        ],
        "service_multitenant": [
            {"variant": "per-run", "throughput_rps": tput_perrun,
             "lat_p99_s": p99_perrun, "deploys": 360},
            {"variant": "pooled", "throughput_rps": tput_pooled,
             "lat_p99_s": p99_pooled, "deploys": 2},
        ],
        "cache_memoization": [
            {"phase": "cold", "invocations": 34, "executed": 34,
             "memoized": 0, "makespan_s": mk_cold,
             "transfer_bytes": bytes_cold},
            {"phase": "warm", "invocations": warm_invocations,
             "executed": warm_invocations - warm_memoized,
             "memoized": warm_memoized, "makespan_s": mk_warm,
             "transfer_bytes": bytes_warm},
        ],
        "autoscale_elasticity": [
            {"mode": "static", "makespan_s": mk_static,
             "useful_invocations": useful, "wasted_invocations": 0},
            {"mode": "elastic", "makespan_s": mk_elastic,
             "useful_invocations": useful, "wasted_invocations": 0},
            {"mode": "preempted", "makespan_s": mk_elastic,
             "useful_invocations": useful, "wasted_invocations": wasted},
        ],
        "analyze_prediction": [
            {"mode": "hand-unrolled", "ratio": lb_ratio_unrolled,
             "predicted_lb_s": 1.0, "measured_s": lb_ratio_unrolled,
             "errors": 0},
            {"mode": "scatter", "ratio": lb_ratio_scatter,
             "predicted_lb_s": 1.0, "measured_s": lb_ratio_scatter,
             "errors": 0},
        ],
    }}


def test_extract_metrics():
    m = compare.extract_metrics(_bench())
    assert m["recovery_speedup"] == pytest.approx(3.0)
    assert m["recovery_steps_ratio"] == pytest.approx(10 / 13)
    assert m["routing_makespan_ratio"] == pytest.approx(0.7)
    assert m["routing_mgmt_bytes_ratio"] == pytest.approx(0.001)
    assert m["routing_direct_transfers"] == 8.0
    assert m["scatter_makespan_ratio"] == pytest.approx(2.3 / 2.4)
    assert m["scatter_count_sites"] == 2.0
    assert m["scatter_invocations_ratio"] == pytest.approx(1.0)
    assert m["service_throughput_ratio"] == pytest.approx(1.4)
    assert m["service_p99_ratio"] == pytest.approx(0.05)
    assert m["cache_warm_makespan_ratio"] == pytest.approx(0.05)
    assert m["cache_bytes_ratio"] == pytest.approx(4e-05)
    assert m["cache_hit_rate"] == pytest.approx(1.0)
    assert m["autoscale_makespan_ratio"] == pytest.approx(0.325)
    assert m["autoscale_wasted_work_ratio"] == pytest.approx(0.125)
    assert m["analyze_lb_ratio_unrolled"] == pytest.approx(1.2)
    assert m["analyze_lb_ratio_scatter"] == pytest.approx(1.8)


def _run(tmp_path, bench, baseline_bench=None, argv_extra=()):
    bj = tmp_path / "bench.json"
    bj.write_text(json.dumps(bench))
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"metrics": compare.extract_metrics(
        baseline_bench or bench)}))
    return compare.main([str(bj), "--baseline", str(base), *argv_extra])


def test_gate_passes_on_baseline_itself(tmp_path, capsys):
    assert _run(tmp_path, _bench()) == 0
    assert "all benchmark-regression checks passed" in capsys.readouterr().out


def test_gate_fails_on_makespan_regression(tmp_path, capsys):
    # direct routing suddenly slower than the two-step control
    assert _run(tmp_path, _bench(mk_direct=1.05)) == 1
    assert "routing_makespan_ratio" in capsys.readouterr().out


def test_gate_fails_on_mgmt_bytes_regression(tmp_path, capsys):
    # bytes leak back through the management node (hard bound 0.10)
    assert _run(tmp_path, _bench(mgmt_direct=50_000)) == 1
    out = capsys.readouterr().out
    assert "routing_mgmt_bytes_ratio" in out and "hard bound" in out


def test_gate_fails_on_metric_missing_from_baseline(tmp_path, capsys):
    # a metric added without refreshing baseline.json fails loudly
    bj = tmp_path / "bench.json"
    bj.write_text(json.dumps(_bench()))
    metrics = compare.extract_metrics(_bench())
    del metrics["routing_direct_transfers"]
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"metrics": metrics}))
    assert compare.main([str(bj), "--baseline", str(base)]) == 1
    out = capsys.readouterr().out
    assert "routing_direct_transfers" in out and "no committed baseline" in out


def test_gate_tolerates_noise_within_rel_tol(tmp_path):
    good = _bench()
    noisy = _bench(resumed=1.3, mk_direct=0.75)
    assert _run(tmp_path, noisy, baseline_bench=good) == 0


def test_gate_fails_when_resume_recomputes_everything(tmp_path, capsys):
    assert _run(tmp_path, _bench(resumed_steps=13)) == 1
    assert "recovery_steps_ratio" in capsys.readouterr().out


def test_gate_fails_when_scatter_stops_spreading(tmp_path, capsys):
    assert _run(tmp_path, _bench(scatter_sites=1)) == 1
    out = capsys.readouterr().out
    assert "scatter_count_sites" in out and "hard bound" in out


def test_gate_fails_when_scatter_loses_invocations(tmp_path, capsys):
    assert _run(tmp_path, _bench(scatter_done=49)) == 1
    assert "scatter_invocations_ratio" in capsys.readouterr().out


def test_gate_fails_when_scatter_costs_makespan(tmp_path, capsys):
    # well past the 1.25x hard ceiling: the expression itself got slow
    assert _run(tmp_path, _bench(mk_scatter=3.2)) == 1
    assert "scatter_makespan_ratio" in capsys.readouterr().out


def test_gate_fails_when_pooling_loses_throughput(tmp_path, capsys):
    # pooled service slower than deploying per run (hard bound 1.05)
    assert _run(tmp_path, _bench(tput_pooled=95.0)) == 1
    out = capsys.readouterr().out
    assert "service_throughput_ratio" in out and "hard bound" in out


def test_gate_fails_when_pooled_tail_balloons(tmp_path, capsys):
    # pooled p99 back at the per-run control's level: the pool stopped
    # absorbing site bring-up (hard ceiling 0.5)
    assert _run(tmp_path, _bench(p99_pooled=0.55)) == 1
    assert "service_p99_ratio" in capsys.readouterr().out


def test_gate_fails_when_warm_rerun_stops_hitting(tmp_path, capsys):
    # memo keys or verification silently broke: warm run re-executes
    assert _run(tmp_path, _bench(warm_memoized=20)) == 1
    out = capsys.readouterr().out
    assert "cache_hit_rate" in out and "hard bound" in out


def test_gate_fails_when_warm_rerun_moves_bytes(tmp_path, capsys):
    # digest aliasing broke: the warm run paid the copies again
    assert _run(tmp_path, _bench(bytes_warm=900_000)) == 1
    out = capsys.readouterr().out
    assert "cache_bytes_ratio" in out and "hard bound" in out


def test_gate_fails_when_memoization_stops_saving_time(tmp_path, capsys):
    # warm makespan back at the cold level (hard ceiling 0.5)
    assert _run(tmp_path, _bench(mk_warm=1.9)) == 1
    assert "cache_warm_makespan_ratio" in capsys.readouterr().out


def test_gate_fails_when_elasticity_stops_helping(tmp_path, capsys):
    # elastic makespan back at the static control's (hard ceiling 0.80)
    assert _run(tmp_path, _bench(mk_elastic=0.78)) == 1
    out = capsys.readouterr().out
    assert "autoscale_makespan_ratio" in out and "hard bound" in out


def test_gate_fails_when_preemption_waste_explodes(tmp_path, capsys):
    # revocations burning more than half an attempt per useful
    # invocation (hard ceiling 0.5)
    assert _run(tmp_path, _bench(wasted=9)) == 1
    out = capsys.readouterr().out
    assert "autoscale_wasted_work_ratio" in out and "hard bound" in out


def test_gate_fails_when_lower_bound_is_unsound(tmp_path, capsys):
    # measured below the "lower bound": the prediction overpromised
    assert _run(tmp_path, _bench(lb_ratio_scatter=0.93)) == 1
    out = capsys.readouterr().out
    assert "analyze_lb_ratio_scatter" in out and "hard bound" in out


def test_gate_fails_when_prediction_goes_vacuous(tmp_path, capsys):
    # measured over 3x the prediction: the bound stopped being useful
    assert _run(tmp_path, _bench(lb_ratio_unrolled=3.4)) == 1
    out = capsys.readouterr().out
    assert "analyze_lb_ratio_unrolled" in out and "hard bound" in out


def test_gate_fails_on_missing_benchmark_section(tmp_path, capsys):
    bench = _bench()
    del bench["results"]["routing_data_plane"]
    bj = tmp_path / "bench.json"
    bj.write_text(json.dumps(bench))
    assert compare.main([str(bj)]) == 1


def test_write_baseline_roundtrip(tmp_path):
    bench = _bench()
    bj = tmp_path / "bench.json"
    bj.write_text(json.dumps(bench))
    base = tmp_path / "baseline.json"
    assert compare.main([str(bj), "--baseline", str(base),
                         "--write-baseline"]) == 0
    assert compare.main([str(bj), "--baseline", str(base)]) == 0


def test_committed_baseline_has_every_metric():
    with open(compare.DEFAULT_BASELINE, encoding="utf-8") as fh:
        committed = json.load(fh)["metrics"]
    assert set(committed) == {m.name for m in compare.METRICS}
