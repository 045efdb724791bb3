"""Benchmark-regression gate: diff a CI ``bench.json`` against the
committed ``benchmarks/baseline.json`` and FAIL on regression.

Raw wall-clock numbers on shared CI runners are too noisy to gate on, so
every gated metric is *self-normalizing* — a ratio between two variants
measured in the same process (direct vs two-step routing, pooled vs
per-run service) or a deterministic structural count (bytes through the
management node, number of direct transfers).  Each metric carries:

  * a committed baseline value (``benchmarks/baseline.json``),
  * a relative tolerance — how much worse than baseline is still noise,
  * an optional hard bound — the claim itself (e.g. "direct routing must
    move fewer bytes through the management node"), enforced regardless
    of what the baseline says.

Usage:
  python benchmarks/compare.py bench.json                # gate (CI)
  python benchmarks/compare.py bench.json --write-baseline
                                                         # refresh baseline

Exit codes: 0 = pass, 1 = regression / missing metric / unreadable input.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_BASELINE = os.path.join(HERE, "baseline.json")


def _rows_by(results: dict, bench: str, key: str) -> Dict[str, dict]:
    rows = results.get(bench)
    if rows is None:
        raise KeyError(f"bench.json has no results for {bench!r} "
                       f"(was it in --only?)")
    return {r[key]: r for r in rows}


def _recovery_speedup(results: dict) -> float:
    """From-scratch over resumed makespan — the PR-3 claim that journal
    recovery re-executes only the lost frontier.  Wall-sensitive (the
    absolute value swings with machine load), so only the hard bound
    carries weight; the structural claim lives in _recovery_steps_ratio."""
    by = _rows_by(results, "recovery_makespan", "phase")
    return (by["from-scratch"]["makespan_s"]
            / max(by["resumed"]["makespan_s"], 1e-9))


def _recovery_steps_ratio(results: dict) -> float:
    """Share of the workflow's steps the resumed run re-executed —
    deterministic (the crash point is fixed), unlike the wall ratio.
    1.0 would mean resume recomputed everything."""
    by = _rows_by(results, "recovery_makespan", "phase")
    return (by["resumed"]["steps_executed"]
            / max(by["from-scratch"]["steps_executed"], 1))


def _routing_makespan_ratio(results: dict) -> float:
    """Direct over management-routed makespan — the PR-4 claim that the
    topology planner beats the two-step baseline.  Lower is better."""
    by = _rows_by(results, "routing_data_plane", "mode")
    return (by["direct"]["makespan_s"]
            / max(by["management"]["makespan_s"], 1e-9))


def _routing_mgmt_bytes_ratio(results: dict) -> float:
    """Share of the baseline's management-node bytes that direct routing
    still moves through the star.  Lower is better; structural, so the
    hard bound is tight."""
    by = _rows_by(results, "routing_data_plane", "mode")
    return (by["direct"]["mgmt_bytes"]
            / max(by["management"]["mgmt_bytes"], 1))


def _routing_direct_transfers(results: dict) -> float:
    """Direct transfers actually executed — zero means the planner never
    took the declared link and the feature is silently off."""
    by = _rows_by(results, "routing_data_plane", "mode")
    return float(by["direct"]["direct_n"])


def _scatter_makespan_ratio(results: dict) -> float:
    """Scatter over hand-unrolled makespan on the Fig.9 hybrid — the PR-5
    claim that the Port/Token scatter expression costs nothing vs
    unrolling the DAG by hand (its per-invocation multi-site placement
    may even win).  Lower is better."""
    by = _rows_by(results, "scatter_width", "mode")
    return (by["scatter"]["makespan_s"]
            / max(by["hand-unrolled"]["makespan_s"], 1e-9))


def _scatter_count_sites(results: dict) -> float:
    """Distinct sites that hosted /count invocations in scatter mode —
    below 2 means one declared scatter no longer spreads across the
    hybrid and per-invocation placement is silently off."""
    by = _rows_by(results, "scatter_width", "mode")
    return float(by["scatter"]["count_sites"])


def _scatter_invocations_ratio(results: dict) -> float:
    """Executed over planned invocations in scatter mode — deterministic;
    anything but 1.0 means the expansion lost or duplicated work."""
    by = _rows_by(results, "scatter_width", "mode")
    return (by["scatter"]["invocations"]
            / max(by["scatter"]["planned"], 1))


def _service_throughput_ratio(results: dict) -> float:
    """Pooled over per-run service throughput under the same bursty
    arrivals — the PR-6 claim that the deployment pool amortizes site
    bring-up across runs.  Higher is better."""
    by = _rows_by(results, "service_multitenant", "variant")
    return (by["pooled"]["throughput_rps"]
            / max(by["per-run"]["throughput_rps"], 1e-9))


def _service_p99_ratio(results: dict) -> float:
    """Pooled over per-run steady-state p99 run latency — with a warm
    pool a run never waits on site bring-up, so its tail must sit far
    below the per-run control's.  Lower is better."""
    by = _rows_by(results, "service_multitenant", "variant")
    return (by["pooled"]["lat_p99_s"]
            / max(by["per-run"]["lat_p99_s"], 1e-9))


def _cache_warm_makespan_ratio(results: dict) -> float:
    """Warm (fully memoized) over cold makespan on the 16-wide scatter —
    the PR-7 claim that a verified cache hit skips the invocation's
    compute AND its data movement.  Lower is better; the hard bound is
    the acceptance criterion (warm at most half of cold; in practice
    ~0.1x)."""
    by = _rows_by(results, "cache_memoization", "phase")
    return (by["warm"]["makespan_s"]
            / max(by["cold"]["makespan_s"], 1e-9))


def _cache_bytes_ratio(results: dict) -> float:
    """Warm over cold transfer-log bytes — structural: a memoized run
    aliases payloads by digest instead of copying them, so it moves only
    the final output collection.  Lower is better."""
    by = _rows_by(results, "cache_memoization", "phase")
    return (by["warm"]["transfer_bytes"]
            / max(by["cold"]["transfer_bytes"], 1))


def _autoscale_makespan_ratio(results: dict) -> float:
    """Elastic over static makespan on the same serialized batch — the
    PR-9 claim that queue-pressure scale-up genuinely grows the pool and
    beats the one-slot control.  Lower is better."""
    by = _rows_by(results, "autoscale_elasticity", "mode")
    return (by["elastic"]["makespan_s"]
            / max(by["static"]["makespan_s"], 1e-9))


def _autoscale_wasted_work_ratio(results: dict) -> float:
    """Attempts lost to spot revocations per useful invocation in the
    preempted run — the PR-9 claim that preemption waste stays bounded
    (each revocation costs at most the attempts in flight on the revoked
    site; retries land on survivors).  Lower is better."""
    by = _rows_by(results, "autoscale_elasticity", "mode")
    return (by["preempted"]["wasted_invocations"]
            / max(by["preempted"]["useful_invocations"], 1))


def _analyze_lb_ratio_unrolled(results: dict) -> float:
    """Measured over statically predicted makespan on the hand-unrolled
    Fig. 9 hybrid — the PR-10 bracket: >= 1 means the analyzer's lower
    bound is sound (it never promised more than the run delivered), <= 3
    means the prediction is tight enough to rank placements with."""
    by = _rows_by(results, "analyze_prediction", "mode")
    return by["hand-unrolled"]["ratio"]


def _analyze_lb_ratio_scatter(results: dict) -> float:
    """The same bracket on the scatter expression of the pipeline, where
    the analyzer must reason through scatter widths and the joint slot
    bound instead of a step-per-chain DAG."""
    by = _rows_by(results, "analyze_prediction", "mode")
    return by["scatter"]["ratio"]


def _cache_hit_rate(results: dict) -> float:
    """Share of the warm run's invocations satisfied from the cache —
    deterministic (same workflow, same inputs, live pooled sites); below
    0.9 means memo keys or verification silently broke."""
    by = _rows_by(results, "cache_memoization", "phase")
    return (by["warm"]["memoized"]
            / max(by["warm"]["invocations"], 1))


@dataclass
class Metric:
    name: str
    extract: Callable[[dict], float]
    higher_is_better: bool
    rel_tol: float                  # fractional drift vs baseline == noise
    hard_min: Optional[float] = None   # the claim itself, baseline-independent
    hard_max: Optional[float] = None

    def check(self, value: float, baseline: Optional[float]) -> List[str]:
        errs = []
        if self.hard_min is not None and value < self.hard_min:
            errs.append(f"hard bound: {value:.4g} < min {self.hard_min}")
        if self.hard_max is not None and value > self.hard_max:
            errs.append(f"hard bound: {value:.4g} > max {self.hard_max}")
        if baseline is not None:
            if self.higher_is_better:
                floor = baseline * (1.0 - self.rel_tol)
                if value < floor:
                    errs.append(f"regressed vs baseline {baseline:.4g} "
                                f"(floor {floor:.4g})")
            else:
                ceil = baseline * (1.0 + self.rel_tol)
                if value > ceil:
                    errs.append(f"regressed vs baseline {baseline:.4g} "
                                f"(ceiling {ceil:.4g})")
        return errs


# Tolerances are generous because CI runners differ from the machine that
# wrote the baseline (core count changes how much compute there is to hide
# transfers behind); the hard bounds carry the actual claims and never
# loosen with the baseline.
METRICS = [
    # wall ratio: hard bound only in practice (rel_tol spans the quiet-
    # vs-contended-machine spread); the steps ratio is the tight check
    Metric("recovery_speedup", _recovery_speedup,
           higher_is_better=True, rel_tol=0.95, hard_min=1.15),
    # the crash fires on a completion-count threshold, so the exact number
    # of in-flight steps lost with the driver wobbles by a couple
    Metric("recovery_steps_ratio", _recovery_steps_ratio,
           higher_is_better=False, rel_tol=0.40, hard_max=0.95),
    Metric("routing_makespan_ratio", _routing_makespan_ratio,
           higher_is_better=False, rel_tol=0.25, hard_max=0.97),
    Metric("routing_mgmt_bytes_ratio", _routing_mgmt_bytes_ratio,
           higher_is_better=False, rel_tol=0.50, hard_max=0.10),
    Metric("routing_direct_transfers", _routing_direct_transfers,
           higher_is_better=True, rel_tol=0.50, hard_min=1.0),
    Metric("scatter_makespan_ratio", _scatter_makespan_ratio,
           higher_is_better=False, rel_tol=0.30, hard_max=1.25),
    # structural: the scatter must really spread and really run everything
    Metric("scatter_count_sites", _scatter_count_sites,
           higher_is_better=True, rel_tol=0.0, hard_min=2.0),
    Metric("scatter_invocations_ratio", _scatter_invocations_ratio,
           higher_is_better=True, rel_tol=0.0,
           hard_min=1.0, hard_max=1.0),
    # wall-ratio between the two service variants in one process; the
    # hard bound is the claim (pooling must not LOSE throughput)
    Metric("service_throughput_ratio", _service_throughput_ratio,
           higher_is_better=True, rel_tol=0.30, hard_min=1.05),
    # steady-state tail latency: the pooled p99 swings with scheduler
    # timing (it is tiny in absolute terms), so the tolerance is wide —
    # the hard bound pins the claim (pooled tail at most half the
    # per-run control's)
    Metric("service_p99_ratio", _service_p99_ratio,
           higher_is_better=False, rel_tol=4.0, hard_max=0.5),
    # warm/cold wall ratio in one process: the hard bound is the PR-7
    # acceptance criterion; the wide tolerance absorbs the tiny absolute
    # warm makespan swinging with scheduler timing
    Metric("cache_warm_makespan_ratio", _cache_warm_makespan_ratio,
           higher_is_better=False, rel_tol=3.0, hard_max=0.5),
    # structural: warm bytes are one small report collection vs the cold
    # run's megabytes of input/feature movement
    Metric("cache_bytes_ratio", _cache_bytes_ratio,
           higher_is_better=False, rel_tol=1.0, hard_max=0.05),
    Metric("cache_hit_rate", _cache_hit_rate,
           higher_is_better=True, rel_tol=0.0, hard_min=0.9),
    # elastic/static wall in one process: the hard bound is the claim
    # (scale-up must beat the one-slot control); with 4-way replicas the
    # ratio sits near 1/4 plus scale-up latency on a quiet machine
    Metric("autoscale_makespan_ratio", _autoscale_makespan_ratio,
           higher_is_better=False, rel_tol=0.50, hard_max=0.80),
    # structural-ish: N_PREEMPTS revocations, each wasting at most the
    # attempts in flight on the revoked replica — far below one wasted
    # attempt per useful invocation
    Metric("autoscale_wasted_work_ratio", _autoscale_wasted_work_ratio,
           higher_is_better=False, rel_tol=1.0, hard_max=0.5),
    # measured/predicted makespan: the hard bounds ARE the claim (sound
    # lower bound, usefully tight); the ratio is self-normalizing because
    # the per-step costs are calibrated from the very run being measured
    Metric("analyze_lb_ratio_unrolled", _analyze_lb_ratio_unrolled,
           higher_is_better=False, rel_tol=0.8, hard_min=1.0,
           hard_max=3.0),
    Metric("analyze_lb_ratio_scatter", _analyze_lb_ratio_scatter,
           higher_is_better=False, rel_tol=0.8, hard_min=1.0,
           hard_max=3.0),
]


def extract_metrics(bench: dict) -> Dict[str, float]:
    results = bench.get("results", {})
    out = {}
    for m in METRICS:
        out[m.name] = round(float(m.extract(results)), 6)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("bench_json", help="the CI run's bench.json "
                    "(benchmarks.run --json output)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"committed baseline (default {DEFAULT_BASELINE})")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the extracted metrics to --baseline "
                    "instead of gating against it")
    args = ap.parse_args(argv)

    with open(args.bench_json, encoding="utf-8") as fh:
        bench = json.load(fh)
    try:
        metrics = extract_metrics(bench)
    except KeyError as e:
        print(f"FAIL cannot extract metrics: {e}", file=sys.stderr)
        return 1

    if args.write_baseline:
        with open(args.baseline, "w", encoding="utf-8") as fh:
            json.dump({"generated_unix": time.time(),
                       "source": os.path.basename(args.bench_json),
                       "metrics": metrics}, fh, indent=2)
            fh.write("\n")
        print(f"wrote {args.baseline}")
        for name, value in metrics.items():
            print(f"  {name} = {value}")
        return 0

    try:
        with open(args.baseline, encoding="utf-8") as fh:
            committed = json.load(fh)["metrics"]
    except (OSError, KeyError, ValueError) as e:
        print(f"FAIL unreadable baseline {args.baseline}: {e}",
              file=sys.stderr)
        return 1

    failures = []
    width = max(len(m.name) for m in METRICS)
    for m in METRICS:
        value = metrics[m.name]
        base = committed.get(m.name)
        errs = m.check(value, base)
        arrow = "↑" if m.higher_is_better else "↓"
        status = "ok " if not errs else "FAIL"
        print(f"{status} {m.name:<{width}s} {arrow} value={value:<10.4g} "
              f"baseline={base if base is not None else 'n/a'}")
        if base is None:
            # a metric without a committed baseline means someone added a
            # metric but forgot to refresh baseline.json — fail loudly
            errs.append("no committed baseline (run --write-baseline)")
        for e in errs:
            failures.append(f"{m.name}: {e}")
            print(f"     {e}")

    if failures:
        print(f"\n{len(failures)} regression check(s) failed",
              file=sys.stderr)
        return 1
    print("\nall benchmark-regression checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
