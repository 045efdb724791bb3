"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus each benchmark's own
detailed output above them).  Wall-clock numbers on this CPU container are
structural (ordering / counts / overlap), not TPU timings; the TPU-facing
performance analysis lives in launch/roofline.py + EXPERIMENTS.md.

``--only a b`` runs a subset; ``--json out.json`` additionally writes the
summary rows plus each benchmark's raw result rows to a JSON file (CI
uploads this as a workflow artifact).
"""
from __future__ import annotations

import argparse
import json
import time


def _timed(fn, *a, **kw):
    t0 = time.time()
    out = fn(*a, **kw)
    return out, (time.time() - t0) * 1e6


def _sections():
    from benchmarks import (bench_analyze, bench_autoscale, bench_cache,
                            bench_deployment, bench_fault, bench_pipeline,
                            bench_recovery, bench_routing, bench_scatter,
                            bench_scheduler, bench_service, bench_timeline,
                            bench_transfer)

    def timeline():
        out, us = _timed(bench_timeline.run, "both")
        hybrid = out.get("hybrid (Fig.9)", {})
        full = out.get("full-hpc (Fig.8)", {})
        derived = (f"hybrid/full_wall="
                   f"{hybrid.get('wall_s', 0) / max(full.get('wall_s', 1), 1e-9):.2f};"
                   f"transfer_frac={hybrid.get('transfer_frac', 0):.4f}")
        return out, us, derived

    def transfer():
        out, us = _timed(bench_transfer.run)
        big = out[-2]
        return out, us, (f"two_step_32MiB={big['two_step_s']:.4f}s;"
                         f"elided={big['elided_s']:.5f}s")

    def scheduler():
        out, us = _timed(bench_scheduler.run)
        return out, us, ";".join(f"{r['policy']}={r['bytes_moved']}"
                                 for r in out)

    def deployment():
        out, us = _timed(bench_deployment.run)
        return out, us, ";".join(f"{r['strategy']}={r['site_s']}"
                                 for r in out)

    def fault():
        out, us = _timed(bench_fault.run)
        return out, us, ";".join(f"{r['scenario']}={r['wall_s']}"
                                 for r in out)

    def pipeline():
        out, us = _timed(bench_pipeline.run)
        fig9 = {r["mode"]: r for r in out if r["topology"] == "fig9"}
        return out, us, f"pipelined={fig9['pipelined']['makespan_s']}s"

    def recovery():
        out, us = _timed(bench_recovery.run)
        by = {r["phase"]: r for r in out}
        return out, us, (f"scratch={by['from-scratch']['makespan_s']}s;"
                         f"resumed={by['resumed']['makespan_s']}s")

    def routing():
        out, us = _timed(bench_routing.run)
        by = {r["mode"]: r for r in out}
        return out, us, (f"mgmt_bytes={by['management']['mgmt_bytes']}"
                         f"->{by['direct']['mgmt_bytes']};"
                         f"makespan={by['management']['makespan_s']}s"
                         f"->{by['direct']['makespan_s']}s")

    def service():
        out, us = _timed(bench_service.run)
        by = {r["variant"]: r for r in out}
        return out, us, (f"throughput={by['per-run']['throughput_rps']}"
                         f"->{by['pooled']['throughput_rps']}rps;"
                         f"p99={by['per-run']['lat_p99_s']}s"
                         f"->{by['pooled']['lat_p99_s']}s;"
                         f"deploys={by['per-run']['deploys']}"
                         f"->{by['pooled']['deploys']}")

    def cache():
        out, us = _timed(bench_cache.run)
        by = {r["phase"]: r for r in out}
        return out, us, (f"hit_rate={by['warm']['hit_rate']};"
                         f"makespan={by['cold']['makespan_s']}s"
                         f"->{by['warm']['makespan_s']}s;"
                         f"bytes={by['cold']['transfer_bytes']}"
                         f"->{by['warm']['transfer_bytes']}")

    def autoscale():
        out, us = _timed(bench_autoscale.run)
        by = {r["mode"]: r for r in out}
        return out, us, (f"makespan={by['static']['makespan_s']}s"
                         f"->{by['elastic']['makespan_s']}s;"
                         f"scale_ups={by['elastic']['scale_ups']};"
                         f"wasted={by['preempted']['wasted_invocations']}"
                         f"/{by['preempted']['useful_invocations']}")

    def analyze():
        out, us = _timed(bench_analyze.run)
        by = {r["mode"]: r for r in out}
        return out, us, (f"unrolled={by['hand-unrolled']['predicted_lb_s']}s"
                         f"<={by['hand-unrolled']['measured_s']}s"
                         f"({by['hand-unrolled']['ratio']}x);"
                         f"scatter={by['scatter']['predicted_lb_s']}s"
                         f"<={by['scatter']['measured_s']}s"
                         f"({by['scatter']['ratio']}x)")

    def scatter():
        out, us = _timed(bench_scatter.run)
        by = {r["mode"]: r for r in out}
        return out, us, (f"unrolled={by['hand-unrolled']['makespan_s']}s;"
                         f"scatter={by['scatter']['makespan_s']}s;"
                         f"sites={by['scatter']['count_sites']};"
                         f"invocations={by['scatter']['invocations']}")

    return [
        ("fig8_fig9_timeline", "bench_timeline — paper Fig.8/Fig.9 "
         "(full-HPC vs hybrid)", timeline),
        ("transfer_strategies", "bench_transfer — §4.6 R3/R4 transfer "
         "strategies", transfer),
        ("scheduler_policies", "bench_scheduler — §4.4 policies", scheduler),
        ("deployment_lifecycle", "bench_deployment — §4.5 lifecycle "
         "strategies", deployment),
        ("fault_drills", "bench_fault — failure/straggler drills "
         "(beyond-paper)", fault),
        ("pipeline_makespan", "bench_pipeline — the pipelined executor "
         "and the queue-aware policies", pipeline),
        ("recovery_makespan", "bench_recovery — journal crash-recovery vs "
         "from-scratch", recovery),
        ("routing_data_plane", "bench_routing — direct site-to-site "
         "routing vs the R3 two-step baseline", routing),
        ("scatter_width", "bench_scatter — N-sample scatter vs the "
         "hand-unrolled control", scatter),
        ("analyze_prediction", "bench_analyze — static makespan lower "
         "bound vs measured (SF3xx cost engine)", analyze),
        ("service_multitenant", "bench_service — pooled vs per-run "
         "deployments under bursty multi-tenant load", service),
        ("cache_memoization", "bench_cache — cross-run invocation "
         "memoization: warm re-run vs cold", cache),
        ("autoscale_elasticity", "bench_autoscale — elastic replicas vs "
         "static pool, plus spot preemption waste", autoscale),
    ]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", nargs="+", metavar="NAME",
                    help="run only these benchmarks (by summary-row name, "
                    "substring match allowed)")
    ap.add_argument("--json", metavar="PATH",
                    help="write summary + raw rows to this JSON file")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    sections = _sections()
    if args.only:
        names = [name for name, _, _ in sections]
        dead = [sel for sel in args.only
                if not any(sel in n for n in names)]
        if dead:   # a typo'd selector must not yield a green empty run
            ap.error(f"--only selector(s) {dead} match no benchmark; "
                     f"known: {names}")

    rows = []
    raw = {}
    for name, title, runner in sections:
        if args.only and not any(sel in name for sel in args.only):
            continue
        print("=" * 72)
        print(title)
        print("=" * 72)
        out, us, derived = runner()
        rows.append((name, us, derived))
        raw[name] = out
        print()

    print("=" * 72)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")

    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump({"generated_unix": time.time(),
                       "summary": [{"name": n, "us_per_call": round(us),
                                    "derived": d} for n, us, d in rows],
                       "results": raw}, fh, indent=2, default=str)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
