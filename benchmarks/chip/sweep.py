"""Find the highest rate a service cell sustains: one sweep on the chip.

    python3 benchmarks/chip/sweep.py --workload montage.service \
        --seconds 20 --rates 2 4 8 16

One process, one warm service; each rate is one open-loop window of the
cell's mix at that rate.  Per rate it prints the runs sent, the runs
finished by the window's end, the drain after it, and run latency (p50,
p95), as JSON lines.  A rate is sustained when its runs keep finishing
inside the window (the backlog at the end stays near what the rate keeps
in flight) and its p95 stays flat.  The cell's mix then runs at about
four fifths of the highest sustained rate; write it into the mix file.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    from pathlib import Path
    from benchmarks.chip import harness
    from benchmarks.chip.traffic import quantile
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 17)
    a = ap.parse_args(argv)
    root = Path(ROOT)
    cell = harness.load_cell(root, a.workload)
    harness.require_chips(cell.chips)
    harness.use_checkout_cache(root)
    from repro.core import load_streamflow_file
    dep = harness.cell_module(cell).deployment(
        cell.config_file, cell.config, cell.mix.workload, a.seed)
    cfg = load_streamflow_file(dep.document, check=True)
    svc = harness.warm_up(cfg, dep, cell.mix)
    for rate in a.rates:
        mix = dataclasses.replace(cell.mix, rate_per_s=rate)
        late = []
        t0 = time.time()
        runs = harness.open_loop(svc, dep, mix, a.seed, a.seconds, late)
        end = t0 + a.seconds
        lat = [f.finished - f.due for f in runs]
        print(json.dumps({
            "rate_per_s": rate, "sent": len(runs),
            "done_in_window": sum(f.ok and f.finished <= end for f in runs),
            "failed": sum(not f.ok for f in runs),
            "drain_s": max(f.finished for f in runs) - end,
            "p50_s": quantile(lat, 0.5), "p95_s": quantile(lat, 0.95),
            "late_max_ms": 1e3 * max(late)}), flush=True)
    svc.close(timeout=60)


if __name__ == "__main__":
    main()
