"""Readings for the limits of a cell's check, in one process on the chip.

    python3 benchmarks/chip/readings.py --workload <cell> --seconds <s> \
        --seeds <a> <b> ... [--control <n>] [--faults <n>]

Runs the cell's timed path once per seed (set-up once, a window of
``--seconds`` each) and prints every compared number of every seed, then
the control's readings (the cell's ``control()``: the plain reference in
a lower precision put in the program's place) on the first ``n`` seeds,
and the readings of the faults planted in the reference (``faults()``).
One JSON line per reading on standard output.
"""
import argparse
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
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--no-program", action="store_true",
                    help="only the control's and the faults' readings")
    a = ap.parse_args(argv)
    root = Path(ROOT)
    for seed in [] if a.no_program else a.seeds:
        t = time.time()
        r = harness.measure(root, a.workload, seed, a.seconds, False,
                            process_start=t,
                            log=lambda m: print(m, file=sys.stderr))
        print(json.dumps({"seed": seed, "kind": "program",
                          "correct": r["correct"],
                          "attempted": r["attempted"],
                          "checks": {k: v["value"]
                                     for k, v in r["checks"].items()},
                          "seconds": time.time() - t}), flush=True)
    cell = harness.load_cell(root, a.workload)
    for seed in a.seeds[:a.control]:
        dep = harness.cell_module(cell).deployment(
            cell.config_file, cell.config, cell.mix.workload, seed)
        t = time.time()
        print(json.dumps({"seed": seed, "kind": "control",
                          "checks": dep.control(seed),
                          "seconds": time.time() - t}), flush=True)
        dep.close()
    for seed in a.seeds[:a.faults]:
        dep = harness.cell_module(cell).deployment(
            cell.config_file, cell.config, cell.mix.workload, seed)
        for fault, readings in dep.faults(seed).items():
            print(json.dumps({"seed": seed, "kind": fault,
                              "checks": readings}), flush=True)
        dep.close()


if __name__ == "__main__":
    main()
