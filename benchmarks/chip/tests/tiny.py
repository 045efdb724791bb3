"""A checkout-like root holding the benchmark's cells at sizes a CPU test
run can hold: the same configuration directories (documents, tools,
references, checks), smaller sizes and mixes, the same metric readers."""
import json
import os
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]

SIZES = {
    "singlecell_minicpm": dict(hidden_size=64, num_attention_heads=4,
                               num_key_value_heads=4, intermediate_size=128,
                               vocab_size=256),
    "montage_2mass": dict(tile_rows=32, tile_cols=64, overlap_px=8,
                          stars_per_tile=6, coordinate_scale_px=64),
}
# Limits at these sizes, set as the chip's are: between the program's
# readings on CPU (seeds 11, 12, 13, 2**31 + 5) and the control's (11, 12,
# 13).  singlecell: loss 1.4e-4..6.2e-4 vs 1.5e-3..1.9e-3; grad
# 7.5e-4..1.7e-3 vs 8.7e-3..2.1e-2; change 6.7e-4..9.6e-4 vs 2.9e-3..3.3e-3
# (half batch 2.2e-2..2.6e-2); embedding 4.8e-3..5.4e-3 vs 5.3e-2..5.8e-2.
# Montage reads 1e-7 against 5e-3 at every size: its chip limit holds.
LIMITS = {
    "singlecell_minicpm": dict(loss_gap=1e-3, grad_gap=5e-3,
                               change_gap=2e-3, embedding_gap=2e-2),
}
MIXES = {
    "sc-4samples-closed": {"workload": dict(samples=2, rows=8, seq_len=16,
                                            train_steps=4, batch=2)},
    "montage-8x16-closed": {"workload": dict(rows=3, cols=4)},
    "montage-2x4-poisson": {"workload": dict(rows=2, cols=3),
                            "rate_per_s": 3.0, "drain_s": 30},
}


# The service cell waits for its bound sets on the chip (PERF.md, Open
# questions); its mix, metrics and open-loop path are tested here.
SERVICE = {
    "workload": {"name": "montage.service", "config": "montage-2mass",
                 "traffic": "montage-2x4-poisson", "chips": 1, "why": "-"},
    "end_to_end": [{"name": n, "unit": "s", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["montage.service"]}
                   for n in ("run_p50_s", "run_p95_s")],
    "per_layer": [{"name": n, "unit": u, "better": "lower",
                   "source": "program_span", "layer": "service",
                   "moves": "run_p95_s", "workloads": ["montage.service"]}
                  for n, u in (("admit_wait_ms.service", "ms"),
                               ("engine_gap_ms.service", "ms"),
                               ("idle_share.service", "%"))],
}


def tiny_root(tmp: Path) -> Path:
    """``tmp`` laid out as a checkout of the benchmark at tiny sizes."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if "montage.service" not in {w["name"] for w in spec["workloads"]}:
        spec["workloads"].append(SERVICE["workload"])
        spec["end_to_end"] += SERVICE["end_to_end"]
        spec["per_layer"] += SERVICE["per_layer"]
    bench = tmp / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    os.symlink(BENCH / "metrics", bench / "metrics")
    for conf in spec["configs"]:
        src = ROOT / conf["file"]
        stem = src.stem
        cfg = json.loads(src.read_text())
        cfg.update(SIZES[stem])
        (bench / "configs" / src.name).write_text(json.dumps(cfg))
        os.symlink(src.with_suffix(""), bench / "configs" / stem)
        conf["file"] = f"bench/configs/{src.name}"
    for w in spec["workloads"]:
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        mix.update(MIXES[w["traffic"]])
        (bench / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(mix))
    spec["paths"] = ["bench"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def clean(tmp: Path):
    shutil.rmtree(tmp, ignore_errors=True)
