"""Integration test for deliverable (e): one real dry-run cell through the
CLI (512 forced host devices, lower + compile + artifact JSON)."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow        # subprocess compile: CI slow tier


def test_dryrun_cli_one_cell(tmp_path):
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "xlstm-1.3b", "--shape", "decode_32k",
         "--out", str(tmp_path)],
        cwd=root, env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.load(open(tmp_path / "xlstm-1.3b__decode_32k__pod1.json"))
    assert rec["chips"] == 256
    r = rec["roofline"]
    assert r["compute_s"] >= 0 and r["memory_s"] > 0
    assert r["dominant"] in ("compute", "memory", "collective")
    assert rec["hlo"]["flops_per_device"] > 0
    # skip cells are recorded, not errored
    out2 = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "minicpm-2b", "--shape", "long_500k",
         "--out", str(tmp_path)],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert out2.returncode == 0
    rec2 = json.load(open(tmp_path / "minicpm-2b__long_500k__pod1.json"))
    assert "skip" in rec2
