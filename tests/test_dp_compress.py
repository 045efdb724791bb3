"""Int8+EF compressed DP train step vs the plain step (multi-pod path)."""
import subprocess
import sys
import os

import pytest

pytestmark = pytest.mark.slow        # subprocess compile: CI slow tier


def test_compressed_step_matches_plain(tmp_path):
    """Runs in a subprocess (needs 8 fake devices before jax init)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.models import registry as R
from repro.launch.steps import (make_train_step, make_train_step_dp_compressed,
                                init_ef_errors)
from repro.optim import adamw_init

cfg = get_arch("minicpm-2b").reduced()
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
params, _ = R.init_params(jax.random.key(0), cfg)
opt = adamw_init(params)
errors = init_ef_errors(params, 2)
k1, k2 = jax.random.split(jax.random.key(1))
batch = {"tokens": jax.random.randint(k1, (8, 64), 0, cfg.vocab_size),
         "labels": jax.random.randint(k2, (8, 64), 0, cfg.vocab_size)}
p2, o2, e2, m2 = jax.jit(make_train_step_dp_compressed(cfg, mesh))(
    params, opt, errors, batch)
p1, o1, m1 = jax.jit(make_train_step(cfg))(params, opt, batch)
d = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)))
assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
assert d < 5e-2, d
# error-feedback state is finite and pod-major
assert all(e.shape[0] == 2 for e in jax.tree.leaves(e2))
print("OK")
"""
    root = os.path.join(os.path.dirname(__file__), "..")
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, timeout=560)
    assert "OK" in out.stdout, out.stderr[-2000:]
