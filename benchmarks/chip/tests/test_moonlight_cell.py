"""The singlecell-moonlight cell's check, driven through the harness at
a size a CPU test run can hold, with the look for a chip skipped.

- A sound run is ``correct``, and its traced run gives the per-layer
  metrics that the CPU can give (the engine's spans; the device metrics
  need a TPU trace, see ``test_scopes.py``).
- The control (the reference at fp8 in the program's place) fails a limit.
- Each fault of the held-share layer, planted in the program, makes
  ``correct`` false: gates taken from the biased scores, no top-k
  normalisation, the 2.446 scale missing, the shared expert left out, a
  capacity of factor 1.0 that drops assignments.
"""
import dataclasses
import time

import jax.numpy as jnp
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.tiny import LIMITS, tiny_root

SEED = 2**31 + 12345
CELL = "sc-moonlight.batch"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def tiny_limits(root, monkeypatch):
    c = harness.load_cell(root, CELL)
    mod = harness.cell_module(c)
    for k, v in LIMITS[c.config_file.stem].items():
        monkeypatch.setitem(mod.LIMITS, k, v)


@pytest.fixture
def fresh_programs():
    """Programs are built once per process; a planted fault needs them
    built again, and after the test built again without it."""
    from benchmarks.chip.configs.singlecell_moonlight import tools
    tools.programs.cache_clear()
    yield
    tools.programs.cache_clear()


def run(root, traced=False):
    return harness.measure(root, CELL, SEED, 0.5, traced,
                           process_start=time.time(), require_chip=False,
                           log=lambda m: None)


def failed_checks(result):
    return {k for k, c in result["checks"].items()
            if c["value"] is None or not c["value"] <= c["limit"]}


def test_sound_run_is_correct(root):
    r = run(root, traced=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert {"dropped", "routing_gap", "loss_gap"} <= set(r["checks"])
    assert r["checks"]["dropped"]["value"] == 0.0
    # the engine's spans are read in this cell as in the others
    assert {"engine_self_ms.batch", "resident_pct.batch"} <= set(r["metrics"])


def test_control_fails_a_limit(root):
    c = harness.load_cell(root, CELL)
    mod = harness.cell_module(c)
    dep = mod.deployment(c.config_file, c.config, c.mix.workload, SEED)
    readings = dep.control(SEED)
    dep.close()
    assert any(v > mod.LIMITS[k] for k, v in readings.items()), readings


def _biased_gates(route):
    def wrong(h, p, cfg):
        scores, idx, _ = route(h, p, cfg)
        sel = jnp.take_along_axis(scores + p["bias"], idx, axis=-1)
        return scores, idx, cfg.routed_scale * sel / jnp.sum(
            sel, axis=-1, keepdims=True)
    return wrong


def _unnormalised_gates(route):
    def wrong(h, p, cfg):
        scores, idx, _ = route(h, p, cfg)
        return scores, idx, cfg.routed_scale * jnp.take_along_axis(
            scores, idx, axis=-1)
    return wrong


def _arch_with(tools, **change):
    arch = tools.arch

    def changed(frozen):
        return dataclasses.replace(arch(frozen), **change)
    return changed


@pytest.mark.parametrize("fault", [
    "gates_from_biased_scores", "no_topk_normalisation", "scale_missing",
    "shared_expert_left_out", "capacity_one"])
def test_planted_fault_is_not_correct(root, monkeypatch, fresh_programs,
                                      fault):
    from benchmarks.chip.configs.singlecell_moonlight import tools
    from repro.models import moe
    if fault == "gates_from_biased_scores":
        monkeypatch.setattr(moe, "route_sigmoid",
                            _biased_gates(moe.route_sigmoid))
    elif fault == "no_topk_normalisation":
        monkeypatch.setattr(moe, "route_sigmoid",
                            _unnormalised_gates(moe.route_sigmoid))
    elif fault == "scale_missing":
        monkeypatch.setattr(tools, "arch", _arch_with(tools, routed_scale=1.0))
    elif fault == "shared_expert_left_out":
        monkeypatch.setattr(moe, "gated_mlp",
                            lambda h, p, act: jnp.zeros_like(h))
    else:
        monkeypatch.setattr(moe, "held_rows", lambda T, cfg: int(
            T * cfg.top_k / cfg.n_experts) * cfg.experts_held)
    r = run(root)
    assert not r["correct"], (fault, r["checks"])
    if fault == "capacity_one":
        assert "dropped" in failed_checks(r)
