"""Each cell's check, driven through the harness at a size a CPU test run
can hold, with the look for a chip skipped.

- A sound run is ``correct``.
- The control (the plain reference in the precision below the one the
  configuration states, put in the program's place) fails a limit.
- Each fault that the cell can have, planted under the timed path, makes
  ``correct`` false: a train step that returns its state unchanged, half
  of each batch left out (the mean taken over the rest), a token altered
  where it is produced.  (No cell spans chips, so none can lose an
  exchange between them.)
"""
import time

import jax.numpy as jnp
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.tiny import LIMITS, tiny_root

SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def tiny_limits(root, monkeypatch):
    """The limits set for the tiny sizes (see ``tiny.py``)."""
    for cell in ("sc-minicpm.batch", "montage.batch"):
        c = harness.load_cell(root, cell)
        mod = harness.cell_module(c)
        for k, v in LIMITS.get(c.config_file.stem, {}).items():
            monkeypatch.setitem(mod.LIMITS, k, v)


def run(root, cell, seconds=0.5):
    return harness.measure(root, cell, SEED, seconds, False,
                           process_start=time.time(), require_chip=False,
                           log=lambda m: None)


def failed_checks(result):
    return {k for k, c in result["checks"].items()
            if c["value"] is None or not c["value"] <= c["limit"]}


@pytest.fixture
def fresh_programs():
    """Programs are built once per process; a planted fault needs them
    built again, and after the test built again without it."""
    from benchmarks.chip.configs.singlecell_minicpm import tools
    tools.programs.cache_clear()
    yield
    tools.programs.cache_clear()


@pytest.mark.parametrize("cell", ["sc-minicpm.batch", "montage.batch",
                                  "montage.service"])
def test_sound_run_is_correct(root, cell):
    r = run(root, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0


@pytest.mark.parametrize("cell", ["sc-minicpm.batch", "montage.batch"])
def test_control_fails_a_limit(root, cell):
    c = harness.load_cell(root, cell)
    mod = harness.cell_module(c)
    dep = mod.deployment(c.config_file, c.config, c.mix.workload, SEED)
    readings = dep.control(SEED)
    dep.close()
    assert any(v > mod.LIMITS[k] for k, v in readings.items()), readings


def test_state_left_unchanged(root, monkeypatch, fresh_programs):
    import repro.launch.steps as steps
    monkeypatch.setattr(steps, "adamw_update",
                        lambda g, o, p, cfg, sched: (p, o, {}))
    r = run(root, "sc-minicpm.batch")
    assert not r["correct"]
    assert "change_gap" in failed_checks(r)


def test_half_batch_left_out(root, monkeypatch, fresh_programs):
    from repro.models import registry as R
    full = R.forward_train

    def half(params, cfg, batch, **kw):
        b = batch["tokens"].shape[0] // 2
        return full(params, cfg, {k: v[:b] for k, v in batch.items()}, **kw)
    monkeypatch.setattr(R, "forward_train", half)
    r = run(root, "sc-minicpm.batch")
    assert not r["correct"]
    assert failed_checks(r) & {"loss_gap", "grad_gap"}


def test_weights_altered_where_produced(root, monkeypatch, fresh_programs):
    import repro.launch.steps as steps
    make = steps.make_train_step

    def altered(cfg, *a, **kw):
        step = make(cfg, *a, **kw)

        def run_step(params, opt, batch):
            p, o, m = step(params, opt, batch)
            p = dict(p, embed=p["embed"].at[0, 0].add(1.0))
            return p, o, m
        return run_step
    monkeypatch.setattr(steps, "make_train_step", altered)
    r = run(root, "sc-minicpm.batch")
    assert not r["correct"]


def test_token_altered_in_transit(root, monkeypatch):
    import repro.core.executor as ex
    from repro.core.connector import serialize

    def corrupt(value):
        # a montage tile, or the weights /count hands to /seurat
        for key in ("image", "embed"):
            if isinstance(value, dict) and key in value:
                x = jnp.asarray(value[key])
                value = dict(value, **{key: x.at[0, 0].add(50.0)})
        return serialize(value)
    monkeypatch.setattr(ex, "serialize", corrupt)
    for cell in ("montage.batch", "sc-minicpm.batch"):
        r = run(root, cell)
        assert not r["correct"], cell


def test_mosaic_half_the_tiles(root, monkeypatch):
    from benchmarks.chip.configs.montage_2mass import tools
    add = tools.add_tool

    def half(config, rows, cols):
        inner = add(config, rows, cols)

        def fn(inputs, ctx):
            return inner({"bg": inputs["bg"][::2]}, ctx)
        return fn
    monkeypatch.setattr(tools, "add_tool", half)
    r = run(root, "montage.batch")
    assert not r["correct"]
    assert "mosaic_gap" in failed_checks(r)
