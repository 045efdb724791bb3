"""A token altered where it is produced, planted at the store's value
boundary (``ObjectStore.put_value``), through which every step output
passes, whether it stays on the device or goes as bytes: each cell's check
reads ``correct`` false."""
import time

import jax.numpy as jnp
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.tiny import LIMITS, tiny_root
from repro.core.connector import ObjectStore

SEED = 2**31 + 4321


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


def run(root, cell):
    return harness.measure(root, cell, SEED, 0.5, False,
                           process_start=time.time(), require_chip=False,
                           log=lambda m: None)


@pytest.mark.parametrize("cell", ["sc-minicpm.batch", "montage.batch"])
def test_token_altered_where_it_is_stored(root, monkeypatch, cell):
    put_value = ObjectStore.put_value
    altered = []

    def corrupt(self, path, value):
        # a montage tile (bytes), or the weights /count hands to /seurat
        # (held on the device)
        for key in ("image", "embed"):
            if isinstance(value, dict) and key in value:
                x = jnp.asarray(value[key])
                value = dict(value, **{key: x.at[0, 0].add(50.0)})
                altered.append(path)
        return put_value(self, path, value)
    monkeypatch.setattr(ObjectStore, "put_value", corrupt)
    r = run(root, cell)
    assert altered, "no token was altered"
    assert not r["correct"], r["checks"]
