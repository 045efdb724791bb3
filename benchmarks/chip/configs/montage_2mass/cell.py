"""montage-2mass: the document sized for a mix, and the check.

The check holds the window's mosaics to the plain reference (numpy,
float64) recomputed from the same raw tiles: every mosaic of a batch
window, or a sample drawn from the seed of a service window's runs, with
the last run always in it.  The mosaic carries every stage (projection,
difference fits, the background solve, the corrections and the co-add),
and it reaches the check through the engine's data plane.  Besides, every
planned invocation of every run completed once.
"""
from __future__ import annotations

import copy
from pathlib import Path

import numpy as np
import yaml

from benchmarks.chip.common import (Check, derive_seed, exactly_once,
                                    jax_key, sample_indices, worse)
from benchmarks.chip.configs.montage_2mass import reference, sky

HERE = Path(__file__).resolve().parent
WORKFLOW = "montage"
# Limits between the program's readings over a dozen seeds and the
# control's (the reference with every array in bfloat16); see PERF.md.
LIMITS = {"mosaic_gap": 1e-4, "exactly_once": 0}
# tiles compared per run: a sample of the mosaics drawn from the seed,
# between 1 and 8 of them (a service window has hundreds of small ones)
SAMPLED_TILES = 256
WARMUP_GRID = (2, 2)


def deployment(config_file: Path, config: dict, workload: dict, seed: int):
    return Deployment(Path(config_file), config, workload, seed)


def mosaic_gap(got, want) -> float:
    """Largest pixel difference over the largest reference pixel."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


class Deployment:
    def __init__(self, config_file: Path, config: dict, workload: dict,
                 seed: int):
        self.config_file = config_file
        self.config = config
        self.seed = seed
        self.rows, self.cols = int(workload["rows"]), int(workload["cols"])
        n, p = self.rows * self.cols, len(sky.pairs(self.rows, self.cols))
        # /fetch, /project x n, /overlaps, /difffit x p, /bgmodel,
        # /background x n, /add
        self.planned = 4 + 2 * n + p
        base = yaml.safe_load((HERE / "workflow.yaml").read_text())
        self.document = self._sized(base, self.rows, self.cols)
        self.warmup_document = self._sized(base, *WARMUP_GRID)

    def _sized(self, base: dict, rows: int, cols: int) -> dict:
        doc = copy.deepcopy(base)
        n, p = rows * cols, len(sky.pairs(rows, cols))
        t = doc["tools"]
        common = {"config": str(self.config_file)}
        grid = {**common, "rows": rows, "cols": cols}
        t["fetch"]["implementation"]["args"].update(grid)
        t["overlaps"]["implementation"]["args"].update(grid)
        t["add"]["implementation"]["args"].update(grid)
        t["difffit"]["implementation"]["args"].update(common)
        t["background"]["implementation"]["args"].update(common)
        t["bgmodel"]["implementation"]["args"].update(common, tiles=n)
        steps = doc["workflows"][WORKFLOW]["steps"]
        steps["/fetch"]["streams"]["raw"] = n
        steps["/overlaps"]["streams"]["pair"] = p
        steps["/bgmodel"]["streams"]["corr"] = n
        return doc

    def inputs(self, k: int) -> dict:
        return {"seed": derive_seed(self.seed, k)}

    def reference_of(self, run_seed: int, precision: str = "f64") -> dict:
        tiles = sky.raw_tiles(self.config, self.rows, self.cols,
                              jax_key(run_seed))
        tiles = [(np.asarray(img), np.asarray(off)) for img, off in tiles]
        return reference.run(tiles, self.config, self.rows, self.cols,
                             precision)

    def check(self, runs, seed: int):
        ok = [f for f in runs if f.ok]
        once = [f.index for f in ok
                if exactly_once(f.result.events, self.planned)]
        worst, bad = 0.0, []
        k = min(8, max(1, SAMPLED_TILES // (self.rows * self.cols)))
        for i in sample_indices(seed, len(ok), k):
            f = ok[i]
            gap = mosaic_gap(f.result.outputs["mosaic"],
                             self.reference_of(f.inputs["seed"])["mosaic"])
            worst = worse(worst, gap)
            if not gap <= LIMITS["mosaic_gap"]:
                bad.append(f.index)
        if not ok:
            worst = float("nan")
        return [Check("exactly_once", float(len(once)),
                      LIMITS["exactly_once"], tuple(once)),
                Check("mosaic_gap", worst, LIMITS["mosaic_gap"],
                      tuple(bad))]

    def control(self, seed: int) -> dict:
        """The control's reading: the reference with every array in
        bfloat16 in the program's place, against the float64 one."""
        run_seed = derive_seed(seed, 0)
        return {"mosaic_gap": mosaic_gap(
            self.reference_of(run_seed, "bf16")["mosaic"],
            self.reference_of(run_seed)["mosaic"])}

    def faults(self, seed: int) -> dict:
        """No fault needs a reading here: an altered tile or a tile left
        out of the co-add moves the mosaic by its whole size."""
        return {}

    def close(self):
        pass
