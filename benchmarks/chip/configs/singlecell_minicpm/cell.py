"""singlecell-minicpm: the document sized for a mix, and the check.

The check holds what the window produced to three things:

- the engine: every planned invocation of every workflow completed once
  (``exactly_once``);
- the data plane: the weights ``/seurat`` received are the ones ``/count``
  sent (``digest_gap``, by per-leaf norms computed on each side), and
  what reached ``/aggregate`` is what k-means and SingleR, called
  directly, make of the embeddings that arrived (``downstream_faults``);
- the step programs: for one sample drawn from the seed, the plain
  reference (float32, ``HIGHEST``) redoes the sample's fine-tune and
  embedding; compared are each step's loss, the first clipped gradient's
  and the three-step change's norms by the worst leaf, and the
  embeddings.
"""
from __future__ import annotations

import copy
import shutil
import tempfile
from pathlib import Path

import numpy as np
import yaml

from benchmarks.chip.common import (Check, derive_seed, exactly_once,
                                    jax_key, worse)
from benchmarks.chip.configs.singlecell_minicpm import data, reference, tools

HERE = Path(__file__).resolve().parent
WORKFLOW = "single-cell"
# Limits, each between the program's readings over a dozen seeds and the
# control's (reference at fp8) or the faults' readings; see PERF.md.
LIMITS = {
    "loss_gap": 1.5e-4,
    "grad_gap": 3e-2,
    "change_gap": 2e-2,
    "embedding_gap": 2e-2,
    "digest_gap": 0.0,
    "downstream_faults": 0,
    "exactly_once": 0,
}
# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone under Adam: left out of change_gap
STILL_LEAF = 1e-3


def deployment(config_file: Path, config: dict, workload: dict, seed: int):
    return Deployment(Path(config_file), config, workload, seed)


def gaps(prog: dict, ref: dict) -> dict:
    """The compared numbers of one sample: ``prog`` as ``/count`` and
    ``/seurat`` reported it, ``ref`` from :func:`reference.run`."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = ref["losses"]

    def worst_leaf(p, r, keep=None):
        rv = np.concatenate([np.ravel(r[k]) for k in sorted(r)])
        floor = np.median(rv)
        worst = 0.0
        for k in sorted(r):
            pk = np.ravel(np.asarray(p[k], np.float64))
            rk = np.ravel(r[k])
            for i in range(len(rk)):
                if keep is not None and not keep[k][i]:
                    continue
                worst = worse(worst, float(abs(pk[i] - rk[i])
                                           / max(rk[i], floor)))
        return worst
    g = ref["grad_norms_1"]
    gmed = np.median(np.concatenate([np.ravel(v) for v in g.values()]))
    moving = {k: np.ravel(v) >= STILL_LEAF * gmed for k, v in g.items()}
    ep = np.asarray(prog["embeddings"], np.float64)
    er = ref["embeddings"]
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": worst_leaf(prog["grad_norms_1"], g),
        "change_gap": worst_leaf(prog["change_norms_3"],
                                 ref["change_norms_3"], moving),
        "embedding_gap": float(np.linalg.norm(ep - er) / np.linalg.norm(er)),
    }


class Deployment:
    def __init__(self, config_file: Path, config: dict, workload: dict,
                 seed: int):
        self.config_file = config_file
        self.sizes = dict(tools.sizes_of(str(config_file)))
        self.seed = seed
        self.samples = int(workload["samples"])
        self.rows = int(workload["rows"])
        self.seq_len = int(workload["seq_len"])
        self.train_steps = int(workload["train_steps"])
        self.batch = int(workload["batch"])
        self.planned = 3 * self.samples + 2
        self._tmp = tempfile.mkdtemp(prefix="singlecell-journal-")
        base = yaml.safe_load((HERE / "workflow.yaml").read_text())
        self.document = self._sized(base, self.samples, "window")
        self.warmup_document = self._sized(base, 1, "warmup")

    def _sized(self, base: dict, samples: int, name: str) -> dict:
        doc = copy.deepcopy(base)
        t = doc["tools"]
        common = {"config": str(self.config_file)}
        t["mkfastq"]["implementation"]["args"].update(
            common, samples=samples, rows=self.rows, seq_len=self.seq_len)
        t["count"]["implementation"]["args"].update(
            common, train_steps=self.train_steps, batch=self.batch)
        t["seurat"]["implementation"]["args"].update(common, batch=self.batch)
        doc["workflows"][WORKFLOW]["steps"]["/mkfastq"]["streams"][
            "shard"] = samples
        doc["checkpoint"]["journal_path"] = str(
            Path(self._tmp) / f"{name}.jsonl")
        return doc

    def inputs(self, k: int) -> dict:
        return {"seed": derive_seed(self.seed, k)}

    def reference_of(self, run_seed: int, sample: int,
                     precision: str = "f32", used_rows: int = 0) -> dict:
        key = jax_key(run_seed)
        tokens = data.reads(key, self.samples, self.rows, self.seq_len,
                            self.sizes["vocab_size"])[sample]
        return reference.run(data.weights(key, self.sizes), tokens,
                             self.sizes, self.train_steps, self.batch,
                             precision, used_rows)

    def check(self, runs, seed: int):
        ok = [f for f in runs if f.ok]
        once = [f.index for f in ok
                if exactly_once(f.result.events, self.planned)]
        digest, down, bad_down = 0.0, 0, set()
        for f in ok:
            stats = f.result.outputs["stats"]
            summary = f.result.outputs["summary"]
            faults = int(summary["n_samples"] != self.samples
                         or len(summary["samples"]) != self.samples)
            types = []
            for i, (st, smp) in enumerate(zip(stats, summary["samples"])):
                faults += int(st["sample"] != i or smp["sample"] != i)
                for k in st["digest"]:
                    digest = worse(digest, float(np.max(np.abs(
                        np.asarray(st["digest"][k], np.float64)
                        - np.asarray(smp["digest"][k], np.float64)))))
                assign, cent = tools.kmeans(np.asarray(smp["embeddings"]), i)
                labels, conf = tools.singler(cent)
                faults += int(not (np.array_equal(assign, smp["assign"])
                                   and np.array_equal(cent, smp["centroids"])
                                   and np.array_equal(labels,
                                                      smp["cluster_types"])
                                   and np.array_equal(conf,
                                                      smp["confidence"])))
                types.append(labels)
            if types:
                counts = np.bincount(np.concatenate(types),
                                     minlength=tools.N_TYPES)
                faults += int(not np.array_equal(counts,
                                                 summary["type_counts"]))
            if faults:
                bad_down.add(f.index)
            down += faults
        checks = [Check("exactly_once", float(len(once)),
                        LIMITS["exactly_once"], tuple(once)),
                  Check("digest_gap", digest, LIMITS["digest_gap"]),
                  Check("downstream_faults", float(down),
                        LIMITS["downstream_faults"], tuple(sorted(bad_down)))]
        if not ok:
            return checks + [Check("reference_sample", float("nan"), 0.0)]
        rng = np.random.default_rng(derive_seed(seed, 11))
        f = ok[int(rng.integers(len(ok)))]
        s = int(rng.integers(self.samples))
        stats = f.result.outputs["stats"][s]
        prog = {**stats,
                "embeddings": f.result.outputs["summary"]["samples"][s][
                    "embeddings"]}
        g = gaps(prog, self.reference_of(f.inputs["seed"], s))
        checks += [Check(k, v, LIMITS[k], () if v <= LIMITS[k] else
                         (f.index,)) for k, v in g.items()]
        return checks

    def control(self, seed: int, sample: int = 0) -> dict:
        """The control's readings: the reference at fp8 in the program's
        place, against the reference at float32, for one sample."""
        run_seed = derive_seed(seed, 0)
        return gaps(self.reference_of(run_seed, sample, "fp8"),
                    self.reference_of(run_seed, sample))

    def faults(self, seed: int, sample: int = 0) -> dict:
        """Readings of the faults planted in the reference put in the
        program's place: half of each batch left out.  (A state left
        unchanged reads 1 on ``change_gap`` by its definition.)"""
        run_seed = derive_seed(seed, 0)
        full = self.reference_of(run_seed, sample)
        return {"half_batch": gaps(
            self.reference_of(run_seed, sample, used_rows=self.batch // 2),
            full)}

    def close(self):
        shutil.rmtree(self._tmp, ignore_errors=True)
