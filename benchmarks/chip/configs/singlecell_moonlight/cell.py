"""singlecell-moonlight: the document sized for a mix, and the check.

The check holds the window to what ``singlecell-minicpm``'s holds it to
(``exactly_once``, ``digest_gap``, ``downstream_faults``, and for one
sample drawn from the seed the ``loss_gap``, ``grad_gap``, ``change_gap``
and ``embedding_gap`` against the plain reference), and adds two numbers
of the held-share MoE layer:

- ``routing_gap``: the share of (token, MoE layer) pairs of the sample's
  first batch whose six selected experts at step 1 differ from the
  reference's;
- ``dropped``: routed assignments to held experts that the program did
  not compute, over every train step of every sample of the window.
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
from benchmarks.chip.configs.singlecell_minicpm import cell as minicpm
from benchmarks.chip.configs.singlecell_minicpm import data as reads
from benchmarks.chip.configs.singlecell_minicpm.tools import (N_TYPES,
                                                              kmeans, singler)
from benchmarks.chip.configs.singlecell_moonlight import (data, reference,
                                                          tools)

HERE = Path(__file__).resolve().parent
WORKFLOW = minicpm.WORKFLOW
# Limits, each between the program's readings over a dozen seeds and the
# control's (reference at fp8) or the faults' readings; see PERF.md.
LIMITS = {
    "loss_gap": 1.5e-4,
    "grad_gap": 3e-2,
    "change_gap": 2e-2,
    "embedding_gap": 2e-2,
    "routing_gap": 5e-2,
    "dropped": 0,
    "digest_gap": 0.0,
    "downstream_faults": 0,
    "exactly_once": 0,
}
# The faults a held-share layer can have, planted in the reference put in
# the program's place (``reference.SOUND`` names the knobs).
FAULTS = {
    "gates_from_biased_scores": {"gate_bias": 1.0},
    "no_topk_normalisation": {"normalize": 0.0},
    "scale_missing": {"scale": 1.0},
    "shared_expert_left_out": {"shared": 0.0},
    # capacity factor 1.0: k / E of a row's tokens per held expert
    "capacity_one": {"capacity": None},
}


def deployment(config_file: Path, config: dict, workload: dict, seed: int):
    return Deployment(Path(config_file), config, workload, seed)


def gaps(prog: dict, ref: dict) -> dict:
    """The compared numbers of one sample: ``singlecell-minicpm``'s four,
    the routing gap and the drops."""
    out = minicpm.gaps(prog, ref)
    p = np.sort(np.asarray(prog["routing_1"]), axis=-1)
    r = np.sort(np.asarray(ref["routing_1"]), axis=-1)
    out["routing_gap"] = float(np.mean(np.any(p != r, axis=-1)))
    out["dropped"] = float(np.sum(prog["dropped"]))
    return out


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

    def capacity(self) -> float:
        """Assignments per held expert and row at capacity factor 1."""
        s = self.sizes
        return float(self.seq_len * s["num_experts_per_tok"]
                     // s["router_experts"])

    def reference_of(self, run_seed: int, sample: int,
                     precision: str = "f32", **fault) -> dict:
        key = jax_key(run_seed)
        tokens = reads.reads(key, self.samples, self.rows, self.seq_len,
                             self.sizes["vocab_size"])[sample]
        return reference.run(lambda: data.weights(key, self.sizes), tokens,
                             self.sizes, self.train_steps, self.batch,
                             precision, **fault)

    def _downstream(self, f) -> tuple:
        """(digest gap, faults) of one workflow: the weights /seurat got
        against those /count sent, and what reached /aggregate against
        k-means and SingleR called directly on the embeddings."""
        stats = f.result.outputs["stats"]
        summary = f.result.outputs["summary"]
        digest = 0.0
        faults = int(summary["n_samples"] != self.samples
                     or len(summary["samples"]) != self.samples)
        types = []
        for i, (st, smp) in enumerate(zip(stats, summary["samples"])):
            faults += int(st["sample"] != i or smp["sample"] != i)
            for k in st["digest"]:
                digest = worse(digest, float(np.max(np.abs(
                    np.asarray(st["digest"][k], np.float64)
                    - np.asarray(smp["digest"][k], np.float64)))))
            assign, cent = kmeans(np.asarray(smp["embeddings"]), i)
            labels, conf = singler(cent)
            faults += int(not (np.array_equal(assign, smp["assign"])
                               and np.array_equal(cent, smp["centroids"])
                               and np.array_equal(labels,
                                                  smp["cluster_types"])
                               and np.array_equal(conf, smp["confidence"])))
            types.append(labels)
        if types:
            counts = np.bincount(np.concatenate(types), minlength=N_TYPES)
            faults += int(not np.array_equal(counts, summary["type_counts"]))
        return digest, faults

    def check(self, runs, seed: int):
        ok = [f for f in runs if f.ok]
        once = [f.index for f in ok
                if exactly_once(f.result.events, self.planned)]
        digest, down, bad_down = 0.0, 0, set()
        dropped, bad_drop = 0, set()
        for f in ok:
            g, faults = self._downstream(f)
            digest = worse(digest, g)
            if faults:
                bad_down.add(f.index)
            down += faults
            n = sum(int(np.sum(st["dropped"]))
                    for st in f.result.outputs["stats"])
            if n:
                bad_drop.add(f.index)
            dropped += n
        checks = [Check("exactly_once", float(len(once)),
                        LIMITS["exactly_once"], tuple(once)),
                  Check("digest_gap", digest, LIMITS["digest_gap"]),
                  Check("downstream_faults", float(down),
                        LIMITS["downstream_faults"], tuple(sorted(bad_down))),
                  Check("dropped", float(dropped), LIMITS["dropped"],
                        tuple(sorted(bad_drop)))]
        if not ok:
            return checks + [Check("reference_sample", float("nan"), 0.0)]
        rng = np.random.default_rng(derive_seed(seed, 11))
        f = ok[int(rng.integers(len(ok)))]
        s = int(rng.integers(self.samples))
        prog = {**f.result.outputs["stats"][s],
                "embeddings": f.result.outputs["summary"]["samples"][s][
                    "embeddings"]}
        g = gaps(prog, self.reference_of(f.inputs["seed"], s))
        g.pop("dropped")                 # counted over the window above
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
        """Readings of each fault of :data:`FAULTS`, planted in the
        reference put in the program's place."""
        run_seed = derive_seed(seed, 0)
        full = self.reference_of(run_seed, sample)
        out = {}
        for name, knobs in FAULTS.items():
            if "capacity" in knobs:
                knobs = {"capacity": self.capacity()}
            out[name] = gaps(self.reference_of(run_seed, sample, **knobs),
                             full)
        return out

    def close(self):
        shutil.rmtree(self._tmp, ignore_errors=True)
