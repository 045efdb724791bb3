"""Step tools of the singlecell-minicpm deployment: the users' code.

Each factory is named by an ``implementation:`` entry of ``workflow.yaml``
and returns an ``(inputs, ctx) -> outputs`` body.  The bodies call the
program's own step programs (``repro.launch.steps.make_train_step``,
``repro.models.registry``) with the program's default kernel mode,
optimizer and dtype, and return their outputs as they come, device arrays
included: moving them is the engine's job.

Besides its model, ``/count`` reports what the benchmark's check compares
with the plain reference (per-leaf norms of the first clipped gradient
and of the change after three steps) and a digest of the weights it
hands on, which ``/seurat`` recomputes on what it received.
"""
from __future__ import annotations

import json
from functools import lru_cache
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.bodies import timed
from benchmarks.chip.common import jax_key
from benchmarks.chip.configs.singlecell_minicpm import data

N_CLUSTERS = 4
KMEANS_ITERS = 8
N_TYPES = 6
REFERENCE_DB_SEED = 1234


@lru_cache(maxsize=4)
def sizes_of(config: str) -> tuple:
    raw = json.loads(Path(config).read_text())
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "vocab_size", "num_hidden_layers",
            "rms_norm_eps", "rope_theta")
    return tuple((k, raw[k]) for k in keys)


@lru_cache(maxsize=4)
def arch(frozen_sizes: tuple):
    from repro.models.config import ArchConfig
    s = dict(frozen_sizes)
    return ArchConfig(
        name="minicpm-2b", family="dense", n_layers=s["num_hidden_layers"],
        d_model=s["hidden_size"], n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], d_ff=s["intermediate_size"],
        vocab_size=s["vocab_size"], tie_embeddings=True,
        norm_eps=s["rms_norm_eps"], rope_theta=s["rope_theta"])


# ------------------------------------------------------------ layouts
def to_program(w: dict) -> dict:
    """The benchmark's weight layout as the program's parameter tree."""
    return {
        "embed": w["embed"],
        "blocks": {"l0": {
            "mix": {"ln": w["attn_norm"], "wq": w["wq"], "wk": w["wk"],
                    "wv": w["wv"], "wo": w["wo"]},
            "ffn": {"ln": w["mlp_norm"], "w1": w["w1"], "w3": w["w3"],
                    "w2": w["w2"]}}},
        "final_ln": w["final_norm"]}


def to_benchmark(p: dict) -> dict:
    """The program's parameter tree (or a tree shaped like it) in the
    benchmark's layout."""
    mix, ffn = p["blocks"]["l0"]["mix"], p["blocks"]["l0"]["ffn"]
    return {"embed": p["embed"], "final_norm": p["final_ln"],
            "attn_norm": mix["ln"], "wq": mix["wq"], "wk": mix["wk"],
            "wv": mix["wv"], "wo": mix["wo"], "mlp_norm": ffn["ln"],
            "w1": ffn["w1"], "w3": ffn["w3"], "w2": ffn["w2"]}


@lru_cache(maxsize=4)
def check_layout(frozen_sizes: tuple) -> None:
    """Refuse to run if the program's parameter tree no longer has the
    layout that :func:`to_program` writes."""
    from repro.models import registry as R
    cfg = arch(frozen_sizes)
    want = jax.eval_shape(lambda k: R.init_params(k, cfg)[0],
                          jax.random.key(0))
    got = jax.eval_shape(
        lambda k: to_program(data.weights(k, dict(frozen_sizes))),
        jax.random.key(0))
    if (jax.tree.structure(want) != jax.tree.structure(got) or
            [a.shape for a in jax.tree.leaves(want)]
            != [b.shape for b in jax.tree.leaves(got)]):
        raise RuntimeError("the program's minicpm parameter tree changed; "
                           "update to_program")


def leaf_norms(tree: dict) -> dict:
    """L2 norm of each leaf, per layer for the stacked ones."""
    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        axes = (None if name in data.UNSTACKED
                else tuple(range(1, x.ndim)))
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


# ------------------------------------------------------------ programs
@lru_cache(maxsize=4)
def programs(frozen_sizes: tuple):
    """The jitted programs of one model size, compiled once per process."""
    from repro.launch.steps import make_train_step
    from repro.models import registry as R
    from repro.optim import AdamWConfig, adamw_init
    check_layout(frozen_sizes)
    cfg = arch(frozen_sizes)
    sizes = dict(frozen_sizes)
    b1 = AdamWConfig().b1
    step = make_train_step(cfg)

    def count_init(key):
        p = to_program(data.weights(key, sizes))
        return p, adamw_init(p)

    def count_train_step(params, opt, tokens):
        return step(params, opt, {"tokens": tokens[:, :-1],
                                  "labels": tokens[:, 1:]})

    def count_grad_norms(opt):
        # the optimizer's first moment after one step is (1 - b1) times
        # the clipped gradient it was given
        return leaf_norms(to_benchmark(
            jax.tree.map(lambda m: m / (1.0 - b1), opt.m)))

    def count_change_norms(params, key):
        w0 = data.weights(key, sizes)
        return leaf_norms(jax.tree.map(jnp.subtract, to_benchmark(params),
                                       w0))

    def weight_digest(params):
        return leaf_norms(to_benchmark(params))

    def seurat_embed(params, tokens):
        logits = R.forward_logits(params, cfg, {"tokens": tokens})
        return jnp.mean(logits.astype(jnp.float32), axis=1)

    return {
        "init": jax.jit(count_init),
        "train": jax.jit(count_train_step, donate_argnums=(0, 1)),
        "grad_norms": jax.jit(count_grad_norms),
        "change_norms": jax.jit(count_change_norms),
        "digest": jax.jit(weight_digest),
        "embed": jax.jit(seurat_embed),
    }


# ------------------------------------------------------------ host steps
def kmeans(embs: np.ndarray, sample: int):
    """k-means with a deterministic start (the clustering stand-in)."""
    rng = np.random.default_rng(sample)
    cent = embs[rng.choice(len(embs), N_CLUSTERS, replace=False)].copy()
    assign = np.zeros(len(embs), np.int32)
    for _ in range(KMEANS_ITERS):
        d = ((embs[:, None] - cent[None]) ** 2).sum(-1)
        assign = d.argmin(1).astype(np.int32)
        for k in range(N_CLUSTERS):
            pts = embs[assign == k]
            if len(pts):
                cent[k] = pts.mean(0)
    return assign, cent


def singler(centroids: np.ndarray):
    """Rank correlation of each centroid with seeded reference profiles."""
    rng = np.random.default_rng(REFERENCE_DB_SEED)
    ref = rng.standard_normal((N_TYPES, centroids.shape[1])
                              ).astype(np.float32)

    def ranks(x):
        return np.argsort(np.argsort(x, axis=-1), axis=-1).astype(np.float32)
    rc, rr = ranks(centroids), ranks(ref)
    rc -= rc.mean(-1, keepdims=True)
    rr -= rr.mean(-1, keepdims=True)
    corr = (rc @ rr.T) / (np.linalg.norm(rc, axis=-1, keepdims=True)
                          * np.linalg.norm(rr, axis=-1).clip(1e-9))
    return corr.argmax(-1).astype(np.int32), corr.max(-1)


# ------------------------------------------------------------ factories
def mkfastq_tool(config: str, samples: int, rows: int, seq_len: int):
    sizes = dict(sizes_of(config))

    def fn(inputs, ctx):
        seed = int(inputs["seed"])
        tokens = data.reads(jax_key(seed), samples, rows, seq_len,
                            sizes["vocab_size"])
        return {"shard": [{"sample": i, "seed": seed, "tokens": tokens[i]}
                          for i in range(samples)]}
    return timed("/mkfastq", fn)


def count_tool(config: str, train_steps: int, batch: int):
    frozen = sizes_of(config)

    def fn(inputs, ctx):
        prog = programs(frozen)
        shard = inputs["shard"]
        tokens = jnp.asarray(shard["tokens"])
        key = jax_key(shard["seed"])
        params, opt = prog["init"](key)
        rows = tokens.shape[0]
        losses, grad1, change3 = [], None, None
        for s in range(train_steps):
            lo = (s * batch) % rows
            params, opt, metrics = prog["train"](params, opt,
                                                 tokens[lo:lo + batch])
            losses.append(metrics["loss"])
            if s == 0:
                grad1 = prog["grad_norms"](opt)
            if s == 2:
                change3 = prog["change_norms"](params, key)
        return {"model": params,
                "stats": {"sample": shard["sample"],
                          "losses": jnp.stack(losses),
                          "grad_norms_1": grad1, "change_norms_3": change3,
                          "digest": prog["digest"](params)}}
    return timed("/count", fn)


def seurat_tool(config: str, batch: int):
    frozen = sizes_of(config)

    def fn(inputs, ctx):
        prog = programs(frozen)
        shard = inputs["shard"]
        params = jax.tree.map(jnp.asarray, inputs["model"])
        tokens = jnp.asarray(shard["tokens"])[:, :-1]
        embs = jnp.concatenate([prog["embed"](params, tokens[i:i + batch])
                                for i in range(0, tokens.shape[0], batch)])
        assign, cent = kmeans(np.asarray(embs), shard["sample"])
        return {"clusters": {"sample": shard["sample"],
                             "digest": prog["digest"](params),
                             "embeddings": embs, "assign": assign,
                             "centroids": cent}}
    return timed("/seurat", fn)


def singler_tool():
    def fn(inputs, ctx):
        cl = inputs["clusters"]
        types, conf = singler(cl["centroids"])
        return {"labels": {"sample": cl["sample"], "cluster_types": types,
                           "confidence": conf}}
    return timed("/singler", fn)


def aggregate_tool():
    def fn(inputs, ctx):
        labels, clusters = inputs["labels"], inputs["clusters"]
        types = np.concatenate([lb["cluster_types"] for lb in labels])
        conf = np.concatenate([lb["confidence"] for lb in labels])
        return {"summary": {
            "n_samples": len(labels),
            "type_counts": np.bincount(types, minlength=N_TYPES),
            "mean_confidence": float(conf.mean()),
            "samples": [{**cl, "cluster_types": lb["cluster_types"],
                         "confidence": lb["confidence"]}
                        for lb, cl in zip(labels, clusters)]}}
    return timed("/aggregate", fn)
