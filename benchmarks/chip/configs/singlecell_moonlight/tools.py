"""Step tools of the singlecell-moonlight deployment: the users' code.

``/count`` and ``/seurat`` call the program's own step programs
(``repro.launch.steps.make_train_step``, ``repro.models.registry``) with
the program's default kernel mode, optimizer and dtype, as in
``singlecell-minicpm``; ``/mkfastq``, ``/singler`` and ``/aggregate`` are
that deployment's tools, named by ``workflow.yaml``.

Besides what ``singlecell-minicpm``'s ``/count`` reports, this one reports
the held-share layer's counters from every train step (assignments per
held expert per MoE layer, and assignments dropped) and the experts each
token of the first batch selected at step 1.

The train program is compiled ahead of its first call (:class:`Aot`), so
that its optimized HLO text is at hand (:func:`hlo_text`) for the
attribution of device time to the program's named scopes
(``benchmarks/chip/scopes.py``).
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
from benchmarks.chip.configs.singlecell_minicpm.tools import kmeans
from benchmarks.chip.configs.singlecell_moonlight import data

#: the train program's name in a profiler trace
TRAIN_MODULE = "jit_count_train_step"
#: the step programs compiled ahead of their first call, by module name
COMPILED = {}


@lru_cache(maxsize=4)
def sizes_of(config: str) -> tuple:
    """The sizes the programs read, with the router's width, this chip's
    expert offset and the assumed constants taken from the file."""
    raw = json.loads(Path(config).read_text())
    keys = ("hidden_size", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size", "n_shared_experts",
            "n_routed_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "scoring_func",
            "first_k_dense_replace", "num_hidden_layers", "vocab_size",
            "rms_norm_eps", "rope_theta", "balance_loss_alpha",
            "correction_bias_std")
    out = {k: raw[k] for k in keys}
    out["router_experts"] = raw["published"]["n_routed_experts"]
    out["expert_offset"] = raw["deployment"]["expert_offset"]
    return tuple(sorted(out.items()))


@lru_cache(maxsize=4)
def arch(frozen_sizes: tuple):
    from repro.models.config import MLA, ArchConfig
    s = dict(frozen_sizes)
    if s["scoring_func"] != "sigmoid" or not s["norm_topk_prob"]:
        raise ValueError("the held-share layer routes by sigmoid scores "
                         "and normalises its top-k gates; the file says "
                         f"{s['scoring_func']!r}, {s['norm_topk_prob']!r}")
    return ArchConfig(
        name="moonlight-16b-a3b", family="moe",
        n_layers=s["num_hidden_layers"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"], n_kv_heads=s["num_attention_heads"],
        d_ff=s["intermediate_size"], vocab_size=s["vocab_size"],
        block_pattern=(MLA,), kv_lora_rank=s["kv_lora_rank"],
        qk_nope_head_dim=s["qk_nope_head_dim"],
        qk_rope_head_dim=s["qk_rope_head_dim"], v_head_dim=s["v_head_dim"],
        n_experts=s["router_experts"], top_k=s["num_experts_per_tok"],
        experts_held=s["n_routed_experts"], expert_offset=s["expert_offset"],
        moe_d_ff=s["moe_intermediate_size"],
        n_shared_experts=s["n_shared_experts"],
        first_k_dense=s["first_k_dense_replace"],
        routed_scale=s["routed_scaling_factor"],
        balance_alpha=s["balance_loss_alpha"],
        tie_embeddings=False, norm_eps=s["rms_norm_eps"],
        rope_theta=float(s["rope_theta"]))


# ------------------------------------------------------------ layouts
_MIX = {"ln": "attn_norm", "wq": "wq", "wkva": "wkva", "kv_ln": "kv_norm",
        "wkvb": "wkvb", "wo": "wo"}
_DENSE = {"ln": "dense_norm", "w1": "dense_w1", "w3": "dense_w3",
          "w2": "dense_w2"}
_MOE = {"ln": "moe_norm", "router": "router", "bias": "bias", "w1": "w1",
        "w3": "w3", "w2": "w2"}
_SHARED = {"w1": "shared_w1", "w3": "shared_w3", "w2": "shared_w2"}


def to_program(w: dict, K: int) -> dict:
    """The benchmark's weight layout as the program's parameter tree:
    ``K`` leading dense layers, then the scanned MoE layers."""
    return {
        "embed": w["embed"], "head": w["head"], "final_ln": w["final_norm"],
        "lead": [{"mix": {k: w[v][i] for k, v in _MIX.items()},
                  "ffn": {k: w[v][i] for k, v in _DENSE.items()}}
                 for i in range(K)],
        "blocks": {"l0": {
            "mix": {k: w[v][K:] for k, v in _MIX.items()},
            "ffn": {**{k: w[v] for k, v in _MOE.items()},
                    "shared": {k: w[v] for k, v in _SHARED.items()}}}}}


def to_benchmark(p: dict) -> dict:
    """The program's parameter tree (or a tree shaped like it) in the
    benchmark's layout."""
    lead, blk = p["lead"], p["blocks"]["l0"]
    out = {"embed": p["embed"], "head": p["head"], "final_norm": p["final_ln"]}
    for k, v in _MIX.items():
        out[v] = jnp.concatenate([jnp.stack([l["mix"][k] for l in lead]),
                                  blk["mix"][k]])
    out.update({v: jnp.stack([l["ffn"][k] for l in lead])
                for k, v in _DENSE.items()})
    out.update({v: blk["ffn"][k] for k, v in _MOE.items()})
    out.update({v: blk["ffn"]["shared"][k] for k, v in _SHARED.items()})
    return out


@lru_cache(maxsize=4)
def check_layout(frozen_sizes: tuple) -> None:
    """Refuse to run if the program's parameter tree no longer has the
    layout that :func:`to_program` writes."""
    from repro.models import registry as R
    cfg = arch(frozen_sizes)
    want = jax.eval_shape(lambda k: R.init_params(k, cfg)[0],
                          jax.random.key(0))
    got = jax.eval_shape(
        lambda k: to_program(data.weights(k, dict(frozen_sizes)),
                             cfg.first_k_dense), jax.random.key(0))
    if (jax.tree.structure(want) != jax.tree.structure(got) or
            [a.shape for a in jax.tree.leaves(want)]
            != [b.shape for b in jax.tree.leaves(got)]):
        raise RuntimeError("the program's moonlight parameter tree "
                           "changed; update to_program")


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
class Aot:
    """A jitted program compiled ahead of its first call; the compiled
    program serves every later call of the same shapes, and is kept in
    :data:`COMPILED` under its module's name."""

    def __init__(self, fn, module: str, **jit_kw):
        self.jit, self.module, self.compiled = jax.jit(fn, **jit_kw), module, None

    def __call__(self, *args):
        if self.compiled is None:
            self.compiled = COMPILED[self.module] = self.jit.lower(
                *args).compile()
        return self.compiled(*args)


@lru_cache(maxsize=4)
def _text(compiled) -> str:
    return compiled.as_text()


def hlo_text(module: str):
    """The optimized HLO text of a program compiled in this process, or
    None: not compiled, or its executable gives no text (one served from
    the persistent compilation cache gives it, on the TPU as on the CPU).
    Metric readers call this after the window, where an error would cost
    the run its result line."""
    compiled = COMPILED.get(module)
    if compiled is None:
        return None
    try:
        return _text(compiled)
    except Exception:                  # noqa: BLE001 — no text, no number
        return None


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
        p = to_program(data.weights(key, sizes), cfg.first_k_dense)
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
        "train": Aot(count_train_step, TRAIN_MODULE, donate_argnums=(0, 1)),
        "grad_norms": jax.jit(count_grad_norms),
        "change_norms": jax.jit(count_change_norms),
        "digest": jax.jit(weight_digest),
        "embed": jax.jit(seurat_embed),
    }


# ------------------------------------------------------------ factories
def count_tool(config: str, train_steps: int, batch: int):
    frozen = sizes_of(config)

    def fn(inputs, ctx):
        prog = programs(frozen)
        shard = inputs["shard"]
        tokens = jnp.asarray(shard["tokens"])
        key = jax_key(shard["seed"])
        params, opt = prog["init"](key)
        rows = tokens.shape[0]
        losses, load, dropped = [], [], []
        grad1 = change3 = routing1 = None
        for s in range(train_steps):
            lo = (s * batch) % rows
            params, opt, metrics = prog["train"](params, opt,
                                                 tokens[lo:lo + batch])
            losses.append(metrics["loss"])
            load.append(metrics["expert_load"])
            dropped.append(metrics["dropped"])
            if s == 0:
                grad1 = prog["grad_norms"](opt)
                routing1 = metrics["routing"]
            if s == 2:
                change3 = prog["change_norms"](params, key)
        return {"model": params,
                "stats": {"sample": shard["sample"],
                          "losses": jnp.stack(losses),
                          "grad_norms_1": grad1, "change_norms_3": change3,
                          "expert_load": jnp.stack(load),
                          "dropped": jnp.stack(dropped),
                          "routing_1": routing1,
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
