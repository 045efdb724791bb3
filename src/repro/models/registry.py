"""Model-zoo registry: shape/axes introspection, parameter accounting,
and the public model API surface used by launch/, core/ and tests.

Nothing here allocates device memory for full-size configs — shapes come
from ``jax.eval_shape`` over the real initialiser so analytic counts can
never drift from the implementation.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models import transformer as tf

# Re-exported model API (single entry point for the rest of the system).
init_params = tf.init_params
forward_train = tf.forward_train
forward_logits = tf.forward_logits
prefill = tf.prefill
decode_step = tf.decode_step
init_cache = tf.init_cache


def params_and_axes_shapes(cfg: ArchConfig):
    """(ShapeDtypeStruct pytree, logical-axes pytree) without allocation."""
    box: Dict[str, Any] = {}

    def f(k):
        p, a = tf.init_params(k, cfg)
        box["axes"] = a          # static side-channel, captured at trace time
        return p

    shapes = jax.eval_shape(f, jax.random.key(0))
    return shapes, box["axes"]


def _is_expert_leaf(path) -> bool:
    """A routed expert tensor: not a leading dense layer's MLP, not the
    shared experts."""
    keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
    return ("ffn" in keys and keys[-1] in ("w1", "w2", "w3")
            and keys[0] != "lead" and "shared" not in keys)


def count_params_analytic(cfg: ArchConfig, active_only: bool = False) -> int:
    """Exact parameter count (from init shapes).  ``active_only`` scales
    routed expert tensors by top_k/E, E the router's width: a tensor of
    ``held`` experts meets top_k * held / E assignments a token, each
    1 / held of it."""
    shapes, _ = params_and_axes_shapes(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = 0
    for path, leaf in leaves:
        n = math.prod(leaf.shape) if leaf.shape else 1
        if active_only and cfg.is_moe and _is_expert_leaf(path):
            n = int(n * cfg.top_k / cfg.n_experts)
        total += n
    return total


def count_flops_params(cfg: ArchConfig, active_only: bool = True) -> int:
    """N for the 6·N·D model-FLOPs estimate: parameters that participate in
    matmuls per token.  Excludes the embedding *lookup* (no FLOPs); the tied
    head re-uses the embedding table so it stays included exactly once."""
    shapes, _ = params_and_axes_shapes(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = 0
    for path, leaf in leaves:
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        n = math.prod(leaf.shape) if leaf.shape else 1
        if keys and keys[0] == "embed" and not cfg.tie_embeddings:
            continue                       # pure lookup, no matmul
        if active_only and cfg.is_moe and _is_expert_leaf(path):
            n = int(n * cfg.top_k / cfg.n_experts)
        total += n
    return total


def model_flops(cfg: ArchConfig, tokens: int, *, train: bool = True) -> float:
    """MODEL_FLOPS = 6·N·D (train: fwd+bwd) or 2·N·D (inference fwd)."""
    n = count_flops_params(cfg, active_only=True)
    return (6.0 if train else 2.0) * n * tokens


def param_bytes(cfg: ArchConfig, dtype_bytes: int = 4) -> int:
    return count_params_analytic(cfg) * dtype_bytes
