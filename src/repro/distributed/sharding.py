"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Every parameter carries a tuple of logical axis names (built by the model
initialisers).  A RULESET maps logical names to mesh axes; ``logical_to_specs``
turns (axes_tree, shapes_tree) into a PartitionSpec tree, dropping any mapping
whose dimension is not divisible by the mesh-axis size (``safe_spec``) and
deduplicating mesh axes used twice within one spec.

Baseline ruleset = TP over "model" for vocab/heads/mlp/rnn + ZeRO-style FSDP
over "data" for the d_model dim; params replicated over "pod" (pure DP across
pods, gradient all-reduce on the DCN axis).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Dict[str, Any]  # logical name -> mesh axis | tuple | None


def abstract_mesh(shape: Sequence[int], axis_names: Sequence[str]):
    """A device-free ``AbstractMesh`` (sharding rules only read
    ``mesh.shape``)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axis_names))

RULESETS: Dict[str, Rules] = {
    # paper-faithful baseline: TP(model) x FSDP(data), experts TP-sliced
    "base": {
        "vocab": "model", "heads": "model", "kv": "model", "mlp": "model",
        "rnn": "model", "rnn_out": "model", "embed": "data",
        "experts": None, "conv": None, "layers": None, "kv_heads": None,
        "head_rnn": "model",
    },
    # expert-parallel variant: experts over model axis, expert-ffn unsharded
    "ep": {
        "vocab": "model", "heads": "model", "kv": "model", "mlp": None,
        "rnn": "model", "rnn_out": "model", "embed": "data",
        "experts": "model", "conv": None, "layers": None, "kv_heads": None,
        "head_rnn": "model",
    },
    # no-FSDP (replicated weights over data) — ablation / small models
    "tp_only": {
        "vocab": "model", "heads": "model", "kv": "model", "mlp": "model",
        "rnn": "model", "rnn_out": "model", "embed": None,
        "experts": None, "conv": None, "layers": None, "kv_heads": None,
        "head_rnn": "model",
    },
}


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return math.prod(mesh.shape[a] for a in axis)
    return mesh.shape[axis]


def safe_spec(shape: Sequence[int], want: Sequence[Any], mesh: Mesh) -> P:
    """Drop mesh axes that don't divide their dim or repeat within the spec."""
    used = set()
    parts = []
    for dim, axis in zip(shape, want):
        if axis is None:
            parts.append(None)
            continue
        flat = axis if isinstance(axis, tuple) else (axis,)
        if any(a in used for a in flat) or dim % _axis_size(mesh, axis) != 0:
            parts.append(None)
            continue
        used.update(flat)
        parts.append(axis)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def logical_to_specs(axes_tree, shapes_tree, mesh: Mesh,
                     rules: Rules) -> Any:
    """PartitionSpec tree for a parameter pytree."""
    def one(axes: Tuple, shape) -> P:
        want = [rules.get(a) if a else None for a in axes]
        return safe_spec(shape.shape, want, mesh)

    return jax.tree.map(one, axes_tree, shapes_tree,
                        is_leaf=lambda t: isinstance(t, tuple))


def data_axes(mesh: Mesh):
    """The DP mesh axes: ("pod","data") on a multi-pod mesh else "data"."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def batch_specs(batch_shapes, mesh: Mesh) -> Any:
    """Input-batch specs: leading dim over the DP axes when divisible."""
    dp = data_axes(mesh)
    dp_axis = dp if len(dp) > 1 else dp[0]

    def one(s):
        want = [dp_axis] + [None] * (len(s.shape) - 1)
        return safe_spec(s.shape, want, mesh)

    return jax.tree.map(one, batch_shapes)


def cache_specs(cache_shapes, mesh: Mesh, *, scanned: bool) -> Any:
    """Decode-cache specs: batch over DP axes; KV caches sequence-sharded
    over "model" (flash-decoding style).

    Sequence sharding is the serving-critical choice: decode attention
    contracts the feature dim, so feature-sharded caches force a full
    per-layer cache all-gather every token (§Perf iteration F2 measured
    2.4 GB/layer/token for minicpm). With the *sequence* dim sharded, the
    softmax/PV reductions over S produce only tiny per-layer all-reduces
    and each chip reads just its local cache slice. Recurrent-state leaves
    (no long S dim) fall back to sharding the trailing feature dim.
    """
    dp = data_axes(mesh)
    dp_axis = dp if len(dp) > 1 else dp[0]

    def one(s):
        nd = len(s.shape)
        want: list = [None] * nd
        b_pos = 1 if scanned and nd >= 2 else 0
        if nd > b_pos:
            want[b_pos] = dp_axis
        if nd >= b_pos + 3 and s.shape[-3] >= 1024:
            want[-3] = "model"               # the (long) sequence dim
        elif nd >= b_pos + 3:
            want[-1] = "model"               # recurrent state: feature dim
        return safe_spec(s.shape, want, mesh)

    return jax.tree.map(one, cache_shapes)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda t: isinstance(t, P))
