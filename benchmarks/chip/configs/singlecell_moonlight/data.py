"""The weights of the singlecell-moonlight deployment, made from a seed.

One layout of the benchmark's own, stacked by layer and named as below, so
the tools and the plain reference start from the same numbers without
either reading the other's.  Attention leaves stack every layer; the
``dense_*`` leaves stack the leading dense layers, the rest the MoE layers
after them.  A sample's reads are made as ``singlecell-minicpm`` makes
them (``singlecell_minicpm.data.reads``).
"""
from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp

# the leaves in the order their PRNG streams are folded in
LEAVES = (
    "embed", "head", "final_norm",
    "attn_norm", "wq", "wkva", "kv_norm", "wkvb", "wo",
    "dense_norm", "dense_w1", "dense_w3", "dense_w2",
    "moe_norm", "router", "bias", "w1", "w3", "w2",
    "shared_w1", "shared_w3", "shared_w2")
# leaves that are not stacked by layer
UNSTACKED = ("embed", "head", "final_norm")
# outputs onto the residual stream, scaled down by 1/sqrt(2 L)
RESIDUAL = ("wo", "dense_w2", "w2", "shared_w2")


def shapes(sz: dict) -> dict:
    d, H, V = sz["hidden_size"], sz["num_attention_heads"], sz["vocab_size"]
    L, K = sz["num_hidden_layers"], sz["first_k_dense_replace"]
    r, dn = sz["kv_lora_rank"], sz["qk_nope_head_dim"]
    dr, dv = sz["qk_rope_head_dim"], sz["v_head_dim"]
    F, f = sz["intermediate_size"], sz["moe_intermediate_size"]
    E, Hn = sz["router_experts"], sz["n_routed_experts"]
    fs, M = sz["n_shared_experts"] * f, L - K
    return {"embed": (V, d), "head": (d, V), "final_norm": (d,),
            "attn_norm": (L, d), "wq": (L, d, H * (dn + dr)),
            "wkva": (L, d, r + dr), "kv_norm": (L, r),
            "wkvb": (L, r, H * (dn + dv)), "wo": (L, H * dv, d),
            "dense_norm": (K, d), "dense_w1": (K, d, F),
            "dense_w3": (K, d, F), "dense_w2": (K, F, d),
            "moe_norm": (M, d), "router": (M, d, E), "bias": (M, E),
            "w1": (M, Hn, d, f), "w3": (M, Hn, d, f), "w2": (M, Hn, f, d),
            "shared_w1": (M, d, fs), "shared_w3": (M, d, fs),
            "shared_w2": (M, fs, d)}


def _std(name: str, shape, sz: dict) -> float:
    """Zero for norms (applied as 1 + w); 1 for the embedding; the
    configuration's for the selection bias; fan-in scaling for matrices,
    with the residual outputs further scaled by 1/sqrt(2 L)."""
    if name.endswith("norm"):
        return 0.0
    if name == "embed":
        return 1.0
    if name == "bias":
        return sz["correction_bias_std"]
    std = shape[-2] ** -0.5
    if name in RESIDUAL:
        std /= math.sqrt(2 * sz["num_hidden_layers"])
    return std


@lru_cache(maxsize=8)
def _weights_fn(frozen):
    sz = dict(frozen)
    shp = shapes(sz)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(LEAVES):
            std = _std(name, shp[name], sz)
            if std == 0.0:
                out[name] = jnp.zeros(shp[name], jnp.float32)
            else:
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shp[name], jnp.float32)
        return out
    return make


def weights(key, sizes: dict) -> dict:
    """Float32 weights in the benchmark's layout, on the device."""
    return _weights_fn(tuple(sorted(sizes.items())))(key)
