"""The data of the singlecell-minicpm deployment, made from a seed.

Weights come in one layout of the benchmark's own (stacked by layer,
named as below), so the tools and the plain reference start from the
same numbers without either reading the other's.  Both functions run on
the device in one jitted call.
"""
from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp

# the leaves in the order their PRNG streams are folded in
LEAVES = (
    "embed", "final_norm", "attn_norm", "wq", "wk", "wv", "wo",
    "mlp_norm", "w1", "w3", "w2")
# leaves that are not stacked by layer
UNSTACKED = ("embed", "final_norm")


def shapes(sizes: dict) -> dict:
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    kv, f = sizes["num_key_value_heads"], sizes["intermediate_size"]
    L, V = sizes["num_hidden_layers"], sizes["vocab_size"]
    dh = d // h
    return {"embed": (V, d), "final_norm": (d,),
            "attn_norm": (L, d), "wq": (L, d, h * dh), "wk": (L, d, kv * dh),
            "wv": (L, d, kv * dh), "wo": (L, h * dh, d),
            "mlp_norm": (L, d), "w1": (L, d, f), "w3": (L, d, f),
            "w2": (L, f, d)}


def _std(name: str, shape, sizes: dict) -> float:
    """Zero for norms (applied as 1 + w); fan-in scaling for matrices,
    with the residual outputs further scaled by 1/sqrt(2 L)."""
    if name.endswith("norm"):
        return 0.0
    if name == "embed":
        return sizes["hidden_size"] ** -0.5
    std = shape[-2] ** -0.5
    if name in ("wo", "w2"):
        std /= math.sqrt(2 * sizes["num_hidden_layers"])
    return std


def _freeze(sizes: dict):
    return tuple(sorted(sizes.items()))


@lru_cache(maxsize=8)
def _weights_fn(frozen):
    sizes = dict(frozen)
    shp = shapes(sizes)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(LEAVES):
            std = _std(name, shp[name], sizes)
            if std == 0.0:
                out[name] = jnp.zeros(shp[name], jnp.float32)
            else:
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shp[name], jnp.float32)
        return out
    return make


def weights(key, sizes: dict) -> dict:
    """Float32 weights in the benchmark's layout, on the device."""
    return _weights_fn(_freeze(sizes))(key)


@lru_cache(maxsize=8)
def _reads_fn(n: int, rows: int, width: int, vocab: int):
    @jax.jit
    def make(key):
        return jax.random.randint(key, (n, rows, width), 0, vocab, jnp.int32)
    return make


def reads(key, n_samples: int, rows: int, seq_len: int, vocab: int):
    """``n_samples`` shards of ``rows`` rows of ``seq_len + 1`` token ids
    (inputs and next-token labels), on the device."""
    return _reads_fn(n_samples, rows, seq_len + 1, vocab)(key)
