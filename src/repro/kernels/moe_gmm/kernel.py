"""Fused grouped expert-FFN kernel (the MoE compute hot-spot).

One pallas_call computes y[e] = (act(x[e] @ w1[e]) * (x[e] @ w3[e])) @ w2[e]
for every expert without materialising the (E, C, f) hidden state in HBM:
the grid's innermost (sequential) dimension walks f-blocks, accumulating the
down-projection into a VMEM scratch accumulator — the hidden activation
exists only as one (block_c x block_f) VMEM tile at a time.

VMEM per step (mixtral-8x7b, d=4096, block_c=128, bf16): every in/out tile
is double-buffered, so block_f=512 needs x+y 2 MiB + w1/w3/w2 12 MiB, twice,
plus acc(f32) 2 MiB = 30 MiB, which the compiler refuses against v5e's
16 MiB scoped limit; ops._pick_blocks shrinks block_f to 128 (12 MiB).
Tiles are MXU-aligned (128-multiples in c/f/d).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _act(name, x):
    if name == "swiglu":
        return jax.nn.silu(x)
    if name in ("geglu", "gelu"):
        return jax.nn.gelu(x)
    if name == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def _ffn_kernel(x_ref, w1_ref, w3_ref, w2_ref, y_ref, acc_ref, *, act: str,
                gated: bool):
    jf = pl.program_id(2)

    @pl.when(jf == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                           # (bc, d)
    h = _act(act, jax.lax.dot(x, w1_ref[0],
                              preferred_element_type=jnp.float32))
    if gated:
        h = h * jax.lax.dot(x, w3_ref[0],
                            preferred_element_type=jnp.float32)
    acc_ref[...] += jax.lax.dot(h.astype(x.dtype), w2_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(jf == pl.num_programs(2) - 1)
    def _done():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)


def expert_ffn_pallas(xe, w1, w3, w2, *, act: str = "swiglu",
                      block_c: int = 128, block_f: int = 512,
                      interpret: bool = True):
    """xe: (E, C, d); w1/w3: (E, d, f); w2: (E, f, d) -> (E, C, d)."""
    E, C, d = xe.shape
    f = w1.shape[-1]
    block_c = min(block_c, C)
    block_f = min(block_f, f)
    gated = w3 is not None
    grid = (E, C // block_c, f // block_f)
    kern = functools.partial(_ffn_kernel, act=act, gated=gated)
    in_specs = [
        pl.BlockSpec((1, block_c, d), lambda e, i, j: (e, i, 0)),
        pl.BlockSpec((1, d, block_f), lambda e, i, j: (e, 0, j)),
        pl.BlockSpec((1, d, block_f), lambda e, i, j: (e, 0, j)),
        pl.BlockSpec((1, block_f, d), lambda e, i, j: (e, j, 0)),
    ]
    args = [xe, w1, w3 if gated else w1, w2]
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_c, d), lambda e, i, j: (e, i, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, d), xe.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, d), jnp.float32)],
        interpret=interpret,
    )(*args)
