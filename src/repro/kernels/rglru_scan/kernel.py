"""Chunked RG-LRU linear-recurrence kernel.

The diagonal recurrence h_t = a_t * h_{t-1} + b_t is bandwidth-bound, not
compute-bound: the TPU-native arrangement keeps a (block_b x block_d) state
tile resident in VMEM scratch while the sequential grid dimension streams
time-chunks through, so every element of a/b is read exactly once from HBM
and h is written exactly once (vs. the unfused XLA scan, which round-trips
the carry).  Gates are fused in (sigmoid/softplus on the VPU) so the
pre-activations never materialise in HBM either.

Grid: (B/block_b, D/block_d, S/block_s) — time (last dim) is sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

RGLRU_C = 8.0
SUB = 8          # time steps per aligned load/store (one f32 sublane tile)


def _rglru_kernel(x_ref, lam_ref, ga_ref, gx_ref, y_ref, h_ref, hout_ref,
                  *, block_s: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    c = -RGLRU_C * jax.nn.softplus(
        lam_ref[...].astype(jnp.float32))               # (1, bd)

    def step(g, h):
        # one sublane-aligned group of time steps: (bb, SUB, bd)
        at = pl.ds(pl.multiple_of(g * SUB, SUB), SUB)
        x = x_ref[:, at, :].astype(jnp.float32)
        log_a = c * jax.nn.sigmoid(ga_ref[:, at, :].astype(jnp.float32))
        a = jnp.exp(log_a)
        # sqrt(1 - a^2) without cancellation near a = 1: Mosaic has no
        # expm1, and expm1(2y) = tanh(y) * (exp(2y) + 1)
        beta = jnp.sqrt(-jnp.tanh(log_a) * (1.0 + a * a))
        b = beta * jax.nn.sigmoid(gx_ref[:, at, :].astype(jnp.float32)) * x
        hs = []
        for t in range(SUB):
            h = a[:, t, :] * h + b[:, t, :]
            hs.append(h)
        y_ref[:, at, :] = jnp.stack(hs, axis=1).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_s // SUB, step, h_ref[...])
    h_ref[...] = h

    @pl.when(it == pl.num_programs(2) - 1)
    def _done():
        hout_ref[...] = h_ref[...]


def rglru_pallas(x, lam, ga, gx, *, block_b: int = 8, block_d: int = 512,
                 block_s: int = 128, interpret: bool = True):
    """x, ga, gx: (B, S, D); lam: (D,). Returns (y (B,S,D) f32, h_last)."""
    B, S, D = x.shape
    block_b = min(block_b, B)
    block_d = min(block_d, D)
    block_s = min(block_s, S)
    grid = (B // block_b, D // block_d, S // block_s)
    kern = functools.partial(_rglru_kernel, block_s=block_s)
    spec_x = pl.BlockSpec((block_b, block_s, block_d),
                          lambda i, j, t: (i, t, j))
    y, h_last = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec_x,
                  pl.BlockSpec((1, block_d), lambda i, j, t: (0, j)),
                  spec_x, spec_x],
        out_specs=[spec_x,
                   pl.BlockSpec((block_b, block_d), lambda i, j, t: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((B, S, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_b, block_d), jnp.float32)],
        interpret=interpret,
    )(x, lam.reshape(1, D), ga, gx)
    return y, h_last
