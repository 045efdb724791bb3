"""Chunkwise-parallel mLSTM kernel (xLSTM matrix memory).

TPU adaptation of the chunkwise mLSTM algorithm: the (Dh x Dh) matrix state
C (plus normaliser n and log-stabiliser m) stays resident in VMEM scratch
across the sequential chunk dimension; each grid step does the intra-chunk
quadratic part as two MXU matmuls ((T x Dh)@(Dh x T), (T x T)@(T x Dh)) and
the inter-chunk part as one (T x Dh)@(Dh x Dh).  Everything is log-space
stabilised exactly like the jnp reference (models.xlstm.mlstm_chunkwise).

Grid: (B*H, S/chunk) — chunk dim sequential.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, ig_ref, fg_ref, h_ref,
                  cout_ref, nout_ref, mout_ref,
                  c_ref, n_ref, m_ref, *, chunk: int, dh: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    q = q_ref[0].astype(jnp.float32) / math.sqrt(dh)     # (T, Dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    ig = ig_ref[0].astype(jnp.float32)                   # (T, 1)
    lf = jax.nn.log_sigmoid(fg_ref[0].astype(jnp.float32))

    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over time (Mosaic has no cumsum): masked row sum
    bc = jnp.sum(jnp.where(causal, lf.T, 0.0), axis=1, keepdims=True)  # (T, 1)
    bt = bc[chunk - 1]                                   # (1,)
    m_prev = m_ref[0, 0]

    # intra-chunk pair log-weights a[t, s] = bc_t - bc_s + ig_s (causal)
    a = bc - bc.T + ig.T                                 # (T, T)
    a = jnp.where(causal, a, NEG_INF)
    m_intra = jnp.max(a, axis=1, keepdims=True)          # (T, 1)
    m_inter = bc + m_prev                                # (T, 1)
    m_t = jnp.maximum(m_intra, m_inter)

    w_inr = jnp.exp(a - m_t)                             # (T, T)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * w_inr
    num = jax.lax.dot(scores, v, preferred_element_type=jnp.float32)
    w_out = jnp.exp(m_inter - m_t)                       # (T, 1)
    qw = q * w_out
    num += jax.lax.dot(qw, c_ref[...], preferred_element_type=jnp.float32)
    den = jnp.sum(scores, axis=1, keepdims=True) + \
        jnp.sum(qw * n_ref[...], axis=1, keepdims=True)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
    h_ref[0] = h.astype(h_ref.dtype)

    # ---- state update ----------------------------------------------------
    m_new = jnp.maximum(bt[0] + m_prev, jnp.max(ig + bt[0] - bc))
    f_c = jnp.exp(bt[0] + m_prev - m_new)
    g = jnp.exp(ig + (bt[0] - bc) - m_new)               # (T, 1)
    kg = k * g
    c_ref[...] = f_c * c_ref[...] + jax.lax.dot_general(
        kg, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_ref[...] = f_c * n_ref[...] + jnp.sum(kg, axis=0, keepdims=True)
    m_ref[...] = jnp.full_like(m_ref, m_new)

    @pl.when(it == pl.num_programs(1) - 1)
    def _done():
        cout_ref[0] = c_ref[...]
        nout_ref[0] = n_ref[...]
        mout_ref[0] = m_ref[...]


def mlstm_chunkwise_pallas(q, k, v, ig, fg, *, chunk: int = 64,
                           interpret: bool = True):
    """q,k,v: (BH, S, Dh); ig,fg: (BH, S, 1).
    Returns (h (BH,S,Dh) f32, C (BH,Dh,Dh), n (BH,1,Dh), m (BH,1,1))."""
    BH, S, Dh = q.shape
    chunk = min(chunk, S)
    grid = (BH, S // chunk)
    kern = functools.partial(_mlstm_kernel, chunk=chunk, dh=Dh)
    spec_qkv = pl.BlockSpec((1, chunk, Dh), lambda b, t: (b, t, 0))
    spec_g = pl.BlockSpec((1, chunk, 1), lambda b, t: (b, t, 0))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec_qkv, spec_qkv, spec_qkv, spec_g, spec_g],
        out_specs=[spec_qkv,
                   pl.BlockSpec((1, Dh, Dh), lambda b, t: (b, 0, 0)),
                   pl.BlockSpec((1, 1, Dh), lambda b, t: (b, 0, 0)),
                   pl.BlockSpec((1, 1, 1), lambda b, t: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, S, Dh), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Dh, Dh), jnp.float32),
                   jax.ShapeDtypeStruct((BH, 1, Dh), jnp.float32),
                   jax.ShapeDtypeStruct((BH, 1, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((Dh, Dh), jnp.float32),
                        pltpu.VMEM((1, Dh), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(q, k, v, ig, fg)
