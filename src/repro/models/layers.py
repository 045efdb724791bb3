"""Shared neural building blocks for the model zoo.

All functions are pure JAX (the reference path used for CPU dry-run lowering
and as kernel oracles).  Perf-critical hot-spots have Pallas TPU twins in
``repro.kernels`` selected via ``repro.models.registry.KERNEL_MODE``.

Conventions:
  * activations compute in bf16 (cfg.dtype), parameters stored fp32,
  * attention uses blockwise (flash-style) evaluation for long sequences so
    the S x S score matrix is never materialised above ``_QBLOCK`` rows,
  * every sequence-stack is `lax.scan`-compatible (stacked leading dim).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_QBLOCK = 512          # query-block rows for blockwise attention
_PLAIN_ATTN_MAX = _QBLOCK  # up to one block, plain attention


# ---------------------------------------------------------------------------
# Elementary ops
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def dense(x, w):
    """x @ w with fp32 params cast to activation dtype."""
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype))


def rope(x, positions, theta: float = 10_000.0):
    """Rotary embedding. x: (..., S, H, Dh); positions: (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (..., S, half)
    ang = ang[..., :, None, :]                                # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


_ACTS = {"swiglu": jax.nn.silu, "geglu": jax.nn.gelu,
         "gelu": jax.nn.gelu, "relu2": _relu2}


def act_fn(name: str):
    return _ACTS[name]


def is_gated_act(name: str) -> bool:
    return name in ("swiglu", "geglu")


def gated_mlp(x, p, act: str):
    """MLP: gated (SwiGLU/GeGLU: w1, w3, w2) or plain (gelu/relu2: w1, w2)."""
    h = act_fn(act)(dense(x, p["w1"]))
    if "w3" in p:
        h = h * dense(x, p["w3"])
    return dense(h, p["w2"])


# ---------------------------------------------------------------------------
# Attention (GQA, causal / sliding-window / cross), blockwise evaluation
# ---------------------------------------------------------------------------

def _attn_scores_block(q, k, scale):
    """q: (B, bq, KH, G, Dh)  k: (B, S, KH, Dh) -> (B, KH, G, bq, S)."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k) * scale


def attention(q, k, v, *, causal: bool, window: int = 0, q_offset=0,
              kv_len: Optional[jax.Array] = None):
    """Grouped-query attention without materialising full S_q x S_k scores.

    q, k: (B, S_q|S_k, H|KH, Dh); v: (B, S_k, KH, Dv).  H = KH * G.  The
    value width Dv may differ from the query/key width Dh (latent
    attention); scores scale by 1/sqrt(Dh).
    ``q_offset``: absolute position of q[0] relative to k[0] (decode/prefill).
    ``window`` > 0 restricts attention to the last ``window`` key positions.
    ``kv_len``: optional dynamic number of valid key slots (decode caches).
    Returns (B, S_q, H, Dv).
    """
    B, Sq, H, Dh = q.shape
    Sk, KH, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KH
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Sq, KH, G, Dh)

    k_pos = jnp.arange(Sk)

    def block_out(q_blk, q_pos):
        # q_blk: (B, bq, KH, G, Dh); q_pos: (bq,) absolute positions
        s = _attn_scores_block(q_blk, k, scale).astype(jnp.float32)
        mask = jnp.ones((q_pos.shape[0], Sk), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        if kv_len is not None:
            mask &= (k_pos < kv_len)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    if Sq <= _PLAIN_ATTN_MAX or Sq % _QBLOCK:
        out = block_out(qg, q_offset + jnp.arange(Sq))
    else:
        nblk = Sq // _QBLOCK
        qb = qg.reshape(B, nblk, _QBLOCK, KH, G, Dh).swapaxes(0, 1)

        # checkpoint the block so backward recomputes the (bq, S) probs
        # instead of saving them per scan step (flash-backward memory shape)
        @partial(jax.checkpoint, prevent_cse=False)
        def body(_, xs):
            blk, i = xs
            pos = q_offset + i * _QBLOCK + jnp.arange(_QBLOCK)
            return None, block_out(blk, pos)

        _, outs = lax.scan(body, None, (qb, jnp.arange(nblk)))
        out = outs.swapaxes(0, 1).reshape(B, Sq, KH, G, Dv)
    return out.reshape(B, Sq, H, Dv)


def self_attention_block(x, p, cfg, *, positions, causal=True, window=0,
                         kernel_mode: str = "reference"):
    """Pre-norm self-attention residual block (no MLP)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    B, S, _ = h.shape
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(h, p["wq"]).reshape(B, S, H, Dh)
    k = dense(h, p["wk"]).reshape(B, S, KH, Dh)
    v = dense(h, p["wv"]).reshape(B, S, KH, Dh)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if kernel_mode == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        o = fa_ops.flash_attention(q, k, v, causal=causal, window=window)
    else:
        o = attention(q, k, v, causal=causal, window=window)
    o = dense(o.reshape(B, S, H * Dh), p["wo"])
    return x + o


def cross_attention_block(x, p, cfg, *, memory):
    """Gated cross-attention to modality embeddings (Llama-3.2-Vision style)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    B, S, _ = h.shape
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(h, p["wq"]).reshape(B, S, H, Dh)
    k = dense(memory, p["wk"]).reshape(B, memory.shape[1], KH, Dh)
    v = dense(memory, p["wv"]).reshape(B, memory.shape[1], KH, Dh)
    o = attention(q, k, v, causal=False)
    o = dense(o.reshape(B, S, H * Dh), p["wo"])
    return x + jnp.tanh(p["gate"].astype(x.dtype)) * o


def mlp_block(x, p, cfg):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    return x + gated_mlp(h, p, cfg.act)


# ---------------------------------------------------------------------------
# Decode-path attention with a KV ring-buffer cache
# ---------------------------------------------------------------------------

def decode_attention_block(x, p, cfg, cache, pos, *, window=0):
    """One-token self-attention against a cache.

    cache: {"k","v": (B, S_cache, KH, Dh)}; pos: scalar int32 absolute pos.
    For windowed attention S_cache == window and writes wrap (ring buffer):
    RoPE is applied pre-insertion so rotated keys stay valid under wrap.
    """
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    B = h.shape[0]
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(h, p["wq"]).reshape(B, 1, H, Dh)
    k = dense(h, p["wk"]).reshape(B, 1, KH, Dh)
    v = dense(h, p["wv"]).reshape(B, 1, KH, Dh)
    posv = jnp.full((1,), pos)
    q = rope(q, posv, cfg.rope_theta)
    k = rope(k, posv, cfg.rope_theta)
    S_cache = cache["k"].shape[1]
    slot = (pos % S_cache) if window else pos
    ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                  (0, slot, 0, 0))
    cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                  (0, slot, 0, 0))
    kv_len = jnp.minimum(pos + 1, S_cache)
    o = attention(q, ck, cv, causal=False, kv_len=kv_len)
    o = dense(o.reshape(B, 1, H * Dh), p["wo"])
    return x + o, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, mask=None, *, chunked: bool = False):
    """Mean token cross-entropy. logits (B,S,V) fp32-upcast; labels (B,S)."""
    if chunked:
        return _chunked_xent(logits, labels, mask)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def _chunked_xent(logits, labels, mask, chunk: int = 1024):
    B, S, V = logits.shape
    n = S // chunk
    lg = logits.reshape(B, n, chunk, V).swapaxes(0, 1)
    lb = labels.reshape(B, n, chunk).swapaxes(0, 1)
    mk = (jnp.ones_like(labels, jnp.float32) if mask is None else mask)
    mk = mk.reshape(B, n, chunk).swapaxes(0, 1)

    def body(c, xs):
        lgi, lbi, mki = xs
        lse = jax.nn.logsumexp(lgi.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            lgi.astype(jnp.float32), lbi[..., None], axis=-1)[..., 0]
        return (c[0] + jnp.sum((lse - gold) * mki), c[1] + jnp.sum(mki)), None

    (tot, cnt), _ = lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                             (lg, lb, mk))
    return tot / jnp.maximum(cnt, 1)
