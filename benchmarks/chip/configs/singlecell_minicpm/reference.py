"""Plain reference of the singlecell-minicpm step programs.

Straight ``jax.numpy`` in float32 with every matmul at ``HIGHEST``
precision, written from the model's description and importing nothing of
the program: a decoder of ``num_hidden_layers`` blocks (RMSNorm weight
applied as ``1 + w``, rotary position embedding on rotated halves, causal
softmax attention, SwiGLU MLP), embeddings scaled by ``sqrt(hidden)`` and
tied to the output projection, next-token cross-entropy plus a 1e-4
z-loss on the log-partition, trained by AdamW with the constants below.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale (accumulation stays float32), the step
below the bfloat16 that the configuration states.  Gradients pass the
rounding straight through, so the backward pass sees the rounded
operands but no cotangent underflows.
"""
from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.configs.singlecell_minicpm import data

HIGHEST = jax.lax.Precision.HIGHEST
# AdamW as the program's default optimizer states it
LR, B1, B2, EPS, WD, CLIP = 3e-4, 0.9, 0.95, 1e-8, 0.1, 1.0
WARMUP, TOTAL = 100, 10_000
Z_LOSS = 1e-4
FP8_MAX = 448.0


def _identity(x):
    return x


def _fp8(x):
    scale = jax.lax.stop_gradient(
        FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


ROUND = {"f32": _identity, "fp8": _fp8}


def _mm(spec, a, b, rnd):
    return jnp.einsum(spec, rnd(a), rnd(b), precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def _rope(x, theta):
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(w, tokens, sz, rnd):
    """Final normed hidden states, (B, S, d)."""
    d, h = sz["hidden_size"], sz["num_attention_heads"]
    kv, eps = sz["num_key_value_heads"], sz["rms_norm_eps"]
    dh = d // h
    b, s = tokens.shape
    x = w["embed"][tokens] * math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    for l in range(sz["num_hidden_layers"]):
        y = _rms_norm(x, w["attn_norm"][l], eps)
        q = _mm("bsd,df->bsf", y, w["wq"][l], rnd).reshape(b, s, h, dh)
        k = _mm("bsd,df->bsf", y, w["wk"][l], rnd).reshape(b, s, kv, dh)
        v = _mm("bsd,df->bsf", y, w["wv"][l], rnd).reshape(b, s, kv, dh)
        q, k = _rope(q, sz["rope_theta"]), _rope(k, sz["rope_theta"])
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        sc = _mm("bqhd,bkhd->bhqk", q, k, rnd) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", p, v, rnd).reshape(b, s, h * dh)
        x = x + _mm("bsf,fd->bsd", o, w["wo"][l], rnd)
        y = _rms_norm(x, w["mlp_norm"][l], eps)
        g = jax.nn.silu(_mm("bsd,df->bsf", y, w["w1"][l], rnd))
        u = _mm("bsd,df->bsf", y, w["w3"][l], rnd)
        x = x + _mm("bsf,fd->bsd", g * u, w["w2"][l], rnd)
    return _rms_norm(x, w["final_norm"], eps)


def loss(w, rows, sz, rnd):
    """Mean next-token cross-entropy plus z-loss over ``rows`` (B, S+1)."""
    x = hidden(w, rows[:, :-1], sz, rnd)
    logits = _mm("bsd,vd->bsv", x, w["embed"], rnd)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, rows[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - gold + Z_LOSS * lse * lse)


def lr_at(step):
    """Linear warmup, then cosine decay (``step`` counts from 1)."""
    warm = min(step / WARMUP, 1.0)
    prog = min(max((step - WARMUP) / (TOTAL - WARMUP), 0.0), 1.0)
    return LR * warm * 0.5 * (1.0 + math.cos(math.pi * prog))


def norms(tree):
    """L2 norm per leaf, per layer for the leaves stacked by layer."""
    return {k: jnp.sqrt(jnp.sum(x * x, axis=None if k in data.UNSTACKED
                                else tuple(range(1, x.ndim))))
            for k, x in tree.items()}


@lru_cache(maxsize=8)
def _step_fn(frozen, precision):
    sz, rnd = dict(frozen), ROUND[precision]

    @jax.jit
    def step(w, m, v, t, lr, rows):
        val, g = jax.value_and_grad(loss)(w, rows, sz, rnd)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, CLIP / jnp.maximum(
            gnorm, 1e-9)), g)
        m = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
        v = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)
        c1, c2 = 1 - B1 ** t, 1 - B2 ** t

        def upd(x, a, b):
            delta = (a / c1) / (jnp.sqrt(b / c2) + EPS)
            if x.ndim >= 2:                  # decoupled decay, matrices only
                delta = delta + WD * x
            return x - lr * delta
        w = jax.tree.map(upd, w, m, v)
        return w, m, v, val, norms(g)
    return step


@lru_cache(maxsize=8)
def _embed_fn(frozen, precision):
    sz, rnd = dict(frozen), ROUND[precision]

    @jax.jit
    def embed(w, tokens):
        x = hidden(w, tokens, sz, rnd)
        return jnp.mean(_mm("bsd,vd->bsv", x, w["embed"], rnd), axis=1)
    return embed


def run(w0, tokens, sizes, train_steps: int, batch: int,
        precision: str = "f32", used_rows: int = 0) -> dict:
    """The sample's fine-tune and embedding, as ``/count`` and ``/seurat``
    compute them: losses of every step, the first clipped gradient's and
    the three-step change's per-leaf norms, and the mean-pooled logits
    of every row after the last step.  ``tokens`` is (rows, S+1).
    ``used_rows`` > 0 plants a fault: each step trains on that many rows
    of its batch only."""
    frozen = tuple(sorted(sizes.items()))
    step = _step_fn(frozen, precision)
    w = w0
    zeros = jax.tree.map(jnp.zeros_like, w0)
    m, v = zeros, zeros
    losses, grad1, change3 = [], None, None
    for s in range(train_steps):
        lo = (s * batch) % tokens.shape[0]
        w, m, v, val, gn = step(w, m, v, float(s + 1), lr_at(s + 1),
                                tokens[lo:lo + (used_rows or batch)])
        losses.append(val)
        if s == 0:
            grad1 = gn
        if s == 2:
            change3 = norms(jax.tree.map(jnp.subtract, w, w0))
    embed = _embed_fn(frozen, precision)
    embs = jnp.concatenate([embed(w, tokens[i:i + batch, :-1])
                            for i in range(0, tokens.shape[0], batch)])
    out = {"losses": jnp.stack(losses), "grad_norms_1": grad1,
           "change_norms_3": change3, "embeddings": embs}
    return jax.tree.map(lambda x: np.asarray(x, np.float64), out)
