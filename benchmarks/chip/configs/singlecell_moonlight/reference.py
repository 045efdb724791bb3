"""Plain reference of the singlecell-moonlight step programs.

Straight ``jax.numpy`` in float32 with every matmul at ``HIGHEST``
precision, written from the model's description and importing nothing of
the program.  Per layer, with the residual stream x and every RMSNorm
weight applied as ``1 + w``:

- latent attention: u = norm(x); q = u W_q split per head into a 128-wide
  part and a 64-wide rotary part; [c, k_rope] = u W_kva; c = norm(c);
  [k_nope, v] per head = c W_kvb; rotary embedding (rotated halves,
  theta from the file) on q's rotary part and on k_rope, which all heads
  share; scores (q_nope . k_nope + q_rope . k_rope) / sqrt(192), causal
  softmax; x += concat_h(p v_h) W_o;
- the leading dense layers: x += SwiGLU_11264(norm(x));
- the MoE layers: u = norm(x); s = sigmoid(u W_r) over the router's 64
  experts; the top 6 of s + b are selected, b the fixed selection bias;
  gates g = 2.446 s_i / sum over the six of s_j; every held expert is
  computed for every token, and a token's routed output is the sum of its
  selected held experts' outputs times their gates; x += routed + the
  shared SwiGLU of width 2 * 1408; the layer's sequence-wise balance loss
  alpha * sum_i f_i P_i over all 64 experts (DeepSeek-V3);
- a final norm and the untied head over the vocabulary slice.

The loss is the mean next-token cross-entropy plus a 1e-4 z-loss on the
log-partition plus the MoE layers' balance losses, trained by the AdamW of
``singlecell_minicpm/reference.py``.  The gradient of a batch is the mean
of its rows' gradients, summed in float32 one row at a time, so that the
whole batch need not fit beside the program's state.

``precision="fp8"`` is the control, as in ``singlecell_minicpm``.  The
``knobs`` plant the faults a held-share layer can have: gates taken from
s + b (``gate_bias`` 1), no top-k normalisation (``normalize`` 0), the
scale missing (``scale`` 1), the shared expert left out (``shared`` 0), a
capacity per held expert and row that drops the assignments past it
(``capacity`` > 0).
"""
from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.configs.singlecell_minicpm.reference import (
    B1, B2, CLIP, EPS, ROUND, WD, Z_LOSS, _mm, _rms_norm, _rope, lr_at)
from benchmarks.chip.configs.singlecell_moonlight import data

SOUND = {"gate_bias": 0.0, "normalize": 1.0, "scale": None, "shared": 1.0,
         "capacity": 0.0}


def knobs_of(sz: dict, **fault) -> dict:
    """The knobs as float32 scalars: the sound model's, changed by
    ``fault``."""
    k = dict(SOUND, scale=sz["routed_scaling_factor"])
    k.update(fault)
    return {n: jnp.float32(v) for n, v in k.items()}


def _swiglu(u, w1, w3, w2, rnd):
    g = jax.nn.silu(_mm("bsd,df->bsf", u, w1, rnd))
    return _mm("bsf,fd->bsd", g * _mm("bsd,df->bsf", u, w3, rnd), w2, rnd)


def mla(x, w, l, sz, rnd):
    """Latent attention of layer ``l``: the update to x."""
    b, s, _ = x.shape
    H, r = sz["num_attention_heads"], sz["kv_lora_rank"]
    dn, dr = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"]
    dv, eps, theta = sz["v_head_dim"], sz["rms_norm_eps"], sz["rope_theta"]
    u = _rms_norm(x, w["attn_norm"][l], eps)
    q = _mm("bsd,df->bsf", u, w["wq"][l], rnd).reshape(b, s, H, dn + dr)
    ckr = _mm("bsd,df->bsf", u, w["wkva"][l], rnd)
    c = _rms_norm(ckr[..., :r], w["kv_norm"][l], eps)
    k_rope = _rope(ckr[..., None, r:], theta)[:, :, 0]
    kv = _mm("bsr,rf->bsf", c, w["wkvb"][l], rnd).reshape(b, s, H, dn + dv)
    q_rope = _rope(q[..., dn:], theta)
    sc = (_mm("bqhd,bkhd->bhqk", q[..., :dn], kv[..., :dn], rnd)
          + _mm("bqhd,bkd->bhqk", q_rope, k_rope, rnd)) / math.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", p, kv[..., dn:], rnd).reshape(b, s, H * dv)
    return _mm("bsf,fd->bsd", o, w["wo"][l], rnd)


def moe(x, w, m, sz, rnd, knobs):
    """MoE layer ``m`` (counted after the dense ones) on the held experts.
    Returns (update, balance loss, selected experts (b, s, k), assignments
    dropped)."""
    b, s, _ = x.shape
    E, k = sz["router_experts"], sz["num_experts_per_tok"]
    Hn, off = sz["n_routed_experts"], sz["expert_offset"]
    u = _rms_norm(x, w["moe_norm"][m], sz["rms_norm_eps"])
    score = jax.nn.sigmoid(_mm("bsd,de->bse", u, w["router"][m], rnd))
    bias = w["bias"][m]
    _, idx = jax.lax.top_k(score + jax.lax.stop_gradient(bias), k)
    sel = jnp.take_along_axis(score + knobs["gate_bias"] * bias, idx, -1)
    n = knobs["normalize"]
    gate = knobs["scale"] * sel / (n * jnp.sum(sel, -1, keepdims=True) + 1 - n)
    mine = idx[..., None] == off + jnp.arange(Hn)              # (b,s,k,Hn)
    chosen = jnp.any(mine, axis=2)                             # (b,s,Hn)
    rank = jnp.cumsum(chosen, axis=1)
    keep = (knobs["capacity"] <= 0) | (rank <= knobs["capacity"])
    dropped = jnp.sum(chosen & ~keep)
    weight = jnp.sum(jnp.where(mine, gate[..., None], 0.0), 2) * keep
    h = jax.nn.silu(_mm("bsd,edf->bsef", u, w["w1"][m], rnd)) * _mm(
        "bsd,edf->bsef", u, w["w3"][m], rnd)
    y = _mm("bsef,efd->bsed", h, w["w2"][m], rnd)
    routed = jnp.sum(weight[..., None] * y, axis=2)
    shared = _swiglu(u, w["shared_w1"][m], w["shared_w3"][m],
                     w["shared_w2"][m], rnd)
    P = jnp.mean(score / jnp.sum(score, -1, keepdims=True), axis=1)
    f = jnp.sum(jax.nn.one_hot(idx, E), axis=(1, 2)) * E / (k * s)
    aux = sz["balance_loss_alpha"] * jnp.mean(jnp.sum(f * P, -1))
    return routed + knobs["shared"] * shared, aux, idx, dropped


def hidden(w, tokens, sz, rnd, knobs):
    """Final normed hidden states (b, s, d), the summed balance loss, the
    selected experts (MoE layers, b, s, k) and the assignments dropped."""
    K = sz["first_k_dense_replace"]
    x = w["embed"][tokens]
    aux, sels, dropped = 0.0, [], 0
    for l in range(sz["num_hidden_layers"]):
        x = x + mla(x, w, l, sz, rnd)
        if l < K:
            u = _rms_norm(x, w["dense_norm"][l], sz["rms_norm_eps"])
            x = x + _swiglu(u, w["dense_w1"][l], w["dense_w3"][l],
                            w["dense_w2"][l], rnd)
        else:
            y, a, sel, d = moe(x, w, l - K, sz, rnd, knobs)
            x, aux, dropped = x + y, aux + a, dropped + d
            sels.append(sel)
    x = _rms_norm(x, w["final_norm"], sz["rms_norm_eps"])
    return x, aux, jnp.stack(sels), dropped


def loss(w, rows, sz, rnd, knobs):
    """Mean next-token cross-entropy plus z-loss plus the balance losses
    over ``rows`` (b, S+1); aux: the selection and the drops."""
    x, aux, sel, dropped = hidden(w, rows[:, :-1], sz, rnd, knobs)
    logits = _mm("bsd,dv->bsv", x, w["head"], rnd)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, rows[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - gold + Z_LOSS * lse * lse) + aux, (sel, dropped)


def norms(tree):
    """L2 norm per leaf, per layer for the leaves stacked by layer."""
    return {k: jnp.sqrt(jnp.sum(x * x, axis=None if k in data.UNSTACKED
                                else tuple(range(1, x.ndim))))
            for k, x in tree.items()}


@lru_cache(maxsize=8)
def _step_fn(frozen, precision):
    sz, rnd = dict(frozen), ROUND[precision]

    def step(w, m, v, t, lr, rows, knobs):
        def row(acc, r):
            (val, (sel, drop)), g = jax.value_and_grad(loss, has_aux=True)(
                w, r[None], sz, rnd, knobs)
            acc = (acc[0] + val, jax.tree.map(jnp.add, acc[1], g),
                   acc[2] + drop)
            return acc, sel[:, 0]
        zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, w), jnp.int32(0))
        (val, g, dropped), sel = jax.lax.scan(row, zero, rows)
        n = rows.shape[0]
        val, g = val / n, jax.tree.map(lambda x: x / n, g)
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
        return w, m, v, val, norms(g), sel.swapaxes(0, 1), dropped
    return jax.jit(step, donate_argnums=(0, 1, 2))


@jax.jit
def _change_norms(w, w0):
    return norms(jax.tree.map(jnp.subtract, w, w0))


@lru_cache(maxsize=8)
def _embed_fn(frozen, precision):
    sz, rnd = dict(frozen), ROUND[precision]

    @jax.jit
    def embed(w, tokens, knobs):
        x = hidden(w, tokens, sz, rnd, knobs)[0]
        return jnp.mean(_mm("bsd,dv->bsv", x, w["head"], rnd), axis=1)
    return embed


def run(make_weights, tokens, sizes, train_steps: int, batch: int,
        precision: str = "f32", **fault) -> dict:
    """The sample's fine-tune and embedding, as ``/count`` and ``/seurat``
    compute them: losses of every step, the first clipped gradient's and
    the three-step change's per-leaf norms, the experts each token of the
    first batch selected at step 1, the assignments dropped over every
    step, and the mean-pooled logits of every row after the last step.
    ``make_weights()`` gives the starting weights, made again for the
    change after three steps (the state is updated in place, so that the
    starting weights need not be held beside it); ``tokens`` is (rows,
    S+1); ``fault`` sets knobs (:data:`SOUND`)."""
    frozen = tuple(sorted(sizes.items()))
    step = _step_fn(frozen, precision)
    knobs = knobs_of(sizes, **fault)
    w = make_weights()
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad1, change3, routing1, dropped = [], None, None, None, 0
    for s in range(train_steps):
        lo = (s * batch) % tokens.shape[0]
        w, m, v, val, gn, sel, drop = step(w, m, v, float(s + 1),
                                           lr_at(s + 1),
                                           tokens[lo:lo + batch], knobs)
        losses.append(val)
        dropped = dropped + drop
        if s == 0:
            grad1, routing1 = gn, sel
        if s == 2:
            change3 = _change_norms(w, make_weights())
    del m, v
    embed = _embed_fn(frozen, precision)
    embs = jnp.concatenate([embed(w, tokens[i:i + batch, :-1], knobs)
                            for i in range(0, tokens.shape[0], batch)])
    out = {"losses": jnp.stack(losses), "grad_norms_1": grad1,
           "change_norms_3": change3, "embeddings": embs}
    out = jax.tree.map(lambda x: np.asarray(x, np.float64), out)
    out["routing_1"] = np.asarray(routing1)
    out["dropped"] = int(dropped)
    return out
