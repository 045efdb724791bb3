"""Mixture-of-Experts blocks (Mixtral / Granite-MoE / DeepSeek-V3 families).

Three dispatch strategies:

* ``einsum``  — T5X/Switch-style capacity-bucketed one-hot dispatch.  This is
  the *baseline*: robust, compiles everywhere, but spends extra HLO FLOPs on
  the dispatch/combine einsums (visible in the roofline MODEL/HLO ratio).
* ``gather``  — capacity-indexed gather/scatter dispatch: only the active
  expert matmuls cost FLOPs.  This is the beyond-baseline path whose TPU twin
  is the ``moe_gmm`` Pallas grouped-matmul kernel.
* held share (``moe_held_block``) — the DeepSeekMoE layer of configs with
  ``experts_held`` > 0: sigmoid router scores over all ``n_experts`` with a
  selection bias, gates normalised over the top-k and scaled, a shared
  expert, and a dropless grouped matmul (``jax.lax.ragged_dot``) over only
  the experts this chip holds, one sequence at a time.  It is one chip's
  share of an expert-parallel layer, run without its exchange.

The first two apply softmax top-k routing with a Switch load-balancing loss
and drop assignments past a capacity.  Their experts are tensor-parallel on
the mesh "model" axis (d_ff sliced), tokens stay data-parallel, so neither
needs an all-to-all; the held share is where one would go (PERF.md, Open
questions).

The dispatch/combine one-hots are never materialised at rank 5: they are
expressed as iota-compare multiply-reduces so XLA loop-fuses them into
(G, g, E, C) outputs directly.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import act_fn, gated_mlp, rms_norm


def init_moe_ffn_axes():
    """Logical axes for the (E, d, f)/(E, f, d) expert tensors."""
    return {"w1": ("experts", "embed", "mlp"),
            "w3": ("experts", "embed", "mlp"),
            "w2": ("experts", "mlp", "embed")}


def router_topk(x, wr, k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Token->expert routing. Returns (weights (T,k), idx (T,k), aux_loss)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        wr.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss.
    E = wr.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce)
    return w, idx, aux


def _group_size(T: int, k: int, cf: float) -> int:
    """Dispatch group size: keep the (g, E, C) tensors ~O(64M) elements."""
    g = 512
    while g * 2 <= T and (2 * g) * (2 * g) * k * cf <= 2 ** 26:
        g *= 2
    return min(g, T)


def _capacity(tokens: int, n_experts: int, top_k: int, cf: float) -> int:
    c = int(tokens * top_k * cf / n_experts)
    return max(8, (c + 7) // 8 * 8)


def _expert_ffn(xe, p, act: str):
    """xe: (E, C, d) -> (E, C, d) through per-expert gated MLP."""
    w1, w2, w3 = (p["w1"].astype(xe.dtype), p["w2"].astype(xe.dtype),
                  p["w3"].astype(xe.dtype))
    h = act_fn(act)(jnp.einsum("ecd,edf->ecf", xe, w1))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


# ---------------------------------------------------------------------------
# einsum (one-hot) dispatch — baseline
# ---------------------------------------------------------------------------

def moe_einsum(x, p, cfg):
    """x: (T, d) flat tokens. Returns (T, d), aux_loss."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    g = _group_size(T, k, cfg.capacity_factor)
    G = T // g
    w, idx, aux = router_topk(x, p["router"], k)
    C = _capacity(g, E, k, cfg.capacity_factor)

    xg = x.reshape(G, g, d)
    wg = w.reshape(G, g, k)                                  # fp32
    ig = idx.reshape(G, g, k)

    onehot = jax.nn.one_hot(ig, E, dtype=jnp.float32)        # (G, g, k, E)
    # slot of each (token, k) inside its expert's capacity bucket,
    # priority token-major then slot-major (cumsum over flattened g*k).
    pos = jnp.cumsum(onehot.reshape(G, g * k, E), axis=1).reshape(
        G, g, k, E) * onehot - 1.0
    keep = (pos >= 0.0) & (pos < C)
    c_iota = jnp.arange(C, dtype=jnp.float32)
    # (G,g,k,E,C) exists only inside the loop fusion of the k-reduction.
    sel = jnp.where(keep[..., None], (pos[..., None] == c_iota), False)
    dispatch = jnp.sum(sel, axis=2, dtype=jnp.float32)       # (G, g, E, C)
    combine = jnp.sum(wg[..., None, None] * sel, axis=2)     # (G, g, E, C)

    xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xg)
    ye = _apply_experts_grouped(xe, p, cfg)
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye)
    return y.reshape(T, d), aux


def _apply_experts_grouped(xe, p, cfg):
    """xe: (G, E, C, d) -> (G, E, C, d)."""
    G, E, C, d = xe.shape
    out = _expert_ffn(
        xe.transpose(1, 0, 2, 3).reshape(E, G * C, d), p, cfg.act)
    return out.reshape(E, G, C, d).transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# gather dispatch — optimized path (Pallas moe_gmm twin on TPU)
# ---------------------------------------------------------------------------

def moe_gather(x, p, cfg, kernel_mode: str = "reference"):
    """Capacity-indexed gather dispatch: active-FLOPs only."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    w, idx, aux = router_topk(x, p["router"], k)
    C = _capacity(T, E, k, cfg.capacity_factor)

    flat_e = idx.reshape(-1)                                 # (T*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    slot = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    keep = slot < C
    tok_id = jnp.repeat(jnp.arange(T), k)
    # scatter token ids into (E, C) buckets; capacity overflow drops.
    bucket = jnp.full((E, C), T, dtype=jnp.int32)            # T == pad row
    bucket = bucket.at[flat_e, jnp.where(keep, slot, C)].set(
        tok_id, mode="drop")
    xpad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = xpad[bucket]                                        # (E, C, d)
    if kernel_mode == "pallas":
        from repro.kernels.moe_gmm import ops as gmm_ops
        ye = gmm_ops.expert_ffn(xe, p, cfg.act)
    else:
        ye = _expert_ffn(xe, p, cfg.act)
    # combine: gather outputs back per (token, k) slot, weighted scatter-add.
    wk = w.reshape(-1).astype(x.dtype)
    src = ye[flat_e, jnp.clip(slot, 0, C - 1)] * jnp.where(
        keep, wk, 0.0)[:, None]
    y = jnp.zeros((T + 1, d), x.dtype)
    y = y.at[jnp.where(keep, tok_id, T)].add(src, mode="drop")
    return y[:T], aux


def moe_block(x, p, cfg, *, dispatch: str = "einsum",
              kernel_mode: str = "reference"):
    """Pre-norm MoE residual block. x: (B, S, d)."""
    B, S, d = x.shape
    h = rms_norm(x, p["ln"], cfg.norm_eps).reshape(B * S, d)
    if dispatch == "gather":
        y, aux = moe_gather(h, p, cfg, kernel_mode)
    else:
        y, aux = moe_einsum(h, p, cfg)
    return x + y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# held share — DeepSeekMoE, dropless, this chip's experts only
# ---------------------------------------------------------------------------

def held_rows(tokens: int, cfg) -> int:
    """Rows of the grouped matmul: every (token, expert) assignment that
    can fall on a held expert, so none is ever dropped (the top-k experts
    of a token are distinct)."""
    return tokens * min(cfg.top_k, cfg.experts_held)


def route_sigmoid(h, p, cfg):
    """Scores, selection and gates of the DeepSeek-V3 router.

    h: (T, d).  Returns (scores (T, E) f32, idx (T, k), gates (T, k) f32):
    the top-k of ``scores + bias`` are selected, and the gates are taken
    from the scores alone, normalised over the k and scaled.  The bias only
    steers selection and takes no gradient."""
    # float32 products: the TPU's default precision multiplies f32 in one
    # bf16 pass, and selection turns on near-ties of the 64 scores
    logits = jnp.einsum("td,de->te", h.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + lax.stop_gradient(p["bias"]), cfg.top_k)
    sel = jnp.take_along_axis(scores, idx, axis=-1)
    sel = sel / jnp.sum(sel, axis=-1, keepdims=True)
    return scores, idx, cfg.routed_scale * sel


def sequence_balance_loss(scores, idx, B: int, S: int, cfg):
    """DeepSeek-V3's sequence-wise balance loss over all E experts,
    weighted by ``balance_alpha`` and averaged over the B sequences:
    alpha * sum_i f_i P_i with f_i = E / (k S) * #(tokens that select i)
    and P_i the mean over tokens of the score normalised over E."""
    E, k = cfg.n_experts, cfg.top_k
    norm = scores / jnp.sum(scores, axis=-1, keepdims=True)
    P = jnp.mean(norm.reshape(B, S, E), axis=1)
    chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1)
    f = jnp.sum(chosen.reshape(B, S, E), axis=1) * (E / (k * S))
    return cfg.balance_alpha * jnp.mean(jnp.sum(f * P, axis=-1))


def _held_share(h, idx, gates, w, cfg):
    """One sequence's tokens through the held experts, dropless.

    h: (t, d); idx, gates: (t, k); w: the held experts' w1, w3, w2.
    Returns (y (t, d) f32, load (experts_held,) int32, dropped int32)."""
    t, d = h.shape
    k, Hn = cfg.top_k, cfg.experts_held
    with jax.named_scope("moe_dispatch"):
        local = idx.reshape(-1) - cfg.expert_offset
        held = (local >= 0) & (local < Hn)
        slot = jnp.where(held, local, Hn)            # Hn: not held here
        order = jnp.argsort(slot, stable=True)       # held first, by expert
        M = held_rows(t, cfg)
        rows = order[:M]
        load = jnp.sum(jax.nn.one_hot(slot, Hn + 1, dtype=jnp.int32),
                       axis=0)[:Hn]
        n_held = jnp.sum(load)
        ends = jnp.minimum(jnp.cumsum(load), M)
        sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
        valid = jnp.arange(M) < jnp.minimum(n_held, M)
        tok = rows // k
        # the TPU's ragged dot writes no row past its groups, forward or
        # transposed: each operand and result is kept to the valid rows,
        # so that what those rows hold reaches no output or gradient
        xs = jnp.where(valid[:, None], h[tok], 0)
        gw = jnp.where(valid, gates.reshape(-1)[rows], 0.0)
    with jax.named_scope("moe_experts"):
        def grouped(x, wi):
            y = lax.ragged_dot(x, wi.astype(h.dtype), sizes)
            return jnp.where(valid[:, None], y, 0)
        a, b = grouped(xs, w["w1"]), grouped(xs, w["w3"])
        ys = grouped(act_fn(cfg.act)(a) * b, w["w2"])
    with jax.named_scope("moe_combine"):
        y = jnp.zeros((t, d), jnp.float32).at[tok].add(
            ys.astype(jnp.float32) * gw[:, None])
    return y, load, jnp.maximum(n_held - M, 0).astype(jnp.int32)


def moe_held_block(x, p, cfg):
    """Pre-norm DeepSeekMoE residual block on the held experts. x: (B,S,d).

    The held experts take one sequence at a time, each under its own
    rematerialisation, so that the dropless buffer (``held_rows`` of a
    sequence) and its gradient are one sequence's, not the batch's.

    Returns (x, balance_loss, stats): ``stats`` holds the assignments
    computed per held expert (``expert_load``, (experts_held,)), the held
    assignments not computed (``dropped``, 0 by construction) and each
    token's selected experts (``routing``, (B, S, k))."""
    B, S, d = x.shape
    T, k = B * S, cfg.top_k
    h = rms_norm(x, p["ln"], cfg.norm_eps).reshape(T, d)
    with jax.named_scope("moe_route"):
        scores, idx, gates = route_sigmoid(h, p, cfg)
        aux = sequence_balance_loss(scores, idx, B, S, cfg)
    share = jax.checkpoint(lambda w, blk: _held_share(*blk, w, cfg))
    experts = {n: p[n] for n in ("w1", "w3", "w2")}
    _, (y, load, dropped) = lax.scan(
        lambda c, blk: (c, share(experts, blk)), None,
        (h.reshape(B, S, d), idx.reshape(B, S, k), gates.reshape(B, S, k)))
    y = y.reshape(T, d)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            y = y + gated_mlp(h, p["shared"], cfg.act).astype(jnp.float32)
    stats = {"expert_load": jnp.sum(load, axis=0),
             "dropped": jnp.sum(dropped),
             "routing": idx.reshape(B, S, k).astype(jnp.int32)}
    return x + y.astype(x.dtype).reshape(B, S, d), aux, stats
