"""Unified model stack for every assigned architecture family.

A model is a ``block_pattern`` (period of block kinds) repeated
``n_scan_blocks`` times under ``lax.scan`` — keeping the HLO size constant in
depth, which is what makes 62-layer/33B dry-run compiles tractable — plus
``n_tail_layers`` unrolled leftovers.

Supported block kinds (see ``repro.models.config``): ATTN / SWA / LOCAL
(GQA self-attention), MLA (latent attention), CROSS (gated cross-attention
to modality memory), MLSTM / SLSTM (xLSTM), RGLRU (Griffin).  Channel mixer
per layer: gated MLP, MoE, or none (d_ff == 0); sLSTM carries its own
post-MLP.  ``first_k_dense`` leading layers (unrolled, before the scanned
periods) take a gated MLP of width d_ff where the rest take MoE.

Public API:
  init_params(rng, cfg)            -> (params, logical_axes)
  forward_train(params, cfg, batch, ...) -> (loss, metrics)
  prefill(params, cfg, batch, ...) -> (last_logits, cache)
  decode_step(params, cfg, tokens, pos, cache, ...) -> (logits, cache)
  init_cache(cfg, B, ctx_len)      -> cache pytree (zeros)
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import xlstm as xl
from repro.models import rglru as rg
from repro.models.config import (ATTN, SWA, LOCAL, CROSS, MLSTM, SLSTM, RGLRU,
                                 MLA, ArchConfig)
from repro.models.layers import (attention, dense, gated_mlp, rms_norm, rope,
                                 decode_attention_block)
from repro.models.moe import moe_block, moe_held_block, init_moe_ffn_axes

XENT_CHUNK = 512  # sequence chunk for the fused logits+loss scan


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _lin(key, m, n, scale=1.0):
    return jax.random.normal(key, (m, n), jnp.float32) * (scale / math.sqrt(m))


def _init_attn(rng, cfg, cross: bool = False):
    d, H, KH, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "ln": jnp.zeros((d,), jnp.float32),
        "wq": _lin(ks[0], d, H * Dh),
        "wk": _lin(ks[1], d, KH * Dh),
        "wv": _lin(ks[2], d, KH * Dh),
        "wo": _lin(ks[3], H * Dh, d, scale=1.0 / math.sqrt(2 * cfg.n_layers)),
    }
    axes = {
        "ln": ("embed",),
        "wq": ("embed", "heads"), "wk": ("embed", "kv"), "wv": ("embed", "kv"),
        "wo": ("heads", "embed"),
    }
    if cross:
        p["gate"] = jnp.zeros((), jnp.float32)
        axes["gate"] = ()
    return p, axes


def _init_mlp(rng, cfg):
    from repro.models.layers import is_gated_act
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 3)
    p = {
        "ln": jnp.zeros((d,), jnp.float32),
        "w1": _lin(ks[0], d, f),
        "w2": _lin(ks[2], f, d, scale=1.0 / math.sqrt(2 * cfg.n_layers)),
    }
    axes = {"ln": ("embed",), "w1": ("embed", "mlp"), "w2": ("mlp", "embed")}
    if is_gated_act(cfg.act):
        p["w3"] = _lin(ks[1], d, f)
        axes["w3"] = ("embed", "mlp")
    return p, axes


def _init_moe(rng, cfg):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(rng, 4)
    p = {
        "ln": jnp.zeros((d,), jnp.float32),
        "router": _lin(ks[0], d, E),
        "w1": jax.random.normal(ks[1], (E, d, f), jnp.float32) / math.sqrt(d),
        "w3": jax.random.normal(ks[2], (E, d, f), jnp.float32) / math.sqrt(d),
        "w2": jax.random.normal(ks[3], (E, f, d), jnp.float32) / (
            math.sqrt(f) * math.sqrt(2 * cfg.n_layers)),
    }
    axes = {"ln": ("embed",), "router": ("embed", None),
            **init_moe_ffn_axes()}
    return p, axes


def _init_moe_held(rng, cfg):
    """The held share of a DeepSeekMoE layer: a router over all n_experts
    with its selection bias, the experts_held routed experts, and the
    shared experts as one gated MLP."""
    d, f, E, Hn = cfg.d_model, cfg.expert_d_ff, cfg.n_experts, cfg.experts_held
    ks = jax.random.split(rng, 5)
    p = {
        "ln": jnp.zeros((d,), jnp.float32),
        "router": _lin(ks[0], d, E),
        "bias": jnp.zeros((E,), jnp.float32),
        "w1": jax.random.normal(ks[1], (Hn, d, f), jnp.float32) / math.sqrt(d),
        "w3": jax.random.normal(ks[2], (Hn, d, f), jnp.float32) / math.sqrt(d),
        "w2": jax.random.normal(ks[3], (Hn, f, d), jnp.float32) / (
            math.sqrt(f) * math.sqrt(2 * cfg.n_layers)),
    }
    axes = {"ln": ("embed",), "router": ("embed", None), "bias": (None,),
            **init_moe_ffn_axes()}
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        k1, k3, k2 = jax.random.split(ks[4], 3)
        p["shared"] = {"w1": _lin(k1, d, fs), "w3": _lin(k3, d, fs),
                       "w2": _lin(k2, fs, d,
                                  scale=1.0 / math.sqrt(2 * cfg.n_layers))}
        axes["shared"] = {"w1": ("embed", "mlp"), "w3": ("embed", "mlp"),
                          "w2": ("mlp", "embed")}
    return p, axes


def _init_mla(rng, cfg):
    """Latent attention with no query compression: W_q (d, H*(nope+rope)),
    W_kva (d, rank+rope) with the latent's norm, W_kvb (rank, H*(nope+v)),
    W_o (H*v, d)."""
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "ln": jnp.zeros((d,), jnp.float32),
        "wq": _lin(ks[0], d, H * (dn + dr)),
        "wkva": _lin(ks[1], d, r + dr),
        "kv_ln": jnp.zeros((r,), jnp.float32),
        "wkvb": _lin(ks[2], r, H * (dn + dv)),
        "wo": _lin(ks[3], H * dv, d, scale=1.0 / math.sqrt(2 * cfg.n_layers)),
    }
    axes = {"ln": ("embed",), "wq": ("embed", "heads"), "wkva": ("embed", None),
            "kv_ln": (None,), "wkvb": (None, "heads"), "wo": ("heads", "embed")}
    return p, axes


_MIX_INIT = {
    ATTN: _init_attn, SWA: _init_attn, LOCAL: _init_attn,
    CROSS: partial(_init_attn, cross=True),
    MLSTM: xl.init_mlstm, SLSTM: xl.init_slstm, RGLRU: rg.init_rglru,
    MLA: _init_mla,
}


def _kind_has_ffn(kind: str, cfg: ArchConfig) -> bool:
    if kind in (MLSTM, SLSTM):
        return False                       # internal / none by design
    return cfg.is_moe or cfg.d_ff > 0


def _init_layer(rng, cfg, kind: str, dense_ffn: bool = False):
    k1, k2 = jax.random.split(rng)
    mix, mix_axes = _MIX_INIT[kind](k1, cfg)
    layer = {"mix": mix}
    axes = {"mix": mix_axes}
    if _kind_has_ffn(kind, cfg):
        if dense_ffn or not cfg.is_moe:
            layer["ffn"], axes["ffn"] = _init_mlp(k2, cfg)
        elif cfg.experts_held:
            layer["ffn"], axes["ffn"] = _init_moe_held(k2, cfg)
        else:
            layer["ffn"], axes["ffn"] = _init_moe(k2, cfg)
    return layer, axes


def _init_period(rng, cfg):
    """One pattern period: dict pos -> layer params."""
    keys = jax.random.split(rng, len(cfg.block_pattern))
    out, axes = {}, {}
    for i, kind in enumerate(cfg.block_pattern):
        out[f"l{i}"], axes[f"l{i}"] = _init_layer(keys[i], cfg, kind)
    return out, axes


def init_params(rng, cfg: ArchConfig):
    """Returns (params, logical_axes) — axes mirror params leaf-for-leaf."""
    ks = jax.random.split(rng, 6)
    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}

    if cfg.modality == "audio":
        params["frontend"] = _lin(ks[0], cfg.frontend_dim, cfg.d_model)
        axes["frontend"] = (None, "embed")
    else:
        # std d^-1/2: lookups are rescaled by sqrt(d) when tied, and the
        # tied head then produces O(1) logits (MiniCPM-style mup scaling)
        params["embed"] = jax.random.normal(
            ks[0], (cfg.vocab_size, cfg.d_model),
            jnp.float32) * (cfg.d_model ** -0.5)
        axes["embed"] = ("vocab", "embed")
    if cfg.modality == "vision":
        params["vis_proj"] = _lin(ks[1], cfg.frontend_dim, cfg.d_model)
        axes["vis_proj"] = (None, "embed")

    # leading dense layers, unrolled
    if cfg.first_k_dense:
        lead_keys = jax.random.split(ks[5], cfg.first_k_dense)
        params["lead"], axes["lead"] = [], []
        for k in lead_keys:
            lp, la = _init_layer(k, cfg, cfg.block_pattern[0], dense_ffn=True)
            params["lead"].append(lp)
            axes["lead"].append(la)

    # scanned super-blocks: stacked (n_scan, ...) leaves via vmap'd init
    n_scan = cfg.n_scan_blocks
    if n_scan:
        period_keys = jax.random.split(ks[2], n_scan)
        params["blocks"] = jax.vmap(
            lambda k: _init_period(k, cfg)[0])(period_keys)
        _, period_axes = _init_period(period_keys[0], cfg)
        axes["blocks"] = jax.tree.map(
            lambda t: ("layers",) + t, period_axes,
            is_leaf=lambda t: isinstance(t, tuple))

    # tail layers (pattern prefix), unrolled
    if cfg.n_tail_layers:
        tail_keys = jax.random.split(ks[3], cfg.n_tail_layers)
        params["tail"], axes["tail"] = [], []
        for i in range(cfg.n_tail_layers):
            kind = cfg.block_pattern[i % cfg.pattern_period]
            lp, la = _init_layer(tail_keys[i], cfg, kind)
            params["tail"].append(lp)
            axes["tail"].append(la)

    params["final_ln"] = jnp.zeros((cfg.d_model,), jnp.float32)
    axes["final_ln"] = ("embed",)
    if not cfg.tie_embeddings:
        params["head"] = _lin(ks[4], cfg.d_model, cfg.vocab_size)
        axes["head"] = ("embed", "vocab")
    return params, axes


# ---------------------------------------------------------------------------
# Block application (full sequence)
# ---------------------------------------------------------------------------

def _window_for(kind: str, cfg: ArchConfig) -> int:
    if kind in (SWA, LOCAL):
        return cfg.window
    return 0


def _apply_mix(kind, x, p, cfg, ctx, collect: bool):
    """Returns (x, kv_or_state_or_None)."""
    if kind == MLA:
        return _mla(x, p, cfg, positions=ctx["positions"])
    if kind in (ATTN, SWA, LOCAL):
        return _self_attn(x, p, cfg, positions=ctx["positions"],
                          causal=not cfg.encoder_only,
                          window=_window_for(kind, cfg),
                          kernel_mode=ctx["kernel_mode"], ctx=ctx)
    if kind == CROSS:
        return _cross_attn(x, p, cfg, memory=ctx["memory"])
    if kind == MLSTM:
        out = xl.apply_mlstm(x, p, cfg, kernel_mode=ctx["kernel_mode"],
                             return_state=collect)
    elif kind == SLSTM:
        out = xl.apply_slstm(x, p, cfg, return_state=collect)
    elif kind == RGLRU:
        out = rg.apply_rglru(x, p, cfg, kernel_mode=ctx["kernel_mode"],
                             return_state=collect)
    else:
        raise ValueError(kind)
    return out if collect else (out, None)


def _self_attn(x, p, cfg, *, positions, causal, window, kernel_mode,
               ctx=None):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    B, S, _ = h.shape
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hint = (lambda a, dims: shard_hint(a, ctx, dims)) if ctx else \
        (lambda a, dims: a)
    q = hint(dense(h, p["wq"]).reshape(B, S, H, Dh),
             ("batch", None, "model", None))
    k = hint(dense(h, p["wk"]).reshape(B, S, KH, Dh),
             ("batch", None, "model", None))
    v = hint(dense(h, p["wv"]).reshape(B, S, KH, Dh),
             ("batch", None, "model", None))
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if kernel_mode == "pallas" and causal:
        from repro.kernels.flash_attention import ops as fa
        o = fa.flash_attention(q, k, v, causal=True, window=window)
    else:
        o = attention(q, k, v, causal=causal, window=window)
    o = hint(o, ("batch", None, "model", None))
    return x + dense(o.reshape(B, S, H * Dh), p["wo"]), (k, v)


def _mla_qkv(h, p, cfg, positions):
    """Per-head q, k (nope then rope columns) and v of latent attention.
    The rope part of k is one (B, S, 1, rope) key shared by every head."""
    B, S, _ = h.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = dense(h, p["wq"]).reshape(B, S, H, dn + dr)
    kva = dense(h, p["wkva"])
    c = rms_norm(kva[..., :r], p["kv_ln"], cfg.norm_eps)
    kr = rope(kva[..., None, r:], positions, cfg.rope_theta)
    kv = dense(c, p["wkvb"]).reshape(B, S, H, dn + dv)
    q = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kr, (B, S, H, dr))], -1)
    return q, k, kv[..., dn:]


def _mla(x, p, cfg, *, positions):
    """Pre-norm latent-attention residual block (causal)."""
    with jax.named_scope("mla"):
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        B, S, _ = h.shape
        q, k, v = _mla_qkv(h, p, cfg, positions)
        o = attention(q, k, v, causal=True)
        o = dense(o.reshape(B, S, cfg.n_heads * cfg.v_head_dim), p["wo"])
    return x + o, (k, v)


def _cross_attn(x, p, cfg, *, memory):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    B, S, _ = h.shape
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    P = memory.shape[1]
    q = dense(h, p["wq"]).reshape(B, S, H, Dh)
    k = dense(memory, p["wk"]).reshape(B, P, KH, Dh)
    v = dense(memory, p["wv"]).reshape(B, P, KH, Dh)
    o = attention(q, k, v, causal=False)
    o = dense(o.reshape(B, S, H * Dh), p["wo"])
    return x + jnp.tanh(p["gate"].astype(x.dtype)) * o, (k, v)


def _apply_ffn(x, p, cfg, ctx):
    """Channel mixer. Returns (x, aux_loss, stats): ``stats`` holds the
    held-share layer's counters, and is empty for every other mixer."""
    if "router" not in p:
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        return x + gated_mlp(h, p, cfg.act), jnp.float32(0.0), {}
    if cfg.experts_held:
        return moe_held_block(x, p, cfg)
    x, aux = moe_block(x, p, cfg, dispatch=ctx["moe_dispatch"],
                       kernel_mode=ctx["kernel_mode"])
    return x, aux, {}


def _apply_layer(kind, x, layer, cfg, ctx, collect: bool = False):
    """Returns (x, aux, kv_or_state_or_None, stats)."""
    x, kv = _apply_mix(kind, x, layer["mix"], cfg, ctx, collect)
    aux, stats = jnp.float32(0.0), {}
    if "ffn" in layer:
        x, aux, stats = _apply_ffn(x, layer["ffn"], cfg, ctx)
    return x, aux, kv, stats


def _moe_stats(scanned, tail):
    """The held-share counters of every MoE layer in depth order:
    ``expert_load`` (layers, experts_held), ``routing`` (layers, B, S, k)
    and the total ``dropped``.  ``scanned`` maps a period position to its
    counters stacked over the scan; ``tail`` lists the tail layers'."""
    parts = []
    if scanned:
        pos = sorted(scanned, key=lambda n: int(n[1:]))
        parts.append({k: jnp.stack([scanned[n][k] for n in pos], axis=1)
                      .reshape((-1,) + scanned[pos[0]][k].shape[1:])
                      for k in scanned[pos[0]]})
    parts += [{k: v[None] for k, v in st.items()} for st in tail]
    if not parts:
        return {}
    out = {k: jnp.concatenate([p[k] for p in parts]) for k in parts[0]}
    out["dropped"] = jnp.sum(out["dropped"])
    return out


def _stack_forward(params, cfg, x, ctx, *, collect_kv: bool = False):
    """Runs the leading dense layers, the scanned super-blocks and the
    tail.  Returns (x, aux, (kvs, tail_kvs, lead_kvs), stats)."""
    remat_policy = {
        "full": None,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "none": jax.checkpoint_policies.everything_saveable,
    }[cfg.remat]

    def lead_body(x, layer):
        x, _, kv, _ = _apply_layer(cfg.block_pattern[0], x, layer, cfg, ctx,
                                   collect=collect_kv)
        return x, (kv if collect_kv else None)

    def period_body(carry, blk):
        x, aux = carry
        kvs, stats = {}, {}
        for i, kind in enumerate(cfg.block_pattern):
            x, a, kv, st = _apply_layer(kind, x, blk[f"l{i}"], cfg, ctx,
                                        collect=collect_kv)
            aux = aux + a
            if collect_kv and kv is not None:
                kvs[f"l{i}"] = kv
            if st:
                stats[f"l{i}"] = st
        return (x, aux), (kvs, stats)

    if cfg.remat != "none":
        lead_body = jax.checkpoint(lead_body, policy=remat_policy,
                                   prevent_cse=False)
        period_body = jax.checkpoint(
            period_body, policy=remat_policy,
            prevent_cse=False)

    lead_kvs = []
    for layer in params.get("lead", []):
        x, kv = lead_body(x, layer)
        if collect_kv and kv is not None:
            lead_kvs.append(kv)
    aux = jnp.float32(0.0)
    kvs, scan_stats = None, {}
    if cfg.n_scan_blocks:
        (x, aux), (kvs, scan_stats) = lax.scan(period_body, (x, aux),
                                               params["blocks"])
    tail_kvs, tail_stats = [], []
    for i, layer in enumerate(params.get("tail", [])):
        kind = cfg.block_pattern[i % cfg.pattern_period]
        x, a, kv, st = _apply_layer(kind, x, layer, cfg, ctx,
                                    collect=collect_kv)
        aux = aux + a
        if collect_kv and kv is not None:
            tail_kvs.append(kv)
        if st:
            tail_stats.append(st)
    return (x, aux, (kvs, tail_kvs, lead_kvs),
            _moe_stats(scan_stats, tail_stats))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def _embed(params, cfg, batch):
    """Returns (x: (B,S,d) activations, memory or None)."""
    dt = jnp.dtype(cfg.dtype)
    if cfg.modality == "audio":
        x = jnp.asarray(batch["frames"], dt) @ params["frontend"].astype(dt)
    else:
        x = params["embed"].astype(dt)[batch["tokens"]]
        if cfg.tie_embeddings:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dt)
    memory = None
    if cfg.modality == "vision":
        memory = jnp.asarray(batch["patches"], dt) @ params["vis_proj"].astype(dt)
    return x, memory


def _head_matrix(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["head"]


def softmax_xent_from_hidden(x, head, labels, mask=None, *, chunk=XENT_CHUNK,
                             z_weight: float = 1e-4):
    """Fused per-chunk logits+cross-entropy with remat (never holds (B,S,V)).

    x: (B,S,d) hidden states; head: (d,V) fp32; labels: (B,S) int32.
    Returns (mean_nll + z_loss, sum_correct) — z-loss regularises logsumexp.
    """
    B, S, d = x.shape
    chunk = min(chunk, S)
    n = S // chunk
    rem = S - n * chunk

    def chunk_loss(xc, lc, mc):
        logits = jnp.einsum("bsd,dv->bsv", xc, head.astype(xc.dtype))
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mc
        zl = z_weight * jnp.sum(jnp.square(lse) * mc)
        correct = jnp.sum((jnp.argmax(logits, -1) == lc) * mc)
        return jnp.sum(nll) + zl, jnp.sum(mc), correct

    chunk_loss = jax.checkpoint(chunk_loss)
    mask = jnp.ones((B, S), jnp.float32) if mask is None else mask.astype(jnp.float32)

    def body(c, xs):
        l, m, cor = chunk_loss(*xs)
        return (c[0] + l, c[1] + m, c[2] + cor), None

    xs = (x[:, :n * chunk].reshape(B, n, chunk, d).swapaxes(0, 1),
          labels[:, :n * chunk].reshape(B, n, chunk).swapaxes(0, 1),
          mask[:, :n * chunk].reshape(B, n, chunk).swapaxes(0, 1))
    (tot, cnt, cor), _ = lax.scan(
        body, (jnp.float32(0), jnp.float32(0), jnp.float32(0)), xs)
    if rem:
        l, m, c2 = chunk_loss(x[:, n * chunk:], labels[:, n * chunk:],
                              mask[:, n * chunk:])
        tot, cnt, cor = tot + l, cnt + m, cor + c2
    return tot / jnp.maximum(cnt, 1.0), cor / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def make_ctx(cfg, *, kernel_mode="reference", moe_dispatch="einsum",
             positions=None, memory=None, mesh=None):
    return {"kernel_mode": kernel_mode, "moe_dispatch": moe_dispatch,
            "positions": positions, "memory": memory, "mesh": mesh}


def shard_hint(x, ctx, dims):
    """Explicit activation-sharding constraint (SPMD guardrail).

    ``dims``: one logical name per dim of x — "batch" (DP axes), "model"
    (TP axis), or None.  Without a mesh in ctx this is a no-op, so model
    code stays runnable on a laptop.  Indivisible dims degrade to None via
    safe_spec instead of failing.

    Why it exists: left alone, XLA SPMD mispartitions the blockwise
    attention scan (it gathered the batch and quarter-sharded a
    non-divisible head dim — 16x redundant compute, found in §Perf
    iteration A2); pinning batch/heads here keeps the partitioner honest.
    """
    mesh = ctx.get("mesh") if isinstance(ctx, dict) else None
    if mesh is None:
        return x
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import data_axes, safe_spec
    dp = data_axes(mesh)
    dp_axis = dp if len(dp) > 1 else dp[0]
    want = [dp_axis if d == "batch" else ("model" if d == "model" else None)
            for d in dims]
    spec = safe_spec(x.shape, want, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def forward_train(params, cfg: ArchConfig, batch, *, kernel_mode="reference",
                  moe_dispatch="einsum", aux_weight: float = 0.01,
                  mesh=None):
    """Training forward: next-token LM loss (or masked-prediction for
    encoder-only audio).  batch: tokens/labels (+frames/patches/mask)."""
    x, memory = _embed(params, cfg, batch)
    B, S, _ = x.shape
    ctx = make_ctx(cfg, kernel_mode=kernel_mode, moe_dispatch=moe_dispatch,
                   positions=jnp.arange(S), memory=memory, mesh=mesh)
    x = shard_hint(x, ctx, ("batch", None, None))
    x, aux, _, stats = _stack_forward(params, cfg, x, ctx)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    mask = batch.get("mask")
    loss, acc = softmax_xent_from_hidden(
        x, _head_matrix(params, cfg), batch["labels"], mask)
    if cfg.experts_held:
        # the held-share layers' sequence-wise balance losses, each
        # weighted by balance_alpha already, summed over the layers
        total = loss + aux
    else:
        n_layers_moe = cfg.n_layers if cfg.is_moe else 0
        total = loss + (aux_weight * aux / max(n_layers_moe, 1)
                        if cfg.is_moe else 0.0)
    return total, {"nll": loss, "aux": aux, "acc": acc, **stats}


def forward_logits(params, cfg: ArchConfig, batch, *, kernel_mode="reference",
                   moe_dispatch="einsum", mesh=None):
    """Full-sequence logits (no cache) — used by eval / tests."""
    x, memory = _embed(params, cfg, batch)
    B, S, _ = x.shape
    ctx = make_ctx(cfg, kernel_mode=kernel_mode, moe_dispatch=moe_dispatch,
                   positions=jnp.arange(S), memory=memory, mesh=mesh)
    x = shard_hint(x, ctx, ("batch", None, None))
    x, _, _, _ = _stack_forward(params, cfg, x, ctx)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    head = _head_matrix(params, cfg)
    return jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))


# ---------------------------------------------------------------------------
# Decode path: cache init, prefill, single-token step
# ---------------------------------------------------------------------------

def _cache_len(kind: str, cfg: ArchConfig, ctx_len: int) -> int:
    w = _window_for(kind, cfg)
    return min(ctx_len, w) if w else ctx_len


def _init_layer_state(kind, cfg, B, ctx_len, dt=jnp.bfloat16):
    KH, Dh = cfg.n_kv_heads, cfg.head_dim
    if kind == MLA:
        H = cfg.n_heads
        dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return {"k": jnp.zeros((B, ctx_len, H, dqk), dt),
                "v": jnp.zeros((B, ctx_len, H, cfg.v_head_dim), dt)}
    if kind in (ATTN, SWA, LOCAL):
        L = _cache_len(kind, cfg, ctx_len)
        return {"k": jnp.zeros((B, L, KH, Dh), dt),
                "v": jnp.zeros((B, L, KH, Dh), dt)}
    if kind == CROSS:
        P = cfg.n_patches
        return {"ck": jnp.zeros((B, P, KH, Dh), dt),
                "cv": jnp.zeros((B, P, KH, Dh), dt)}
    if kind == MLSTM:
        return xl.init_state_mlstm(cfg, B)
    if kind == SLSTM:
        return xl.init_state_slstm(cfg, B)
    if kind == RGLRU:
        return rg.init_state_rglru(cfg, B)
    raise ValueError(kind)


def init_cache(cfg: ArchConfig, B: int, ctx_len: int):
    """Zeroed decode cache: {"blocks": {l<i>: (n_scan,...)}, "tail": [...]}."""
    blocks = {}
    for i, kind in enumerate(cfg.block_pattern):
        st = _init_layer_state(kind, cfg, B, ctx_len)
        if cfg.n_scan_blocks:
            blocks[f"l{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (cfg.n_scan_blocks,) + a.shape), st)
    tail = []
    for i in range(cfg.n_tail_layers):
        kind = cfg.block_pattern[i % cfg.pattern_period]
        tail.append(_init_layer_state(kind, cfg, B, ctx_len))
    cache = {"blocks": blocks, "tail": tail}
    if cfg.first_k_dense:
        cache["lead"] = [_init_layer_state(cfg.block_pattern[0], cfg, B,
                                           ctx_len)
                         for _ in range(cfg.first_k_dense)]
    return cache


def _mla_decode(x, p, cfg, cache, pos):
    """One-token latent attention against a cache of per-head k and v."""
    with jax.named_scope("mla"):
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        B = h.shape[0]
        q, k, v = _mla_qkv(h, p, cfg, jnp.full((1,), pos))
        ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, pos, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, pos, 0, 0))
        o = attention(q, ck.astype(q.dtype), cv.astype(q.dtype), causal=False,
                      kv_len=pos + 1)
        o = dense(o.reshape(B, 1, cfg.n_heads * cfg.v_head_dim), p["wo"])
    return x + o, {"k": ck, "v": cv}


def _decode_layer(kind, x, layer, state, cfg, pos, ctx):
    """One-token step for one layer. Returns (x, new_state)."""
    if kind == MLA:
        x, new = _mla_decode(x, layer["mix"], cfg, state, pos)
    elif kind in (ATTN, SWA, LOCAL):
        w = _window_for(kind, cfg)
        x, new = decode_attention_block(x, layer["mix"], cfg, state, pos,
                                        window=w)
    elif kind == CROSS:
        h = rms_norm(x, layer["mix"]["ln"], cfg.norm_eps)
        B = h.shape[0]
        H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = dense(h, layer["mix"]["wq"]).reshape(B, 1, H, Dh)
        o = attention(q, state["ck"].astype(x.dtype),
                      state["cv"].astype(x.dtype), causal=False)
        o = dense(o.reshape(B, 1, H * Dh), layer["mix"]["wo"])
        x = x + jnp.tanh(layer["mix"]["gate"].astype(x.dtype)) * o
        new = state
    elif kind == MLSTM:
        x, new = xl.decode_mlstm(x, layer["mix"], cfg, state)
    elif kind == SLSTM:
        x, new = xl.decode_slstm(x, layer["mix"], cfg, state)
    elif kind == RGLRU:
        x, new = rg.decode_rglru(x, layer["mix"], cfg, state)
    else:
        raise ValueError(kind)
    if "ffn" in layer:
        x, _, _ = _apply_ffn(x, layer["ffn"], cfg, ctx)
    return x, new


def decode_step(params, cfg: ArchConfig, tokens, pos, cache, *,
                memory=None, kernel_mode="reference", moe_dispatch="einsum",
                mesh=None):
    """One new token against the cache.  tokens: (B, 1) int32; pos: scalar.

    Returns (logits: (B, V), new_cache).
    """
    dt = jnp.dtype(cfg.dtype)
    if cfg.modality == "audio":
        raise ValueError("encoder-only arch has no decode step")
    x = params["embed"].astype(dt)[tokens]
    if cfg.tie_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dt)
    ctx = make_ctx(cfg, kernel_mode=kernel_mode, moe_dispatch=moe_dispatch,
                   mesh=mesh)
    x = shard_hint(x, ctx, ("batch", None, None))

    def period_body(carry, xs):
        x = carry
        blk, st = xs
        new_states = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, ns = _decode_layer(kind, x, blk[f"l{i}"], st[f"l{i}"],
                                  cfg, pos, ctx)
            new_states[f"l{i}"] = ns
        return x, new_states

    new_cache = {"blocks": cache["blocks"], "tail": []}
    if cfg.first_k_dense:
        new_cache["lead"] = []
        for layer, st in zip(params["lead"], cache["lead"]):
            x, ns = _decode_layer(cfg.block_pattern[0], x, layer, st, cfg,
                                  pos, ctx)
            new_cache["lead"].append(ns)
    if cfg.n_scan_blocks:
        x, new_blocks = lax.scan(period_body, x,
                                 (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = new_blocks
    for i, layer in enumerate(params.get("tail", [])):
        kind = cfg.block_pattern[i % cfg.pattern_period]
        x, ns = _decode_layer(kind, x, layer, cache["tail"][i], cfg, pos, ctx)
        new_cache["tail"].append(ns)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    head = _head_matrix(params, cfg)
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))[:, 0]
    return logits, new_cache


def prefill(params, cfg: ArchConfig, batch, *, kernel_mode="reference",
            moe_dispatch="einsum", cache_len: Optional[int] = None,
            mesh=None):
    """Full-context forward that also materialises the decode cache.

    Returns (last_token_logits: (B, V), cache).  For attention layers the
    cache is sized ``cache_len`` (default: context length) and filled with
    the (windowed, ring-rotated) keys/values.
    """
    x, memory = _embed(params, cfg, batch)
    B, S, _ = x.shape
    ctx = make_ctx(cfg, kernel_mode=kernel_mode, moe_dispatch=moe_dispatch,
                   positions=jnp.arange(S), memory=memory, mesh=mesh)
    x = shard_hint(x, ctx, ("batch", None, None))
    # full-seq forward collecting per-layer KV
    x, _, (kvs, tail_kvs, lead_kvs), _ = _stack_forward(params, cfg, x, ctx,
                                                        collect_kv=True)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    head = _head_matrix(params, cfg)
    logits = jnp.einsum("bd,dv->bv", x[:, -1], head.astype(x.dtype))

    L_default = cache_len or S
    dt = jnp.dtype(cfg.dtype)

    def to_cache(kind, kv):
        if kind not in (ATTN, SWA, LOCAL, CROSS, MLA):
            return kv                       # recurrent state dict, verbatim
        k, v = kv  # (B,S,KH,Dh) [or (n,B,S,KH,Dh) when scanned], or memory KV
        if kind == CROSS:
            return {"ck": k.astype(dt), "cv": v.astype(dt)}
        w = _window_for(kind, cfg)
        L = min(w, L_default) if w else L_default

        def fit(arr):
            if arr.shape[-3] > L:           # keep last L, ring-rotate
                arr = arr[..., -L:, :, :]
                arr = jnp.roll(arr, S % L, axis=-3)
            elif arr.shape[-3] < L:         # pad up to L slots
                pad = [(0, 0)] * arr.ndim
                pad[-3] = (0, L - arr.shape[-3])
                arr = jnp.pad(arr, pad)
            return arr.astype(dt)

        return {"k": fit(k), "v": fit(v)}

    cache = init_cache(cfg, B, L_default)
    if cfg.n_scan_blocks and kvs:
        for i, kind in enumerate(cfg.block_pattern):
            key = f"l{i}"
            if key in kvs:
                cache["blocks"][key] = to_cache(kind, kvs[key])
    for i in range(cfg.n_tail_layers):
        kind = cfg.block_pattern[i % cfg.pattern_period]
        if i < len(tail_kvs):
            cache["tail"][i] = to_cache(kind, tail_kvs[i])
    for i, kv in enumerate(lead_kvs):
        cache["lead"][i] = to_cache(cfg.block_pattern[0], kv)
    return logits, cache
