"""Jittable step functions (train / prefill / serve) + abstract input specs.

These are the "tasks" the StreamFlow layer schedules and the objects the
dry-run lowers.  Everything is shape-polymorphic over the (arch x shape)
grid; input_specs() returns ShapeDtypeStructs (no allocation) exactly like
the workflow's ports describe them.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import registry as R
from repro.models.config import ArchConfig, ShapeSpec
from repro.optim import AdamWConfig, adamw_init, adamw_update, make_schedule


# ---------------------------------------------------------------------------
# Abstract input specs (ShapeDtypeStruct stand-ins, shardable, no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Model inputs for one (arch, shape) cell as ShapeDtypeStructs."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        batch: Dict[str, Any] = {}
        if cfg.modality == "audio":
            batch["frames"] = sds((B, S, cfg.frontend_dim), jnp.bfloat16)
            batch["labels"] = sds((B, S), jnp.int32)
            batch["mask"] = sds((B, S), jnp.float32)
        else:
            batch["tokens"] = sds((B, S), jnp.int32)
            batch["labels"] = sds((B, S), jnp.int32)
        if cfg.modality == "vision":
            batch["patches"] = sds((B, cfg.n_patches, cfg.frontend_dim),
                                   jnp.bfloat16)
        return {"batch": batch}
    if shape.kind == "prefill":
        batch = {}
        if cfg.modality == "audio":
            batch["frames"] = sds((B, S, cfg.frontend_dim), jnp.bfloat16)
        else:
            batch["tokens"] = sds((B, S), jnp.int32)
        if cfg.modality == "vision":
            batch["patches"] = sds((B, cfg.n_patches, cfg.frontend_dim),
                                   jnp.bfloat16)
        return {"batch": batch}
    # decode: one new token against a KV/recurrent cache of length S
    cache = jax.eval_shape(lambda: R.init_cache(cfg, B, S))
    return {"tokens": sds((B, 1), jnp.int32),
            "pos": sds((), jnp.int32),
            "cache": cache}


def params_specs(cfg: ArchConfig):
    return R.params_and_axes_shapes(cfg)


def opt_specs(cfg: ArchConfig):
    shapes, _ = R.params_and_axes_shapes(cfg)
    return jax.eval_shape(adamw_init, shapes)


# ---------------------------------------------------------------------------
# Step factories
# ---------------------------------------------------------------------------

def make_train_step(cfg: ArchConfig, opt_cfg: Optional[AdamWConfig] = None, *,
                    kernel_mode: str = "reference",
                    moe_dispatch: str = "einsum",
                    accum_steps: int = 1, mesh=None):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``accum_steps`` > 1 splits the global batch into microbatches scanned
    sequentially — the DP gradient all-reduce of microbatch i overlaps the
    compute of microbatch i+1 once XLA latency-hides the (async) collective.
    """
    opt_cfg = opt_cfg or AdamWConfig()
    schedule = make_schedule(opt_cfg)

    def loss_fn(p, b):
        return R.forward_train(p, cfg, b, kernel_mode=kernel_mode,
                               moe_dispatch=moe_dispatch, mesh=mesh)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def micro(carry, mb):
                (l, g) = carry
                (li, mi), gi = grad_fn(params, mb)
                return (l + li, jax.tree.map(jnp.add, g, gi)), mi

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            mb = jax.tree.map(
                lambda x: x.reshape((accum_steps, -1) + x.shape[1:]), batch)
            (loss, grads), metrics = jax.lax.scan(
                micro, (jnp.float32(0), zeros), mb)
            loss = loss / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        params, opt_state, om = adamw_update(grads, opt_state, params,
                                             opt_cfg, schedule)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def init_ef_errors(params, n_pods: int):
    """Per-pod error-feedback state: leading pod dim, sharded P('pod')."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_pods,) + p.shape, jnp.float32), params)


def make_train_step_dp_compressed(cfg: ArchConfig, mesh,
                                  opt_cfg: Optional[AdamWConfig] = None, *,
                                  kernel_mode: str = "reference",
                                  moe_dispatch: str = "einsum"):
    """Multi-pod train step with int8+error-feedback gradient all-reduce on
    the DCN ("pod") axis (beyond-paper distributed-optimization feature).

    Partial-auto shard_map: manual over "pod" only — inside the body the
    data/model axes are still compiler-partitioned SPMD, so the per-pod
    gradient is the usual FSDP/TP-sharded tree; only the cross-pod reduce
    is hand-written (quantize -> psum(int32) -> dequant + EF residual).

    Signature: (params, opt_state, errors, batch) ->
               (params, opt_state, errors, metrics).
    """
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import psum_int8_with_ef

    opt_cfg = opt_cfg or AdamWConfig()
    schedule = make_schedule(opt_cfg)

    def loss_fn(p, b):
        return R.forward_train(p, cfg, b, kernel_mode=kernel_mode,
                               moe_dispatch=moe_dispatch)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def body(params, opt_state, errors, batch):
        errors = jax.tree.map(lambda e: e[0], errors)   # drop pod-local dim
        (loss, metrics), grads = grad_fn(params, batch)
        grads, errors = psum_int8_with_ef(grads, errors, "pod")
        params, opt_state, om = adamw_update(grads, opt_state, params,
                                             opt_cfg, schedule)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["loss"] = jax.lax.pmean(loss, "pod")
        errors = jax.tree.map(lambda e: e[None], errors)
        return params, opt_state, errors, metrics

    batch_spec = {k: P("pod") for k in ("tokens", "labels", "frames",
                                        "mask", "patches")}

    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def train_step(params, opt_state, errors, batch):
        # partial-auto: manual over "pod" only
        f = jax.shard_map(
            body, mesh=mesh, axis_names={"pod"}, check_vma=False,
            in_specs=(specs_like(params, P()), specs_like(opt_state, P()),
                      specs_like(errors, P("pod")),
                      {k: batch_spec[k] for k in batch}),
            out_specs=(specs_like(params, P()), specs_like(opt_state, P()),
                       specs_like(errors, P("pod")), P()))
        return f(params, opt_state, errors, batch)

    return train_step


def make_prefill_step(cfg: ArchConfig, *, kernel_mode: str = "reference",
                      moe_dispatch: str = "einsum",
                      cache_len: Optional[int] = None, mesh=None):
    def prefill_step(params, batch):
        return R.prefill(params, cfg, batch, kernel_mode=kernel_mode,
                         moe_dispatch=moe_dispatch, cache_len=cache_len,
                         mesh=mesh)
    return prefill_step


def make_serve_step(cfg: ArchConfig, *, kernel_mode: str = "reference",
                    moe_dispatch: str = "einsum", greedy: bool = True,
                    mesh=None):
    """One decode step: (params, tokens, pos, cache) ->
    (next_tokens, logits, cache)."""
    def serve_step(params, tokens, pos, cache):
        logits, cache = R.decode_step(params, cfg, tokens, pos, cache,
                                      kernel_mode=kernel_mode,
                                      moe_dispatch=moe_dispatch, mesh=mesh)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, cache
    return serve_step


def make_eval_step(cfg: ArchConfig, *, kernel_mode: str = "reference",
                   moe_dispatch: str = "einsum"):
    def eval_step(params, batch):
        loss, metrics = R.forward_train(params, cfg, batch,
                                        kernel_mode=kernel_mode,
                                        moe_dispatch=moe_dispatch)
        return {"loss": loss, **metrics}
    return eval_step
