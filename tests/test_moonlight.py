"""Moonlight-16B-A3B (DeepSeek-V3 block) on the program's normal path,
against the plain reference of the chip benchmark's singlecell-moonlight
configuration, at small sizes: latent attention, the leading dense layer,
and the held-share DeepSeekMoE layer (sigmoid routing with a selection
bias, normalised and scaled gates, shared experts, dropless grouped
matmul over the held experts)."""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.chip.configs.singlecell_moonlight import (  # noqa: E402
    data, reference, tools)
from repro.configs import get_arch  # noqa: E402
from repro.models import moe, registry as R  # noqa: E402
from repro.models.layers import attention  # noqa: E402

# the cut's sizes with small widths: 1 dense + 2 MoE layers, 4 of a
# 16-wide router's experts held from offset 4, top 6, v width unlike q/k's
SIZES = dict(
    hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=8,
    intermediate_size=128, moe_intermediate_size=32, n_shared_experts=2,
    n_routed_experts=4, num_experts_per_tok=6, routed_scaling_factor=2.446,
    norm_topk_prob=True, scoring_func="sigmoid", first_k_dense_replace=1,
    num_hidden_layers=3, vocab_size=256, rms_norm_eps=1e-5,
    rope_theta=50000, balance_loss_alpha=1e-2, correction_bias_std=0.1,
    router_experts=16, expert_offset=4)
FROZEN = tuple(sorted(SIZES.items()))
F32 = reference.ROUND["f32"]


def program_cfg(**kw):
    """The program's config for SIZES, in float32 so that it can be held
    to the reference closely."""
    return dataclasses.replace(tools.arch(FROZEN), dtype="float32",
                               remat="none", **kw)


def weights(seed=0):
    return data.weights(jax.random.key(seed), SIZES)


def rows(seed=1, b=2, s=16):
    return jax.random.randint(jax.random.key(seed), (b, s + 1), 0,
                              SIZES["vocab_size"])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ------------------------------------------------------------ whole model
def test_logits_loss_and_gradients_match_the_reference():
    cfg, w, r = program_cfg(), weights(), rows()
    knobs = reference.knobs_of(SIZES)
    p = tools.to_program(w, cfg.first_k_dense)
    batch = {"tokens": r[:, :-1], "labels": r[:, 1:]}

    logits = R.forward_logits(p, cfg, {"tokens": r[:, :-1]})
    x = reference.hidden(w, r[:, :-1], SIZES, F32, knobs)[0]
    want = jnp.einsum("bsd,dv->bsv", x, w["head"],
                      precision=jax.lax.Precision.HIGHEST)
    assert rel(logits, want) < 1e-4

    def prog_loss(w):
        return R.forward_train(tools.to_program(w, cfg.first_k_dense), cfg,
                               batch)[0]

    def ref_loss(w):
        return reference.loss(w, r, SIZES, F32, knobs)[0]
    lp, gp = jax.jit(jax.value_and_grad(prog_loss))(w)
    lr, gr = jax.jit(jax.value_and_grad(ref_loss))(w)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for k in gr:
        assert rel(gp[k], gr[k]) < 1e-3, k
    # the selection bias takes no gradient
    assert float(jnp.max(jnp.abs(gp["bias"]))) == 0.0


def test_balance_loss_is_in_the_train_loss():
    cfg, w, r = program_cfg(), weights(), rows()
    p = tools.to_program(w, cfg.first_k_dense)
    batch = {"tokens": r[:, :-1], "labels": r[:, 1:]}
    total, m = R.forward_train(p, cfg, batch)
    assert float(m["aux"]) > 0
    assert float(total) == pytest.approx(float(m["nll"] + m["aux"]),
                                         rel=1e-6)
    # its gradient reaches the router
    g = jax.grad(lambda p: R.forward_train(
        p, dataclasses.replace(cfg, balance_alpha=1.0), batch)[1]["aux"])(p)
    assert float(jnp.max(jnp.abs(g["blocks"]["l0"]["ffn"]["router"]))) > 0


def test_train_step_reports_counters():
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    cfg, w, r = program_cfg(), weights(), rows(b=2, s=16)
    p = tools.to_program(w, cfg.first_k_dense)
    _, _, m = jax.jit(make_train_step(cfg))(
        p, adamw_init(p), {"tokens": r[:, :-1], "labels": r[:, 1:]})
    L = SIZES["num_hidden_layers"] - SIZES["first_k_dense_replace"]
    assert m["expert_load"].shape == (L, SIZES["n_routed_experts"])
    assert m["routing"].shape == (L, 2, 16, SIZES["num_experts_per_tok"])
    assert int(m["dropped"]) == 0
    held = ((m["routing"] >= 4) & (m["routing"] < 8)).sum(axis=(1, 2, 3))
    np.testing.assert_array_equal(m["expert_load"].sum(-1), held)


# ------------------------------------------------------------ attention
def test_mla_v_width_unlike_qk_matches_a_per_head_loop():
    cfg, w = program_cfg(), weights()
    from repro.models.transformer import _mla
    x = jax.random.normal(jax.random.key(3), (2, 12, SIZES["hidden_size"]))
    p = tools.to_program(w, 1)["lead"][0]["mix"]
    got, _ = _mla(x, p, cfg, positions=jnp.arange(12))
    # the reference's layer, and a loop over heads and queries
    want = x + reference.mla(x, w, 0, SIZES, F32)
    assert rel(got, want) < 1e-5
    from repro.models.transformer import _mla_qkv
    from repro.models.layers import rms_norm
    q, k, v = _mla_qkv(rms_norm(x, p["ln"]), p, cfg, jnp.arange(12))
    assert q.shape[-1] == 24 and v.shape[-1] == 8
    out = np.zeros(v.shape[:2] + (4, 8))
    for b in range(2):
        for h in range(4):
            for t in range(12):
                s = np.array([q[b, t, h] @ k[b, u, h] for u in range(t + 1)])
                s = np.exp((s - s.max()) / math.sqrt(24))
                out[b, t, h] = (s / s.sum()) @ np.asarray(v[b, :t + 1, h])
    assert rel(attention(q, k, v, causal=True), out) < 1e-5


def test_attention_in_query_blocks_matches_one_softmax():
    """Above one 512-row query block (the cell's 1,024 rows) attention runs
    block by block under remat; values and gradients are those of one
    causal softmax over the whole sequence, with v narrower than q/k."""
    S, H, Dh, Dv = 1024, 2, 24, 8
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (1, S, H, Dh))
    k = jax.random.normal(ks[1], (1, S, H, Dh))
    v = jax.random.normal(ks[2], (1, S, H, Dv))

    def whole(q, k, v):
        s = jnp.einsum("bqhd,bshd->bhqs", q, k) / math.sqrt(Dh)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), v)

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))

    blocked = lambda q, k, v: attention(q, k, v, causal=True)  # noqa: E731
    assert rel(blocked(q, k, v), whole(q, k, v)) < 1e-5
    got = jax.grad(loss(blocked), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(whole), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5


# ------------------------------------------------------------ MoE layer
held_block = jax.jit(moe.moe_held_block, static_argnums=2)


def layer_of(w, m=0):
    """MoE layer ``m``'s program parameters (unstacked)."""
    ffn = tools.to_program(w, 1)["blocks"]["l0"]["ffn"]
    return jax.tree.map(lambda a: a[m], ffn)


def test_expert_shares_sum_to_the_uncut_layer():
    """The 8 disjoint shares of a 64-expert layer, with the shared expert
    counted once, add up to the uncut layer."""
    sz = dict(SIZES, router_experts=64, n_routed_experts=64, expert_offset=0)
    w = data.weights(jax.random.key(5), sz)
    uncut = dataclasses.replace(program_cfg(), n_experts=64,
                                experts_held=64, expert_offset=0)
    x = jax.random.normal(jax.random.key(6), (2, 16, sz["hidden_size"]))
    p = layer_of(w)
    full, _, st = held_block(x, p, uncut)
    shared = moe.gated_mlp(moe.rms_norm(x, p["ln"]), p["shared"], "swiglu")
    total = x + shared
    for j in range(8):
        cut = dataclasses.replace(uncut, experts_held=8, expert_offset=8 * j)
        pj = dict(p, **{k: p[k][8 * j:8 * j + 8] for k in ("w1", "w3", "w2")})
        y, _, sj = held_block(x, pj, cut)
        total = total + (y - x - shared)
        np.testing.assert_array_equal(sj["expert_load"],
                                      st["expert_load"][8 * j:8 * j + 8])
    assert rel(total, full) < 1e-5
    # and the uncut layer is the reference's
    want = x + reference.moe(x, w, 0, sz, F32, reference.knobs_of(sz))[0]
    assert rel(full, want) < 1e-5


def test_gates_come_from_the_scores_not_the_biased_scores():
    w = dict(weights())
    # a bias that changes which experts are selected
    w["bias"] = jnp.zeros_like(w["bias"]).at[:, 4:8].set(0.5)
    cfg = program_cfg()
    x = jax.random.normal(jax.random.key(7), (2, 16, SIZES["hidden_size"]))
    y, _, st = held_block(x, layer_of(w), cfg)
    unbiased = reference.moe(x, dict(w, bias=jnp.zeros_like(w["bias"])), 0,
                             SIZES, F32, reference.knobs_of(SIZES))[2]
    assert not np.array_equal(np.sort(st["routing"], -1),
                              np.sort(unbiased, -1))
    sound = reference.moe(x, w, 0, SIZES, F32, reference.knobs_of(SIZES))
    wrong = reference.moe(x, w, 0, SIZES, F32,
                          reference.knobs_of(SIZES, gate_bias=1.0))
    assert rel(y, x + sound[0]) < 1e-5
    assert rel(y, x + wrong[0]) > 1e-2
    np.testing.assert_array_equal(st["routing"], sound[2])


def test_dropless_when_every_token_picks_the_same_experts():
    """A router that sends every token to the same six experts, all held:
    each of them gets every token, and none is dropped."""
    w = dict(weights())
    w["router"] = jnp.zeros_like(w["router"])
    w["bias"] = jnp.zeros_like(w["bias"]).at[:, 4:8].set(1.0).at[
        :, 8:10].set(0.5)
    cfg = program_cfg(experts_held=8, expert_offset=4)
    sz = dict(SIZES, n_routed_experts=8)
    w8 = data.weights(jax.random.key(0), sz)
    w8 = dict(w8, router=w["router"], bias=w["bias"])
    x = jax.random.normal(jax.random.key(8), (2, 16, SIZES["hidden_size"]))
    y, _, st = held_block(x, layer_of(w8), cfg)
    np.testing.assert_array_equal(st["expert_load"],
                                  [32, 32, 32, 32, 32, 32, 0, 0])
    assert int(st["dropped"]) == 0
    want = reference.moe(x, w8, 0, sz, F32, reference.knobs_of(sz))
    assert int(want[3]) == 0
    assert rel(y, x + want[0]) < 1e-5


def test_held_rows_cover_every_assignment():
    cfg = program_cfg()
    assert moe.held_rows(100, cfg) == 100 * min(6, 4)
    cfg8 = program_cfg(experts_held=8)
    assert moe.held_rows(100, cfg8) == 600


def _unwritten_tail(ragged_dot):
    """``ragged_dot`` as a grouped matmul that writes no row past its
    groups, forward or transposed: those rows hold NaN."""
    def tail(y, sizes):
        past = jnp.arange(y.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, y)

    @jax.custom_vjp
    def f(x, w, sizes):
        return tail(ragged_dot(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        return f(x, w, sizes), (x, w, sizes)

    def bwd(res, dy):
        x, w, sizes = res
        _, vjp = jax.vjp(lambda x, w: ragged_dot(x, w, sizes), x, w)
        dx, dw = vjp(jnp.nan_to_num(tail(dy, sizes), nan=0.0))
        return tail(dx, sizes), dw, None
    f.defvjp(fwd, bwd)
    return f


def test_rows_past_the_groups_stay_out_of_the_gradient(monkeypatch):
    """What the grouped matmul leaves in the rows past its groups (the
    buffer's padding) reaches neither the output nor any gradient."""
    w, cfg = weights(), program_cfg()
    p = layer_of(w)
    x = jax.random.normal(jax.random.key(9), (2, 16, SIZES["hidden_size"]))

    def grads():
        def f(x, p):
            y, aux, _ = moe.moe_held_block(x, p, cfg)
            return jnp.sum(jnp.sin(y)) + aux
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(x, p)

    want = grads()
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _unwritten_tail(jax.lax.ragged_dot))
    got = grads()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ counting
def closed_form_params(c) -> int:
    """Parameters of the DeepSeek-V3 block at config ``c``, by hand."""
    d, H, r = c.d_model, c.n_heads, c.kv_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    f, E, Hn = c.expert_d_ff, c.n_experts, c.experts_held
    mla = d + d * H * (dn + dr) + d * (r + dr) + r + r * H * (dn + dv) \
        + H * dv * d
    dense = d + 3 * d * c.d_ff
    moe_ = d + d * E + E + 3 * Hn * d * f + 3 * d * c.n_shared_experts * f
    K = c.first_k_dense
    return (2 * c.vocab_size * d + d + c.n_layers * mla + K * dense
            + (c.n_layers - K) * moe_)


def test_param_counts_published_and_cut():
    full = get_arch("moonlight-16b-a3b")
    assert R.count_params_analytic(full) == closed_form_params(full)
    assert 15.9e9 < R.count_params_analytic(full) < 16.0e9
    cut = dataclasses.replace(full, n_layers=5, experts_held=8,
                              vocab_size=20480)
    assert R.count_params_analytic(cut) == closed_form_params(cut) \
        == 568_484_608
    small = program_cfg()
    p, _ = R.init_params(jax.random.key(0), small)
    assert R.count_params_analytic(small) == sum(
        a.size for a in jax.tree.leaves(p)) == closed_form_params(small)


def test_active_routed_work_is_top_k_times_held_over_e():
    full = get_arch("moonlight-16b-a3b")
    cut = dataclasses.replace(full, n_layers=5, experts_held=8,
                              vocab_size=20480)
    d, f = full.d_model, full.expert_d_ff
    expert = 3 * d * f
    for c in (full, cut):
        routed = (c.n_layers - 1) * c.top_k * c.experts_held / c.n_experts \
            * expert
        dense = R.count_flops_params(c, active_only=False) \
            - (c.n_layers - 1) * c.experts_held * expert
        assert R.count_flops_params(c) == pytest.approx(dense + routed,
                                                        abs=16)
    # 275.7 M matmul parameters a token meets on the cut (26 M routed)
    assert R.count_flops_params(cut) == pytest.approx(275.67e6, rel=1e-4)


# ------------------------------------------------------------ minicpm
def test_minicpm_train_step_unchanged_to_the_bit():
    """The dense tied decoder's loss, second loss and gradient norm after
    the ``attention()`` and stack edits, as the code before them gave."""
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    cfg = get_arch("minicpm-2b").reduced()
    p, _ = R.init_params(jax.random.key(7), cfg)
    k1, k2 = jax.random.split(jax.random.key(8))
    b = {"tokens": jax.random.randint(k1, (2, 32), 0, cfg.vocab_size),
         "labels": jax.random.randint(k2, (2, 32), 0, cfg.vocab_size)}
    step = jax.jit(make_train_step(cfg))
    p2, o2, m = step(p, adamw_init(p), b)
    _, _, m2 = step(p2, o2, b)
    assert float(m["loss"]).hex() == "0x1.cbff140000000p+2"
    assert float(m2["loss"]).hex() == "0x1.cbeae40000000p+2"
    assert float(m2["grad_norm"]).hex() == "0x1.4940240000000p+1"
