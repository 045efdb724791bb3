"""Operations and bytes of the singlecell-moonlight step programs.

Model FLOPs follow ``benchmarks/chip/flops.py``'s convention: a
multiply-add is two operations, the backward pass is twice the forward,
recomputation does not count, the embedding lookup is free, and causal
attention needs the lower triangle of its score and value products only.
Routed expert work is what the program's counters say it computed: each
held assignment is one token through one expert's SwiGLU.

The grouped expert matmuls are also counted as the program executes them,
for their roofline share: a train step runs each of a MoE layer's three
expert matmuls in the forward pass, again in its recomputation under full
remat, and twice in the backward (the input's and the weight's
gradients): 12 grouped matmuls.  Each reads one held-expert weight tensor
and an activation of its rows, and writes another, in bfloat16.
"""
from __future__ import annotations

BF16 = 2


def _dims(sz: dict):
    return (sz["hidden_size"], sz["num_attention_heads"], sz["kv_lora_rank"],
            sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], sz["v_head_dim"])


def mla_params(sz: dict) -> int:
    """Matmul parameters of one latent-attention layer."""
    d, H, r, dn, dr, dv = _dims(sz)
    return (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
            + H * dv * d)


def dense_params(sz: dict) -> int:
    """Matmul parameters every token meets, routed experts left out:
    attention in every layer, the leading dense SwiGLUs, the router and
    shared experts of the MoE layers, the head."""
    d, L = sz["hidden_size"], sz["num_hidden_layers"]
    K = sz["first_k_dense_replace"]
    fs = sz["n_shared_experts"] * sz["moe_intermediate_size"]
    moe = d * sz["router_experts"] + 3 * d * fs
    return (L * mla_params(sz) + K * 3 * d * sz["intermediate_size"]
            + (L - K) * moe + d * sz["vocab_size"])


def expert_flops(sz: dict) -> float:
    """One assignment through one expert's SwiGLU, forward."""
    return 2.0 * 3 * sz["hidden_size"] * sz["moe_intermediate_size"]


def attention_flops(sz: dict, batch: int, seq: int) -> float:
    """Causal score and value products of every layer, forward."""
    _, H, _, dn, dr, dv = _dims(sz)
    per = 2.0 * seq * seq * H * ((dn + dr) + dv) / 2
    return sz["num_hidden_layers"] * batch * per


def forward_flops(sz: dict, batch: int, seq: int,
                  assignments: float) -> float:
    """One forward pass over ``batch`` rows of ``seq`` tokens in which
    ``assignments`` (token, held expert) pairs were computed."""
    return (2.0 * dense_params(sz) * batch * seq
            + expert_flops(sz) * assignments
            + attention_flops(sz, batch, seq))


def train_step_flops(sz: dict, batch: int, seq: int,
                     assignments: float) -> float:
    return 3.0 * forward_flops(sz, batch, seq, assignments)


# ------------------------------------------------------- grouped matmuls
#: grouped expert matmuls per MoE layer and train step (3 forward, 3 in
#: the recomputation, 6 in the backward)
MATMULS_PER_STEP = 12


def expert_ops(sz: dict, assignments: float) -> float:
    """Operations the train step's grouped expert matmuls execute, for
    ``assignments`` held assignments summed over its MoE layers."""
    return MATMULS_PER_STEP / 3 * expert_flops(sz) * assignments


def expert_bytes(sz: dict, assignments: float) -> float:
    """Bytes the train step's grouped expert matmuls move: each of the 12
    per layer reads one (held, d, f) weight tensor, and reads or writes
    one (rows, d) and one (rows, f) activation."""
    d, f = sz["hidden_size"], sz["moe_intermediate_size"]
    layers = sz["num_hidden_layers"] - sz["first_k_dense_replace"]
    weights = layers * sz["n_routed_experts"] * d * f
    return MATMULS_PER_STEP * BF16 * (weights + assignments * (d + f))


def assignments_per_step(runs) -> float:
    """Mean held assignments per train step, summed over the MoE layers,
    from the ``expert_load`` counters ``/count`` reported in the completed
    workflows of ``runs``; 0.0 where there is none."""
    import numpy as np
    steps = [np.sum(np.asarray(st["expert_load"]), axis=(1, 2))
             for f in runs if f.ok
             for st in f.result.outputs.get("stats", ())
             if "expert_load" in st]
    return float(np.mean(np.concatenate(steps))) if steps else 0.0
