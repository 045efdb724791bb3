"""Model FLOPs of the step programs, from their shapes alone.

A dense decoder with tied embeddings (the ``singlecell-minicpm`` step
model).  Counted per the usual convention: a multiply-add is two
operations, the backward pass is twice the forward, recomputation does
not count, the embedding lookup is free, and causal attention needs the
lower triangle of its score and value products only.
"""
from __future__ import annotations

import json
from pathlib import Path


def matmul_params(sizes: dict) -> int:
    """Parameters that take part in a matmul for each token."""
    d = sizes["hidden_size"]
    h = sizes["num_attention_heads"]
    kv = sizes["num_key_value_heads"]
    dh = d // h
    f = sizes["intermediate_size"]
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    head = d * sizes["vocab_size"]           # the tied output projection
    return sizes["num_hidden_layers"] * per_layer + head


def forward_flops(sizes: dict, batch: int, seq: int) -> float:
    """One forward pass over ``batch`` rows of ``seq`` tokens."""
    tokens = batch * seq
    dense = 2.0 * matmul_params(sizes) * tokens
    d = sizes["hidden_size"]
    # QK^T and PV: 2 products of 2 * S * S * d each, causal half of them
    attn = sizes["num_hidden_layers"] * batch * 2 * (2.0 * seq * seq * d) / 2
    return dense + attn


def train_step_flops(sizes: dict, batch: int, seq: int) -> float:
    """Forward and backward of one train step (the optimizer's
    elementwise update is left out)."""
    return 3.0 * forward_flops(sizes, batch, seq)


def embed_flops(sizes: dict, batch: int, seq: int) -> float:
    """One embedding call: the forward pass to full-sequence logits."""
    return forward_flops(sizes, batch, seq)


PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a chip missing from the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]
