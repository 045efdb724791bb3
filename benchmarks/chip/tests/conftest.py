"""The singlecell-moonlight configuration at a size a CPU test run can
hold, added to the tables of ``tiny.py`` that ``tiny_root`` reads: its
sizes, its mix and the limits set for them (see
``test_moonlight_cell.py``)."""
from benchmarks.chip.tests import tiny

# 1 dense + 2 MoE layers; 4 of a 16-wide router's experts held, top 6;
# v_head_dim unlike the query/key width
tiny.SIZES.setdefault("singlecell_moonlight", dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=8, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, n_routed_experts=4, vocab_size=256,
    published={"num_hidden_layers": 27, "n_routed_experts": 16,
               "vocab_size": 163840}))
tiny.MIXES.setdefault("sc-moonlight-2samples-closed", {"workload": dict(
    samples=2, rows=8, seq_len=16, train_steps=4, batch=2)})
# Limits at these sizes, between the program's readings on CPU (seeds 11,
# 12, 13, 2**31 + 5, 2**31 + 12345) and the control's (11, 12, 13): loss
# 1.4e-4..1.3e-3 vs 1.2e-3..3.8e-3; grad 2.1e-3..7.0e-3 vs 2.2e-2..2.5e-2;
# change 1.9e-3..7.7e-3 vs 7.4e-3..1.2e-2; embedding 5.5e-3..1.6e-2 vs
# 6.7e-2..7.3e-2; routing 0..0.047 vs 0.19..0.25 (64 pairs a sample).
# Loss and change overlap at this size; the control fails the other three.
tiny.LIMITS.setdefault("singlecell_moonlight", dict(
    loss_gap=2e-3, grad_gap=1.2e-2, change_gap=1.5e-2, embedding_gap=3e-2,
    routing_gap=0.12))
