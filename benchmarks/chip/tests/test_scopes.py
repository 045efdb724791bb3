"""Scope attribution (``scopes.py``) and the five per-layer readers of
the held-share model, on a hand-made trace and HLO text; and the
attribution on the HLO text of the program's own compiled train step."""
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import scopes, trace as tr
from benchmarks.chip.harness import ROOT, load_module

MS = 1e6
SCOPES = scopes.MODEL_SCOPES
HLO = '''HloModule jit_count_train_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[8,16]) -> bf16[8,16] {
  %param_0 = bf16[8,16]{1,0} parameter(0)
  ROOT %ragged-dot.1 = bf16[8,16]{1,0} ragged-dot(%param_0, %param_0), metadata={op_name="jit(count_train_step)/transpose(jvp(moe_experts))/ragged_dot"}
}

ENTRY %main.9 (Arg_0.1: bf16[8,16]) -> bf16[8,16] {
  %Arg_0.1 = bf16[8,16]{1,0} parameter(0)
  %fusion.1 = bf16[8,16]{1,0} fusion(%Arg_0.1), kind=kOutput, calls=%fused_computation.1
  %dot.2 = bf16[8,16]{1,0} dot(%fusion.1, %fusion.1), metadata={op_name="jit(count_train_step)/jvp()/while/body/mla/dot_general"}
  %sort.3 = s32[8]{0} sort(%Arg_0.1), metadata={op_name="jit(count_train_step)/jvp()/moe_dispatch/sort"}
  %add.4 = bf16[8,16]{1,0} add(%dot.2, %dot.2), metadata={op_name="jit(count_train_step)/jvp()/add"}
  ROOT %copy.5 = bf16[8,16]{1,0} copy(%add.4), metadata={op_name="jit(count_train_step)/checkpoint/moe_route/mla_like/moe_shared/mul"}
}
'''


def ev(name):
    return f"/device:TPU:0|%{name} = bf16[8,16]{{1,0}} op(...)"


def made():
    """Window 0-100 ms; two train runs (10-30, 50-70 ms) and one run of
    another program (80-90 ms) whose ops share instruction names."""
    return tr.Trace(
        window=(0.0, 100 * MS),
        ops={ev("fusion.1"): [(10 * MS, 14 * MS), (50 * MS, 54 * MS),
                              (80 * MS, 85 * MS)],
             ev("dot.2"): [(14 * MS, 20 * MS), (54 * MS, 60 * MS)],
             ev("sort.3"): [(20 * MS, 21 * MS), (60 * MS, 61 * MS)],
             ev("add.4"): [(21 * MS, 25 * MS)],
             ev("copy.5"): [(25 * MS, 26 * MS)]},
        modules={"jit_count_train_step": [(10 * MS, 30 * MS),
                                          (50 * MS, 70 * MS)],
                 "jit_other": [(80 * MS, 90 * MS)]})


def test_instruction_names_and_scopes():
    assert scopes.instruction_name(
        "%fusion.12 = bf16[2]{0} fusion(%p), kind=kLoop") == "fusion.12"
    assert scopes.instruction_name("copy-start.3") == "copy-start.3"
    got = scopes.instruction_scopes(HLO, SCOPES)
    # a fusion takes its root's scope; the innermost scope wins; an op in
    # no scope (add.4) has none
    assert got["fusion.1"] == "moe_experts"
    assert got["ragged-dot.1"] == "moe_experts"
    assert got["dot.2"] == "mla"
    assert got["sort.3"] == "moe_dispatch"
    assert got["copy.5"] == "moe_shared"
    assert "add.4" not in got


def test_scope_seconds_inside_the_program_runs():
    secs = scopes.scope_seconds(made(), "jit_count_train_step", HLO, SCOPES)
    # fusion.1's run at 80 ms belongs to jit_other
    assert secs["moe_experts"] == pytest.approx(0.008)
    assert secs["mla"] == pytest.approx(0.012)
    assert secs["moe_dispatch"] == pytest.approx(0.002)
    assert secs["moe_shared"] == pytest.approx(0.001)
    assert secs["moe_route"] == 0.0


# ------------------------------------------------------------ readers
READERS = ("mla_ms.batch", "moe_dispatch_ms.batch", "moe_experts_ms.batch",
           "moe_experts_roofline_pct.batch", "mfu_moe.batch")
BENCH = Path(__file__).resolve().parents[1]
CONFIG = ROOT / "benchmarks/chip/configs/singlecell_moonlight.json"


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py").read


def obs_of(trace, load=None):
    """Runs whose /count reported ``load`` (steps, layers, held)."""
    stats = [] if load is None else [{"expert_load": load}]
    run = SimpleNamespace(ok=True, result=SimpleNamespace(
        outputs={"stats": stats}))
    cell = SimpleNamespace(config_file=CONFIG, mix=SimpleNamespace(
        workload={"batch": 8, "seq_len": 1024}))
    return SimpleNamespace(trace=trace, runs=[run], cell=cell,
                           device_kind="TPU v5 lite")


@pytest.fixture
def hlo(monkeypatch):
    from benchmarks.chip.configs.singlecell_moonlight import tools
    monkeypatch.setattr(tools, "hlo_text",
                        lambda m: HLO if m == tools.TRAIN_MODULE else None)


def balanced_load():
    # the expected load: 8192 tokens x 6 x 8 / 64 per layer
    return np.full((8, 4, 8), 768, np.int32)


def test_readers_read_the_made_trace(hlo):
    o = obs_of(made(), balanced_load())
    assert reader("mla_ms.batch")(o) == pytest.approx(6.0)
    assert reader("moe_experts_ms.batch")(o) == pytest.approx(4.0)
    assert reader("moe_dispatch_ms.batch")(o) == pytest.approx(1.0)
    share = reader("moe_experts_roofline_pct.batch")(o)
    assert 0.0 < share
    mfu = reader("mfu_moe.batch")(o)
    assert mfu > 0.0


def test_roofline_share_counts_what_the_counters_say(hlo):
    """Twice the assignments in the same time is twice the share (the
    grouped matmuls are compute-bound at this load)."""
    one = reader("moe_experts_roofline_pct.batch")(
        obs_of(made(), balanced_load()))
    two = reader("moe_experts_roofline_pct.batch")(
        obs_of(made(), 2 * balanced_load()))
    assert two / one == pytest.approx(2.0, rel=0.05)


@pytest.mark.parametrize("name", READERS)
def test_readers_give_none_with_nothing_to_read(name, hlo, monkeypatch):
    read = reader(name)
    assert read(obs_of(None, balanced_load())) is None
    empty = tr.Trace(window=(0.0, 100 * MS))
    assert read(obs_of(empty, balanced_load())) is None
    if name.startswith(("moe_experts_roofline", "mfu_moe")):
        assert read(obs_of(made(), None)) is None
    from benchmarks.chip.configs.singlecell_moonlight import tools
    monkeypatch.setattr(tools, "hlo_text", lambda m: None)
    if name != "mfu_moe.batch":
        assert read(obs_of(made(), balanced_load())) is None


def test_program_hlo_holds_every_scope():
    """The train step as compiled (here for the CPU) names every scope
    in its metadata, forward and backward."""
    import jax
    from benchmarks.chip.configs.singlecell_moonlight import tools
    sizes = dict(tools.sizes_of(str(CONFIG)))
    sizes.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=8,
                 intermediate_size=128, moe_intermediate_size=32,
                 num_hidden_layers=3, n_routed_experts=4, vocab_size=256,
                 router_experts=16)
    prog = tools.programs(tuple(sorted(sizes.items())))
    p, o = prog["init"](jax.random.key(0))
    prog["train"](p, o, jax.random.randint(jax.random.key(1), (2, 17), 0,
                                           256))
    got = set(scopes.instruction_scopes(tools.hlo_text(tools.TRAIN_MODULE),
                                        SCOPES).values())
    assert got == set(SCOPES)
