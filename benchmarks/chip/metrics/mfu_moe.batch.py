"""Model FLOPs of the held-share model's train and embed steps run in the
traced window over the window times the chip's bf16 peak, %.  Routed
expert work is the train steps' counted assignments per token, taken for
the embed steps too (they report no counters)."""
from benchmarks.chip.configs.singlecell_moonlight import flops, tools
from benchmarks.chip.readers import model_flops_share_pct


def read(obs):
    w = obs.cell.mix.workload
    per_step = flops.assignments_per_step(obs.runs)
    if not per_step:
        return None
    sz = dict(tools.sizes_of(str(obs.cell.config_file)))
    b, s = w["batch"], w["seq_len"]
    return model_flops_share_pct(obs, {
        tools.TRAIN_MODULE: flops.train_step_flops(sz, b, s, per_step),
        "jit_seurat_embed": flops.forward_flops(sz, b, s, per_step),
    })
