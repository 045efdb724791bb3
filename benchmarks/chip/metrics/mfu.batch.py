"""Model FLOPs of the train and embed steps run in the traced window over
the window times the chip's bf16 peak, %."""
from benchmarks.chip import flops
from benchmarks.chip.readers import model_flops_share_pct


def read(obs):
    w, sizes = obs.cell.mix.workload, obs.cell.config
    return model_flops_share_pct(obs, {
        "jit_count_train_step": flops.train_step_flops(
            sizes, w["batch"], w["seq_len"]),
        "jit_seurat_embed": flops.embed_flops(
            sizes, w["batch"], w["seq_len"]),
    })
