"""Roofline share of the held experts' grouped matmuls, %: the least time
the chip could take for their operations and bytes (``flops.expert_ops``,
``flops.expert_bytes``, from the assignment counters) at the bf16 and
HBM peaks, over their device time per train step (``moe_experts``)."""
from benchmarks.chip import flops as peaks
from benchmarks.chip.configs.singlecell_moonlight import flops, tools
from benchmarks.chip.scopes import ms_per_run


def read(obs):
    ms = ms_per_run(obs, tools.TRAIN_MODULE, tools.hlo_text(
        tools.TRAIN_MODULE), ("moe_experts",))
    assign = flops.assignments_per_step(obs.runs)
    if ms is None or not assign:
        return None
    sz = dict(tools.sizes_of(str(obs.cell.config_file)))
    peak = peaks.peaks(obs.device_kind)
    least = max(flops.expert_ops(sz, assign) / peak["bf16_flops_per_s"],
                flops.expert_bytes(sz, assign) / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
