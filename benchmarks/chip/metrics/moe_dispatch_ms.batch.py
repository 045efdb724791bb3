"""Device time per /count train step in the held-share MoE layer's
routing, dispatch and combine (``moe_route``, ``moe_dispatch``,
``moe_combine``), ms."""
from benchmarks.chip.scopes import ms_per_run
from benchmarks.chip.configs.singlecell_moonlight import tools


def read(obs):
    return ms_per_run(obs, tools.TRAIN_MODULE, tools.hlo_text(
        tools.TRAIN_MODULE), ("moe_route", "moe_dispatch", "moe_combine"))
