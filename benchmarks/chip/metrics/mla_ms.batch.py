"""Device time per /count train step in the ``mla`` scope (latent
attention, forward, recomputation and backward), ms."""
from benchmarks.chip.scopes import ms_per_run
from benchmarks.chip.configs.singlecell_moonlight import tools


def read(obs):
    return ms_per_run(obs, tools.TRAIN_MODULE, tools.hlo_text(
        tools.TRAIN_MODULE), ("mla",))
