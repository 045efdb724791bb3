"""Mean device time of one /count train step, ms, from the trace."""
from benchmarks.chip.readers import module_mean_ms


def read(obs):
    return module_mean_ms(obs, "jit_count_train_step")
