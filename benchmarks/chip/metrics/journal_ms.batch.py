"""Journal time (``sf:journal``: write, flush, fsync), every thread
summed, per completed workflow, ms."""
from benchmarks.chip.spans import total_ms


def read(obs):
    return total_ms(obs, ("sf:journal",))
