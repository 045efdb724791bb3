"""Object-store put time (``sf:cas_put``: hash and index), every thread
summed, per completed workflow, ms."""
from benchmarks.chip.spans import total_ms


def read(obs):
    return total_ms(obs, ("sf:cas_put",))
