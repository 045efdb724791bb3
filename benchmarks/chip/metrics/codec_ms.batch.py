"""Serialize and deserialize time (``sf:encode``, ``sf:decode``), every
thread summed, per completed workflow, ms."""
from benchmarks.chip.spans import total_ms


def read(obs):
    return total_ms(obs, ("sf:encode", "sf:decode"))
