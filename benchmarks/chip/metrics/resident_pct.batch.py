"""Share of the window's token bytes that stayed on the device: the bytes
of ``sf:hold`` (a value of device arrays held in its store as it is) over
those of ``sf:hold`` and ``sf:encode`` (a value pickled, at its put or
later), every thread, %.  No number where no token was held: an engine
that keeps every token as bytes records no ``sf:hold``."""
from benchmarks.chip.spans import traced_runs


def read(obs):
    held = encoded = 0
    holds = 0
    for f in traced_runs(obs):
        for s in f.result.spans:
            if s.name == "sf:hold":
                holds += 1
                held += s.attrs.get("bytes", 0)
            elif s.name == "sf:encode":
                encoded += s.attrs.get("bytes", 0)
    if not holds or not held + encoded:
        return None
    return 100.0 * held / (held + encoded)
