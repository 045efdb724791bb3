"""Jobs the scheduling policy was asked about per job it placed: the
``probed`` over the ``placed`` attributes of the engine loop's
``sf:schedule`` spans, summed over the window's traced workflows.  No
number where no span carries them (an engine that does not count its
probes) or where nothing was placed."""
from benchmarks.chip.spans import traced_runs


def read(obs):
    probed = placed = passes = 0
    for f in traced_runs(obs):
        for s in f.result.spans:
            if s.name == "sf:schedule" and "probed" in s.attrs:
                passes += 1
                probed += s.attrs["probed"]
                placed += s.attrs.get("placed", 0)
    if not passes or not placed:
        return None
    return probed / placed
