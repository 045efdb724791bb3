"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix (``traffic/<name>.json``) says how a client reaches the system and
when it sends:

``{"entry": "executor" | "service", "arrival": "closed" | "poisson", ...}``

- ``closed``: one client runs workflows back to back; each is sent when
  the one before it has finished.  No schedule is drawn.
- ``poisson``: an open loop at ``rate_per_s``.  A run of ``seconds``
  seconds sends exactly ``round(rate_per_s * seconds)`` runs.  Their gaps
  are the exponential distribution's quantiles at the midpoints of equal
  bins, and ``tenants`` (``count``, Zipf ``exponent``) are dealt by their
  Zipf shares; ``--seed`` only orders both.  Every seed therefore sends
  the same set of gaps and tenants, so seeds change the order of the work
  and not its amount.

``workload`` carries the sizes the configuration reads (samples, grid);
the generator passes it through untouched.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

import numpy as np

ENTRIES = ("executor", "service")
ARRIVALS = ("closed", "poisson")


@dataclass(frozen=True)
class Send:
    """One scheduled run: due ``at`` seconds after the window opens."""
    index: int
    at: float
    tenant: str


@dataclass(frozen=True)
class Mix:
    name: str
    entry: str
    arrival: str
    workload: dict
    rate_per_s: Optional[float] = None
    tenants: int = 1
    zipf_exponent: float = 0.0
    drain_s: float = 60.0

    def schedule(self, seed: int, seconds: float) -> List[Send]:
        """The open loop's sends for one run (empty for a closed loop)."""
        if self.arrival == "closed":
            return []
        n = max(1, round(self.rate_per_s * seconds))
        rng = np.random.default_rng(seed)
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / self.rate_per_s
        gaps *= seconds / gaps.sum()          # the n sends fill the window
        at = np.cumsum(rng.permutation(gaps)) - gaps.min() / 2
        shares = np.arange(1, self.tenants + 1, dtype=float) ** (
            -self.zipf_exponent)
        counts = np.floor(shares / shares.sum() * n).astype(int)
        # deal the remainder to the largest fractional shares, in order
        frac = shares / shares.sum() * n - counts
        for i in np.argsort(-frac, kind="stable")[:n - counts.sum()]:
            counts[i] += 1
        tenants = np.repeat(np.arange(self.tenants), counts)
        tenants = rng.permutation(tenants)
        return [Send(i, float(a), f"tenant-{int(t):02d}")
                for i, (a, t) in enumerate(zip(at, tenants))]


def load_mix(path: Path) -> Mix:
    raw = json.loads(Path(path).read_text())
    entry, arrival = raw.get("entry"), raw.get("arrival")
    if entry not in ENTRIES or arrival not in ARRIVALS:
        raise ValueError(f"{path}: entry must be one of {ENTRIES} and "
                         f"arrival one of {ARRIVALS}")
    if arrival == "poisson" and not raw.get("rate_per_s", 0) > 0:
        raise ValueError(f"{path}: a poisson mix needs rate_per_s > 0")
    tenants = raw.get("tenants", {})
    return Mix(name=Path(path).stem, entry=entry, arrival=arrival,
               workload=dict(raw.get("workload", {})),
               rate_per_s=raw.get("rate_per_s"),
               tenants=int(tenants.get("count", 1)),
               zipf_exponent=float(tenants.get("exponent", 0.0)),
               drain_s=float(raw.get("drain_s", 60.0)))


def quantile(values, q: float) -> float:
    """The q-quantile by linear interpolation (numpy's default)."""
    if not len(values):
        return math.nan
    return float(np.quantile(np.asarray(values, float), q))
