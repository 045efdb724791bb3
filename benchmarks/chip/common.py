"""Pieces every configuration's check uses: seeds, compared numbers,
and the engine's exactly-once count."""
from __future__ import annotations

from collections import Counter
from dataclasses import dataclass
from typing import Iterable, List, Tuple

import numpy as np


#: the run index of the warm-up workflow, never one of the window's
WARMUP = -1


@dataclass(frozen=True)
class Check:
    """One number compared with its limit; passes when ``value <= limit``
    (every compared number is a gap or a count of faults).
    ``failed_runs`` names the window's runs that the number condemns."""
    name: str
    value: float
    limit: float
    failed_runs: Tuple[int, ...] = ()

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def worse(a: float, b: float) -> float:
    """The larger gap, where NaN (no number) is the largest."""
    return b if not b <= a else a


def derive_seed(seed: int, *path: int) -> int:
    """A 32-bit seed for one workflow, sample or tile, from ``--seed``
    (any size) and its place: the same inputs give the same seed."""
    return int(np.random.SeedSequence(
        [seed % 2**63, *(p % 2**32 for p in path)]).generate_state(1)[0])


def jax_key(seed: int):
    """A JAX PRNG key from a 32-bit seed."""
    import jax
    return jax.random.key(np.uint32(seed))


def exactly_once(events: Iterable, expected: int) -> int:
    """How far a run's invocations are from completing exactly once each:
    the planned invocations that did not complete, plus the extra
    completions, plus failed or retried attempts.  A speculative twin is
    the engine's own hedge: it may lose its race or be cancelled, so long
    as its invocation completes once."""
    events = list(events)
    done = Counter(e.step for e in events if e.status == "completed")
    bad = sum(1 for e in events if not e.speculative and (
        e.attempt or e.status.startswith(("failed", "preempted"))))
    extra = sum(n - 1 for n in done.values())
    return abs(expected - len(done)) + extra + bad


def sample_indices(seed: int, n: int, k: int) -> List[int]:
    """``k`` of ``range(n)``, drawn from the seed, always with the last."""
    if n <= k:
        return list(range(n))
    rng = np.random.default_rng(derive_seed(seed, 7))
    pick = set(rng.choice(n - 1, size=k - 1, replace=False).tolist())
    return sorted(pick | {n - 1})
