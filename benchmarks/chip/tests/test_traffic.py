"""The traffic generator: every seed sends the same work in another order."""
from collections import Counter

import numpy as np
import pytest

from benchmarks.chip.traffic import Mix


def test_same_gaps_and_tenants_for_every_seed():
    mix = Mix("m", "service", "poisson", {}, rate_per_s=6.0, tenants=16,
              zipf_exponent=1.0)
    a, b = mix.schedule(2**31 + 1, 45.0), mix.schedule(5, 45.0)
    assert len(a) == len(b) == 270
    assert [s.at for s in a] != [s.at for s in b]
    # the same gaps in another order: the last send is at the same time
    assert a[-1].at == pytest.approx(b[-1].at)
    ga, gb = (np.sort(np.diff([s.at for s in x])) for x in (a, b))
    assert np.isin(np.round(ga, 9), np.round(gb, 9)).sum() >= len(ga) - 2
    ca, cb = Counter(s.tenant for s in a), Counter(s.tenant for s in b)
    assert ca == cb
    # Zipf 1.0 over 16 tenants: the first has 1/H_16 of the runs
    assert ca["tenant-00"] == pytest.approx(270 / sum(
        1 / k for k in range(1, 17)), abs=1)
    assert 0 < a[0].at and a[-1].at < 45.0


def test_closed_loop_has_no_schedule():
    assert Mix("m", "executor", "closed", {}).schedule(1, 10.0) == []
