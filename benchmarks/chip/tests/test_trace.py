"""The trace reduction, on a hand-made trace and on one recorded on a TPU
v5e (``data/small.xplane.pb``: a window span, two tool spans and a few
compiled programs)."""
from pathlib import Path

import pytest

from benchmarks.chip import trace as tr

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
MS = 1e6


def made():
    """Window 0-100 ms; ops 10-20, 15-30 and 60-70 ms; tool /a spans
    35-55 ms, tool /b spans 70-100 ms."""
    return tr.Trace(
        window=(0.0, 100 * MS),
        ops={"/device:TPU:0|fusion.1": [(10 * MS, 20 * MS)],
             "/device:TPU:0|fusion.2": [(15 * MS, 30 * MS),
                                        (60 * MS, 70 * MS)],
             "/device:TPU:0|copy.3": [(95 * MS, 120 * MS)]},
        modules={"jit_step": [(10 * MS, 30 * MS), (60 * MS, 70 * MS)],
                 "jit_late": [(95 * MS, 120 * MS), (130 * MS, 140 * MS)]},
        tools=[("/a", (35 * MS, 55 * MS)), ("/b", (70 * MS, 100 * MS))])


def test_busy_union_and_idle_share():
    t = made()
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    # 10-30, 60-70 and 95-100 (clipped to the window)
    assert tr.busy_s(t) == pytest.approx(0.035)
    assert tr.window_s(t) == pytest.approx(0.1)
    assert tr.idle_share(t) == pytest.approx(0.65)


def test_device_ops_by_program():
    ops = dict(tr.device_ops(made()))
    assert ops["jit_step"] == pytest.approx(0.030)
    assert ops["jit_late"] == pytest.approx(0.005)
    assert len(tr.module_runs(made(), "jit_late")) == 1


def test_idle_gaps_named_after_the_host():
    gaps = tr.idle_gaps(made())
    # 30-60 (tool /a covers 20 of 30 ms), 70-95 (tool /b), 0-10 (none)
    assert gaps[0] == ("tool:/a", pytest.approx(0.030))
    assert gaps[1] == ("tool:/b", pytest.approx(0.025))
    assert gaps[2] == (tr.OUTSIDE, pytest.approx(0.010))
    assert tr.module_name("jit_train_step(8999633355340492680)") \
        == "jit_train_step"


def test_recorded_trace():
    t = tr.read_xplane(str(DATA))
    assert t.n_devices == 1
    assert 0.0 < tr.busy_s(t) < tr.window_s(t)
    names = {n for n, _ in tr.device_ops(t)}
    assert "jit_busy_matmul" in names
    assert {s for s, _ in t.tools} == {"/busy", "/sleep"}
    gaps = tr.idle_gaps(t)
    # the recorder slept 50 ms inside tool /sleep with the chip idle
    assert gaps[0][0] == "tool:/sleep" and gaps[0][1] > 0.04
