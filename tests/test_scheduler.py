"""Scheduling policies (paper §4.4 Fig. 3 interface)."""
from repro.core import (DataLocalityPolicy, JobDescription, JobStatus,
                        LoadBalancePolicy, RoundRobinPolicy, Scheduler,
                        BackfillPolicy)
from repro.core.workflow import Requirements


def _sched(policy):
    s = Scheduler(policy)
    for i in range(3):
        s.register_resource(f"r{i}", "m", "svc", cores=2, memory_gb=4)
    return s


def _job(name, deps=None, cores=1):
    return JobDescription(name, Requirements(cores=cores, memory_gb=1),
                          deps or {}, "svc")


def test_locality_prefers_largest_dep_holder():
    s = _sched(DataLocalityPolicy())
    rp = {"big": [("r2", "big")], "small": [("r0", "small")]}
    got = s.schedule(_job("j", {"small": 10, "big": 1000}),
                     ["r0", "r1", "r2"], rp)
    assert got == "r2"


def test_locality_falls_back_to_any_free():
    s = _sched(DataLocalityPolicy())
    rp = {"t": [("r1", "t")]}
    assert s.schedule(_job("j1", {"t": 5}), ["r0", "r1", "r2"], rp) == "r1"
    # r1 now busy -> next job with same dep goes to any free resource
    assert s.schedule(_job("j2", {"t": 5}), ["r0", "r1", "r2"], rp) == "r0"


def test_returns_none_when_all_busy_then_frees():
    s = _sched(DataLocalityPolicy())
    for i in range(3):
        assert s.schedule(_job(f"j{i}"), ["r0", "r1", "r2"], {}) is not None
    assert s.schedule(_job("j3"), ["r0", "r1", "r2"], {}) is None
    s.notify("j0", JobStatus.COMPLETED)
    assert s.schedule(_job("j3"), ["r0", "r1", "r2"], {}) == "r0"


def test_requirements_checked():
    s = _sched(DataLocalityPolicy())
    assert s.schedule(_job("huge", cores=99), ["r0", "r1", "r2"], {}) is None


def test_round_robin_cycles():
    s = _sched(RoundRobinPolicy())
    got = [s.schedule(_job(f"j{i}"), ["r0", "r1", "r2"], {})
           for i in range(3)]
    assert got == ["r0", "r1", "r2"]


def test_load_balance_allows_oversubscription():
    s = _sched(LoadBalancePolicy())
    got = [s.schedule(_job(f"j{i}"), ["r0", "r1", "r2"], {})
           for i in range(6)]
    assert got.count("r0") == got.count("r1") == got.count("r2") == 2


def test_backfill_orders_locality_ready_first():
    s = _sched(BackfillPolicy())
    rp = {"t": [("r1", "t")]}
    q = [_job("no_dep"), _job("dep_free", {"t": 100})]
    # the hook schedule_batch orders the queue with
    ordered = s.policy.order_queue(q, rp, s.resources)
    assert ordered[0].name == "dep_free"     # its locality target is free


def test_forget_model_clears_resources():
    s = _sched(DataLocalityPolicy())
    s.forget_model("m")
    assert s.schedule(_job("j"), ["r0"], {}) is None
