"""Pipelined executor: async prefetch, in-flight dedup, queue-aware batch
scheduling, and outputs under concurrent completions."""
import time

import pytest

from repro.core import (BackfillPolicy, DataLocalityPolicy, DataManager,
                        DeploymentManager, FaultConfig, JobDescription,
                        LocalityBatchPolicy, ModelSpec, Scheduler,
                        StreamFlowExecutor, WidestFirstPolicy)
from repro.core.streamflow_file import Binding
from repro.core.workflow import Requirements, Step, Workflow


# --------------------------------------------------------------- executor

def _wf_independent(n=3, sleep=0.2):
    """n independent jobs, each consuming one external token."""
    wf = Workflow("w")
    for i in range(n):
        def fn(inputs, ctx, i=i):
            time.sleep(sleep)
            return {f"out{i}": inputs["x"]}
        wf.add_step(Step(f"/j{i}", fn, {"x": f"in{i}"}, (f"out{i}",)))
    return wf


def _slow_link_site(replicas=1, latency=0.15):
    return {"site": ModelSpec("site", "local", {
        "link_latency_s": latency,
        "services": {"svc": {"replicas": replicas}}})}


def _run(n=3, sleep=0.2, replicas=1):
    ex = StreamFlowExecutor(_slow_link_site(replicas=replicas),
                            fault=FaultConfig(speculative=False))
    wf = _wf_independent(n, sleep)
    res = ex.run(wf, [Binding("/", "site", "svc")],
                 {f"in{i}": i for i in range(n)})
    return ex, res


@pytest.mark.parametrize("replicas", [1, 2])
def test_outputs_under_concurrent_completions(replicas):
    # the outputs are the known values whichever order workers finish in:
    # two replicas let completions race
    _, res = _run(n=3, sleep=0.0, replicas=replicas)
    assert res.outputs == {f"out{i}": i for i in range(3)}
    done = [e for e in res.events if e.status == "completed"]
    assert sorted(e.step for e in done) == ["/j0", "/j1", "/j2"]


def test_pipelined_overlaps_transfers_with_compute():
    # one worker slot, 3 jobs, 150ms WAN hop per input token: a loop that
    # moved each job's token in-line would pay (hop + compute) per job;
    # the pipelined loop stages token N+1 in while job N computes
    _, res = _run()
    assert res.outputs == {f"out{i}": i for i in range(3)}
    # from the first job's start to the last one's end (the run's wall
    # time adds deployment and collecting the outputs): under the in-line
    # lower bound 3 * (0.15 + 0.2) by more than 0.1 s, 2 of 3 hops hidden
    jobs = [e for e in res.events if e.status == "completed"]
    span = max(e.end for e in jobs) - min(e.start for e in jobs)
    assert span < 3 * (0.15 + 0.2) - 0.1


def test_stage_in_prefetches_before_slot_frees():
    ex, res = _run()
    rows = res.timeline_rows()
    # with prefetch, later jobs start back-to-back: the gap between a job's
    # end and the next job's start stays well under one 150ms WAN hop
    rows.sort(key=lambda r: r[2])
    gaps = [rows[i + 1][2] - rows[i][3] for i in range(len(rows) - 1)]
    assert max(gaps) < 0.1


def test_speculative_twins_release_their_scheduler_slots():
    # twins register allocations under "path#specN"; harvesting must free
    # THAT allocation, or every speculation permanently leaks a resource
    wf = Workflow("w")
    for i in range(3):
        def fn(inputs, ctx, i=i):
            time.sleep(0.06)
            return {f"o{i}": i}
        wf.add_step(Step(f"/j{i}", fn, {}, (f"o{i}",)))
    models = {"site": ModelSpec("site", "local", {
        "services": {"svc": {"replicas": 4}}})}
    ex = StreamFlowExecutor(models, fault=FaultConfig(
        speculative=True, straggler_factor=1.01,
        straggler_min_samples=1, straggler_min_elapsed_s=0.0))
    res = ex.run(wf, [Binding("/", "site", "svc")], {})
    assert len([e for e in res.events if e.status == "completed"]) == 3
    # every allocation — primary or twin — was released on harvest
    assert all(not r.jobs for r in ex.scheduler.resources.values())


def test_drop_model_fences_inflight_transfer_registration():
    dm, d = _world()                      # 0.1s link latency per hop
    d.put_local("tok", b"z" * 64)
    fut = d.transfer_data_async("tok", "hpc", "hpc/x/0")
    time.sleep(0.04)                      # let the copy enter its WAN hop
    d.drop_model("hpc")                   # site dies while copy is in flight
    fut.result()
    # the landed copy must NOT be registered: the store it wrote to belongs
    # to the dead deployment, and eliding future transfers against it would
    # poison every consumer of the token
    assert not d.has_replica("tok", "hpc")
    rec = d.transfer_data("tok", "hpc", "hpc/x/0")
    assert rec.kind in ("two-step", "elided")  # re-copy allowed post-fence
    assert d.has_replica("tok", "hpc")


def test_drop_model_purges_inflight_dedup_map():
    dm, d = _world()
    d.put_local("tok", b"z" * 64)
    f1 = d.transfer_data_async("tok", "hpc", "hpc/x/0")
    d.drop_model("hpc")
    # post-drop consumers must get a FRESH copy, not ride the doomed future
    f2 = d.transfer_data_async("tok", "hpc", "hpc/x/0")
    assert f2 is not f1
    f1.result(); f2.result()
    assert d.has_replica("tok", "hpc")    # the fresh post-drop copy lands


def test_fault_retry_still_works_in_pipelined_mode():
    calls = {"n": 0}

    def flaky(inputs, ctx):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        return {"out": 7}

    wf = Workflow("w")
    wf.add_step(Step("/flaky", flaky, {}, ("out",)))
    ex = StreamFlowExecutor(_slow_link_site(latency=0.0),
                            fault=FaultConfig(speculative=False,
                                              max_retries=2,
                                              backoff_s=0.02))
    res = ex.run(wf, [Binding("/", "site", "svc")], {})
    assert res.outputs["out"] == 7
    done = [e for e in res.events if e.status == "completed"]
    assert done[0].attempt == 1


# ------------------------------------------------------------ datamanager

def _world():
    dm = DeploymentManager({
        "hpc": ModelSpec("hpc", "local", {
            "link_latency_s": 0.1,
            "services": {"x": {"replicas": 2}}}),
    })
    dm.deploy("hpc")
    return dm, DataManager(dm)


def test_inflight_transfer_dedup_single_copy():
    dm, d = _world()
    d.put_local("tok", b"z" * 64)
    f1 = d.transfer_data_async("tok", "hpc", "hpc/x/0")
    f2 = d.transfer_data_async("tok", "hpc", "hpc/x/0")
    assert f1 is f2                       # second consumer rides the first
    f1.result()
    assert d.dedup_hits == 1
    moved = [t for t in d.transfers if t.kind == "two-step"]
    assert len(moved) == 1                # one physical copy


def test_stage_in_then_move_is_intra_model():
    dm, d = _world()
    d.put_local("tok", b"z" * 64)
    d.transfer_data_async("tok", "hpc", "hpc/x/0").result()
    rec = d.transfer_data("tok", "hpc", "hpc/x/1")
    assert rec.kind == "intra-model"      # WAN hop already paid by stage-in


def test_transfer_pool_close_is_idempotent():
    dm, d = _world()
    d.put_local("tok", b"1")
    d.transfer_data_async("tok", "hpc", "hpc/x/0").result()
    d.close()
    d.close()
    # pool restarts lazily after close
    d.transfer_data_async("tok", "hpc", "hpc/x/1").result()


# -------------------------------------------------------------- scheduler

def _sched(policy, n=2):
    s = Scheduler(policy)
    for i in range(n):
        s.register_resource(f"r{i}", "m", "svc", cores=2, memory_gb=4)
    return s


def _job(name, deps=None, fanout=0):
    return JobDescription(name, Requirements(1, 1), deps or {}, "svc",
                          fanout=fanout)


def test_backfill_batch_protects_locality_targets():
    s = _sched(BackfillPolicy())
    rp = {"t": [("r0", "t")]}
    # FCFS head has no deps; the later job's data lives on r0.  Plain FCFS
    # would hand r0 to the head; backfill routes the head to r1.
    queue = [_job("head"), _job("needs_r0", {"t": 1000})]
    avail = {"head": ["r0", "r1"], "needs_r0": ["r0", "r1"]}
    placed = dict((j.name, r) for j, r in s.schedule_batch(queue, avail, rp))
    assert placed == {"head": "r1", "needs_r0": "r0"}


def test_locality_batch_biggest_transfer_picks_first():
    s = _sched(LocalityBatchPolicy())
    rp = {"big": [("r1", "big")], "small": [("r1", "small")]}
    queue = [_job("small_dep", {"small": 10}), _job("big_dep", {"big": 10_000})]
    avail = {p.name: ["r0", "r1"] for p in queue}
    placed = dict((j.name, r) for j, r in s.schedule_batch(queue, avail, rp))
    # the big mover claims its holder even though it's later in the queue
    assert placed["big_dep"] == "r1"
    assert placed["small_dep"] == "r0"


def test_widest_first_orders_by_fanout():
    p = WidestFirstPolicy()
    q = [_job("leaf", fanout=0), _job("fanout", fanout=5)]
    ordered = p.order_queue(q, {}, {})
    assert ordered[0].name == "fanout"


def test_schedule_batch_commits_allocations():
    s = _sched(DataLocalityPolicy())
    placed = s.schedule_batch([_job("a"), _job("b"), _job("c")],
                              {n: ["r0", "r1"] for n in "abc"}, {})
    assert len(placed) == 2               # two free resources only
    assert all(s.resources[r].jobs for _, r in placed)
    # the unplaced job schedules once a resource frees
    from repro.core import JobStatus
    s.notify(placed[0][0].name, JobStatus.COMPLETED)
    more = s.schedule_batch([_job("c")], {"c": ["r0", "r1"]}, {})
    assert len(more) == 1
