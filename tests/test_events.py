"""Typed event stream: run_stream's terminal state, backpressure without
loss, resume replay, and each invocation's lifecycle."""
import json
import random
import sys
import threading
import time

import pytest

from repro.configs import recovery_demo
from repro.configs.paper_pipeline import build_scatter_workflow
from repro.configs.paper_pipeline import build_workflow as build_scalar
from repro.core import (CheckpointConfig, EventSink, FaultConfig,
                        InvocationStateChanged, ModelSpec, RunCancelled,
                        StreamFlowExecutor, TokenAvailable, TransferRouted,
                        WorkflowCompleted, WorkflowStarted, serialize)
from repro.core.streamflow_file import Binding
from repro.core.workflow import parse_token_ref

SITE = {"site": ModelSpec("site", "local",
                          {"services": {"svc": {"replicas": 4}}})}
BIND = [Binding("/", "site", "svc")]


def _executor(**kw):
    kw.setdefault("fault", FaultConfig(speculative=False))
    return StreamFlowExecutor(SITE, **kw)


BUILDERS = {
    "scalar": lambda: build_scalar(n_chains=2, rows_per_chain=8,
                                   seq_len=16, train_steps=1, batch=2,
                                   vocab=64, d_model=16),
    "diamond": lambda: recovery_demo.build_workflow(
        n_blocks=3, block_rows=32, rounds=3),
    "scatter": lambda: build_scatter_workflow(
        n_samples=4, rows_per_sample=4, seq_len=16, train_steps=1,
        batch=2, vocab=64, d_model=16),
}


# ------------------------------------------------------- terminal equality

@pytest.mark.parametrize("name", sorted(BUILDERS))
def test_stream_terminal_state_equals_run_result(name):
    wf = BUILDERS[name]()
    ref = _executor().run(wf, BIND, {"seed": 7})

    wf2 = BUILDERS[name]()
    es = _executor().run_stream(wf2, BIND, {"seed": 7})
    events = list(es)

    assert isinstance(events[0], WorkflowStarted)
    terminals = [e for e in events if isinstance(e, WorkflowCompleted)]
    assert len(terminals) == 1 and events[-1] is terminals[0]
    term = terminals[0]
    assert sorted(term.outputs) == sorted(ref.outputs)
    assert sorted(term.result.outputs) == sorted(ref.outputs)
    assert es.result(timeout=5).outputs.keys() == ref.outputs.keys()
    # every invocation that ran to completion is visible in the stream
    done_paths = {e.path for e in events
                  if isinstance(e, InvocationStateChanged)
                  and e.state == "completed"}
    ref_done = {e.step for e in ref.events if e.status == "completed"}
    assert done_paths == ref_done


def test_stream_events_are_ordered_and_stamped():
    es = _executor().run_stream(BUILDERS["diamond"](), BIND, {"seed": 1})
    events = list(es)
    seqs = [e.seq for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert all(e.t > 0 for e in events)
    # lifecycle ordering per invocation: fireable < scheduled < running <
    # completed in stream order
    order = {"fireable": 0, "scheduled": 1, "running": 2, "completed": 3}
    by_path = {}
    for e in events:
        if isinstance(e, InvocationStateChanged) and e.state in order:
            by_path.setdefault(e.path, []).append(order[e.state])
    for path, states in by_path.items():
        assert states == sorted(states), path


def test_token_and_transfer_events_flow():
    es = _executor().run_stream(BUILDERS["diamond"](), BIND, {"seed": 2})
    events = list(es)
    tokens = [e for e in events if isinstance(e, TokenAvailable)]
    assert {t.token for t in tokens} >= {"digest0", "combined"}
    assert all(t.port and t.model for t in tokens)
    transfers = [e for e in events if isinstance(e, TransferRouted)]
    assert transfers and all(t.kind for t in transfers)


# ------------------------------------------------------------ backpressure

def test_lagging_consumer_loses_nothing():
    """buffer=2 with a consumer slower than the producer: emit() must
    block (not drop), so the full event story still arrives."""
    wf = BUILDERS["diamond"]()
    es = _executor().run_stream(wf, BIND, {"seed": 3}, buffer=2)
    events = []
    for ev in es:
        time.sleep(0.002)
        events.append(ev)
    assert isinstance(events[-1], WorkflowCompleted)
    seqs = [e.seq for e in events]
    # gap-free sequence: nothing was dropped while the consumer lagged
    assert seqs == list(range(len(events)))
    completed = [e for e in events if isinstance(e, InvocationStateChanged)
                 and e.state == "completed"]
    assert len(completed) == len(wf.steps)


def test_abandoning_consumer_does_not_wedge_the_run():
    es = _executor().run_stream(BUILDERS["diamond"](), BIND, {"seed": 4},
                                buffer=1)
    it = iter(es)
    next(it)
    it.close()                      # consumer walks away mid-run
    res = es.result(timeout=30)     # producer must not deadlock on emit
    assert "combined" in res.outputs


def test_concurrent_producers_arrive_in_seq_order():
    """Producers on several threads, each held up for a while as it
    enqueues: the consumer still sees every event, in the order of its
    seq."""
    sink = EventSink(maxsize=4)
    put, rng = sink._q.put, random.Random(0)

    def late_put(item, *a, **kw):
        time.sleep(rng.random() * 1e-3)
        return put(item, *a, **kw)
    sink._q.put = late_put

    def produce():
        for _ in range(20):
            sink.emit(WorkflowStarted())
    threads = [threading.Thread(target=produce) for _ in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        got = []
        for ev in sink.events():
            got.append(ev.seq)
            if len(got) == 12 * 20:
                break
        for t in threads:
            t.join(timeout=10)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert got == list(range(12 * 20))


def test_abandon_frees_producers_waiting_on_a_full_buffer():
    sink = EventSink(maxsize=1)
    sink.emit(WorkflowStarted())          # the buffer is full
    threads = [threading.Thread(target=sink.emit, args=(WorkflowStarted(),))
               for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.1)
    assert all(t.is_alive() for t in threads)
    sink.abandon()
    for t in threads:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in threads)


def test_unconsumed_stream_still_completes():
    # nobody iterates; default buffer is larger than the event count
    es = _executor().run_stream(BUILDERS["diamond"](), BIND, {"seed": 5})
    assert "combined" in es.result(timeout=30).outputs


# ------------------------------------------------------------- lifecycles

@pytest.mark.parametrize("name", ["scalar", "diamond", "scatter"])
def test_invocation_lifecycles_and_token_announcements(name):
    """Every invocation of the plan goes fireable -> scheduled -> running
    -> completed, and every token of the plan is announced once, with its
    tag (scatter shards keep their identity)."""
    wf = BUILDERS[name]()
    plan = wf.expand()
    events = list(_executor().run_stream(wf, BIND, {"seed": 6}))
    lifecycles = {}
    for e in events:
        if isinstance(e, InvocationStateChanged) and not e.speculative:
            lifecycles.setdefault(e.path, []).append(e.state)
    assert lifecycles == {
        path: ["fireable", "scheduled", "running", "completed"]
        for path in plan.steps}
    announced = sorted((e.token, e.port, tuple(e.tag)) for e in events
                       if isinstance(e, TokenAvailable))
    expected = sorted((t, *parse_token_ref(t))
                      for inv in plan.steps.values() for t in inv.outputs)
    assert announced == expected


@pytest.mark.parametrize("name", ["diamond", "scatter"])
def test_journal_step_records_repeat_across_runs(name, tmp_path):
    """Two runs of one workflow journal the same multiset of (path, state)
    step records and give the same outputs, whichever worker finishes
    first."""
    runs = []
    for i in range(2):
        journal = tmp_path / f"run{i}.jsonl"
        res = _executor(checkpoint=CheckpointConfig(
            journal_path=str(journal))).run(BUILDERS[name](), BIND,
                                            {"seed": 6})
        with open(journal) as f:
            recs = [json.loads(line) for line in f]
        steps = sorted((r["path"], r["state"]) for r in recs
                       if r["kind"] == "step")
        runs.append((steps, serialize(res.outputs)))
    assert runs[0] == runs[1]
    assert runs[0][0]                   # the journal did record the steps


# ------------------------------------------------------------ resume replay

class _Crash(RuntimeError):
    pass


def test_resume_replays_history_then_goes_live(tmp_path):
    journal = str(tmp_path / "run.jsonl")
    wf = recovery_demo.build_workflow(n_blocks=3, block_rows=32, rounds=3)
    ex = _executor(checkpoint=CheckpointConfig(journal_path=journal,
                                               include_payloads=True))

    def crash(tick, completed):
        if len(completed) >= 2:
            raise _Crash("driver killed")
    ex.tick_hook = crash
    with pytest.raises(_Crash):
        ex.run(wf, BIND, {"seed": 7})

    ex2 = _executor(checkpoint=CheckpointConfig(journal_path=journal,
                                                include_payloads=True))
    wf2 = recovery_demo.build_workflow(n_blocks=3, block_rows=32, rounds=3)
    es = ex2.resume_stream(journal, wf2, BIND, {"seed": 7})
    events = list(es)
    assert isinstance(events[0], WorkflowStarted) and events[0].resumed
    replayed = [e for e in events if e.replayed]
    live = [e for e in events[1:] if not e.replayed]
    # the replay block sits between the resumed WorkflowStarted and
    # every live event
    assert max(e.seq for e in replayed) < min(e.seq for e in live)
    assert any(isinstance(e, InvocationStateChanged)
               and e.state == "completed" for e in replayed)
    assert isinstance(events[-1], WorkflowCompleted)
    # replayed + live completions cover the whole workflow exactly once
    done = [e.path for e in events if isinstance(e, InvocationStateChanged)
            and e.state == "completed"]
    assert sorted(done) == sorted(wf2.steps)


# --------------------------------------------- timeline stability (sat. 6)

def test_timeline_rows_stable_under_equal_starts():
    """Equal-start events used to sort non-deterministically; the recording
    sequence number is the tiebreak now."""
    from repro.core.executor import JobEvent, RunResult
    events = []
    for i, step in enumerate(["/b", "/a", "/c"]):
        e = JobEvent(step=step, model="m", resource="r", start=1.0,
                     end=2.0, attempt=0, status="completed")
        e.seq = i
        events.append(e)
    res = RunResult(outputs={}, events=events, transfers=[],
                    deployment_timeline=[], wall_seconds=1.0)
    rows = [r[0] for r in res.timeline_rows()]
    assert rows == ["/b", "/a", "/c"]
    # and it is genuinely stable: shuffling input order changes nothing
    res2 = RunResult(outputs={}, events=list(reversed(events)),
                     transfers=[], deployment_timeline=[], wall_seconds=1.0)
    assert [r[0] for r in res2.timeline_rows()] == rows


# -------------------------------------------------------- executor cancel

def test_executor_cancel_raises_runcancelled_and_journals(tmp_path):
    journal = str(tmp_path / "cancel.jsonl")
    wf = recovery_demo.build_workflow(n_blocks=3, block_rows=32, rounds=3)
    ex = _executor(checkpoint=CheckpointConfig(journal_path=journal,
                                               include_payloads=True))

    def hook(tick, completed):
        if len(completed) >= 2:
            ex.cancel()
    ex.tick_hook = hook
    with pytest.raises(RunCancelled):
        ex.run(wf, BIND, {"seed": 7})

    from repro.core import ExecutionJournal
    state = ExecutionJournal.replay(journal)
    assert state.cancelled
    assert state.cancelled_pending
    assert set(state.cancelled_pending) <= set(wf.steps)
    assert not (set(state.cancelled_pending)
                & set(state.completed_steps))
