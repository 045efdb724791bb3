"""The engine loop's incremental tick: a placement pass that asks the
policy only about jobs it could place, is not run again while nothing it
reads has changed, and a ready set kept as tokens arrive.  Each is held
to a brute-force rendition of the whole-queue pass or the plan scan that
it replaces."""
import random
import threading
import time

import jax
import pytest

from repro.core import (POLICIES, DataLocalityPolicy, FaultConfig,
                        JobAllocation, JobDescription, JobStatus, ModelSpec,
                        Scheduler, StreamFlowExecutor)
from repro.core.streamflow_file import Binding
from repro.core.topology import TopologyGraph
from repro.core.workflow import ReadySet, Requirements, Step, Workflow


# ------------------------------------------------- placement equivalence
def whole_queue_pass(s, queue, available, remote_paths, draining):
    """The pass as it was: every job of the (ordered) queue is put to the
    policy, each placement committed before the next is asked."""
    if draining:
        available = {
            n: [r for r in res if s.resources.get(r) is None
                or s.resources[r].model not in draining]
            for n, res in available.items()}
    select = getattr(s.policy, "select_batch", None)
    if select is not None:
        picked = select(list(queue), available, remote_paths, s.jobs,
                        s.resources)
        for job, resource in picked:
            _occupy(s, job, resource)
        return picked
    hook = getattr(s.policy, "order_queue", None)
    ordered = (hook(list(queue), remote_paths, s.resources) if hook
               else list(queue))
    picked = []
    for job in ordered:
        resource = s.policy.get_resource(job, available.get(job.name, ()),
                                         remote_paths, s.jobs, s.resources)
        if resource is not None:
            picked.append((job, resource))
            _occupy(s, job, resource)
    return picked


def _occupy(s, job, resource):
    s.jobs[job.name] = JobAllocation(job, resource)
    s.resources[resource].jobs.append(job.name)


class World:
    """A random scheduler state and queue, built the same way twice from
    one seed: one copy for the pass under test, one for the brute force."""

    def __init__(self, seed, policy, topology):
        rng = random.Random(seed)
        self.models = [f"m{i}" for i in range(rng.randint(1, 3))]
        self.res = [(f"{m}/r{k}", m, rng.choice([1, 2]),
                     rng.choice([1.0, 4.0]))
                    for m in self.models for k in range(rng.randint(1, 4))]
        names = [r[0] for r in self.res]
        self.busy = rng.sample(names, rng.randint(0, len(names)))
        self.draining = {m for m in self.models if rng.random() < 0.2}
        self.tokens = [f"t{i}" for i in range(rng.randint(0, 8))]
        self.remote_paths = {t: [(r, t) for r in rng.sample(
            names, rng.randint(0, min(2, len(names))))] for t in self.tokens}
        # jobs of one binding share their availability list, as in the
        # executor; one list also names a resource nobody registered
        self.bindings = [rng.sample(names + ["ghost"],
                                    rng.randint(0, len(names) + 1))
                         for _ in range(rng.randint(1, 3))]
        self.rng = rng
        self.n_jobs = 0
        self.queue = []
        self.available = {}
        self.add_jobs(rng.randint(0, 24))
        self.policy, self.topology = policy, topology

    def add_jobs(self, n):
        rng = self.rng
        for _ in range(n):
            name = f"run/j{self.n_jobs}"
            self.n_jobs += 1
            deps = {t: rng.randint(1, 1000) for t in rng.sample(
                self.tokens, rng.randint(0, len(self.tokens)))}
            self.queue.append(JobDescription(
                name, Requirements(cores=rng.choice([1, 2]),
                                   memory_gb=rng.choice([1.0, 2.0])),
                deps, "svc", fanout=rng.randint(0, 5),
                group=rng.choice(["/a", "/b"])))
            self.available[name] = rng.choice(self.bindings)

    def scheduler(self):
        s = Scheduler(POLICIES[self.policy]())
        if self.topology:
            g = TopologyGraph()
            for i, m in enumerate(self.models):
                g.add_site(m, mgmt_latency_s=0.01 * (i + 1),
                           mgmt_bandwidth_mbps=100.0 * (i + 1))
            if len(self.models) > 1:
                g.add_link(self.models[0], self.models[1], latency_s=0.05,
                           bandwidth_mbps=10.0)
            s.set_topology(g)
        for name, model, cores, mem in self.res:
            s.register_resource(name, model, "svc", cores, mem)
        for i, r in enumerate(self.busy):
            _occupy(s, JobDescription(f"other/x{i}",
                                      Requirements(1, 1.0)), r)
        for m in self.draining:
            s.set_draining(m)
        return s


@pytest.mark.parametrize("topology", [False, True])
@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_pass_places_as_the_whole_queue_pass(policy, topology):
    """Over random queues, resources, requirements, holders and drains,
    and rounds of releases, arrivals and new replicas between passes: the
    same jobs on the same resources in the same order, the same state
    after, and a skipped pass (nothing placed at this version, the same
    queue) would have placed nothing."""
    for seed in range(40):
        w = World(seed, policy, topology)
        s, ref = w.scheduler(), w.scheduler()
        idle_at = None
        for rnd in range(6):
            mark = (s.version, len(w.queue), w.n_jobs)
            if mark == idle_at:
                assert whole_queue_pass(ref, w.queue, w.available,
                                        w.remote_paths, w.draining) == []
            stats = {}
            got = s.schedule_batch(w.queue, w.available, w.remote_paths,
                                   stats)
            want = whole_queue_pass(ref, w.queue, w.available,
                                    w.remote_paths, w.draining)
            assert [(j.name, r) for j, r in got] == \
                [(j.name, r) for j, r in want], (seed, rnd)
            assert {n: r.jobs for n, r in s.resources.items()} == \
                {n: r.jobs for n, r in ref.resources.items()}
            assert getattr(s.policy, "_next", None) == \
                getattr(ref.policy, "_next", None)
            assert 0 <= stats["probed"] <= len(w.queue)
            if not got:
                idle_at = mark
            placed = {j.name for j, _ in got}
            w.queue = [j for j in w.queue if j.name not in placed]
            _between_passes(w, s, ref, seed * 100 + rnd)


def _between_passes(w, s, ref, seed):
    """One random event on both copies, or none: a release, a new
    replica, a drain flag, new jobs, or a token copy (which moves no
    version: a pass that placed nothing still places nothing)."""
    rng = random.Random(seed)
    event = rng.choice(["none", "release", "release", "register", "drain",
                        "arrive", "replica"])
    running = sorted(n for n, a in s.jobs.items()
                     if a.status is JobStatus.RUNNING)
    if event == "release" and running:
        name = rng.choice(running)
        status = rng.choice([JobStatus.COMPLETED, JobStatus.FAILED])
        s.notify(name, status)
        ref.notify(name, status)
    elif event == "register":
        m = rng.choice(w.models)
        name = f"{m}/new{seed}"
        for sch in (s, ref):
            sch.register_resource(name, m, "svc", 2, 4.0)
        w.bindings[0].append(name)
    elif event == "drain":
        m = rng.choice(w.models)
        flag = m not in w.draining
        (w.draining.add if flag else w.draining.discard)(m)
        for sch in (s, ref):
            sch.set_draining(m, flag)
    elif event == "arrive":
        w.add_jobs(rng.randint(1, 4))
    elif event == "replica" and w.tokens:
        t = rng.choice(w.tokens)
        w.remote_paths[t].append((rng.choice(w.res)[0], t))


def test_free_only_pass_stops_at_the_last_free_resource():
    """A wide queue on 8 one-job resources with one free: one probe."""
    s = Scheduler(DataLocalityPolicy())
    names = [f"r{i}" for i in range(8)]
    for n in names:
        s.register_resource(n, "m", "svc", 1, 4.0)
    for i, n in enumerate(names[1:]):
        _occupy(s, JobDescription(f"busy{i}", Requirements(1, 1.0)), n)
    queue = [JobDescription(f"j{i}", Requirements(1, 1.0))
             for i in range(400)]
    stats = {}
    got = s.schedule_batch(queue, {j.name: names for j in queue}, {}, stats)
    assert [(j.name, r) for j, r in got] == [("j0", "r0")]
    assert stats["probed"] == 1


def test_version_moves_with_what_a_placement_reads():
    s = Scheduler(DataLocalityPolicy())
    v = s.version
    s.register_resource("r0", "m", "svc", 1, 4.0)
    assert s.version > v
    v = s.version
    s.register_resource("r0", "m", "svc", 1, 4.0)       # already known
    s.set_draining("m", False)                          # already clear
    assert s.version == v
    job = JobDescription("j", Requirements(1, 1.0))
    assert s.schedule(job, ["r0"], {}) == "r0"
    assert s.version > v
    v = s.version
    s.notify("j", JobStatus.COMPLETED)
    assert s.version > v
    v = s.version
    s.set_draining("m")
    assert s.version > v
    v = s.version
    s.forget_model("m")
    assert s.version > v


# ---------------------------------------------------------- the executor
SITE8 = {"site": ModelSpec("site", "local",
                           {"services": {"svc": {"replicas": 8}}})}
BIND = [Binding("/", "site", "svc")]


def _fan(n):
    """/src -> n invocations -> /sink."""
    wf = Workflow("fan")
    wf.add_step(Step("/src", lambda i, c: {f"x{k}": k for k in range(n)},
                     {"s": "seed"}, tuple(f"x{k}" for k in range(n))))
    for k in range(n):
        wf.add_step(Step(f"/w{k}",
                         lambda i, c, k=k: {f"y{k}": i["x"] * 2 + i["s"]},
                         {"x": f"x{k}", "s": "seed"}, (f"y{k}",)))
    wf.add_step(Step("/sink", lambda i, c: {"z": sum(i.values())},
                     {f"y{k}": f"y{k}" for k in range(n)}, ("z",)))
    return wf


def _schedule_spans(res):
    return [s for s in res.spans if s.name == "sf:schedule"]


def test_wide_fan_out_probes_few_jobs_per_placement(tmp_path):
    """About 400 invocations onto 8 one-job resources: the policy is asked
    about at most 3 jobs per placement (the whole-queue pass asked about
    every waiting job), and the outputs and the exactly-once count hold."""
    n = 400
    ex = StreamFlowExecutor(SITE8, fault=FaultConfig(speculative=False))
    with jax.profiler.trace(str(tmp_path / "trace")):
        res = ex.run(_fan(n), BIND, {"seed": 1})
    assert res.outputs["z"] == sum(2 * k + 1 for k in range(n))
    done = [e.step for e in res.events if e.status == "completed"]
    assert len(done) == len(set(done)) == n + 2
    spans = _schedule_spans(res)
    assert max(s.attrs["waiting"] for s in spans) >= n - 8
    probed = sum(s.attrs.get("probed", 0) for s in spans)
    placed = sum(s.attrs.get("placed", 0) for s in spans)
    assert placed == n + 2
    assert probed <= 3 * placed


def test_shared_scheduler_release_by_another_run_ends_the_skip(tmp_path):
    """Another run holds the one shared resource: this run's passes are
    skipped while nothing changes, and that run's release lets this one
    place at once."""
    sched = Scheduler(DataLocalityPolicy())
    site = {"site": ModelSpec("site", "local",
                              {"services": {"svc": {"replicas": 1}}})}
    holder = Workflow("holder")
    holder.add_step(Step("/hold", lambda i, c: time.sleep(1.0) or {"h": 1},
                         {}, ("h",)))
    other = StreamFlowExecutor(site, scheduler=sched, namespace="a/",
                               fault=FaultConfig(speculative=False))
    ex = StreamFlowExecutor(site, scheduler=sched, namespace="b/",
                            fault=FaultConfig(speculative=False),
                            deadlock_timeout_s=10.0)
    held = {}
    with jax.profiler.trace(str(tmp_path / "trace")):
        t = threading.Thread(target=lambda: held.update(
            res=other.run(holder, BIND, {})))
        t.start()
        deadline = time.time() + 10
        while not sched.has_running() and time.time() < deadline:
            time.sleep(0.005)
        assert sched.has_running()
        res = ex.run(_fan(2), BIND, {"seed": 1})
    t.join(timeout=30)
    assert not t.is_alive()
    assert res.outputs["z"] == 1 + 3
    spans = _schedule_spans(res)
    first = next(i for i, s in enumerate(spans) if s.attrs.get("placed"))
    assert any(s.attrs.get("skipped") for s in spans[:first])
    hold_end = next(e.end for e in held["res"].events
                    if e.status == "completed")
    starts = [e.start for e in res.events if e.step != "/src"]
    src = next(e for e in res.events if e.step == "/src")
    assert src.start >= hold_end
    assert min(starts) >= src.end


# ------------------------------------------------------------ ready set
def _random_plan(seed):
    rng = random.Random(seed)
    wf = Workflow(f"dag{seed}")
    outputs = ["ext0", "ext1"]
    for k in range(30):
        ins = rng.sample(outputs, rng.randint(0, min(3, len(outputs))))
        # a token may feed two ports of one step
        if ins and rng.random() < 0.2:
            ins.append(ins[0])
        made = tuple(f"o{k}_{j}" for j in range(rng.randint(1, 2)))
        wf.add_step(Step(f"/s{k}", lambda i, c: {},
                         {f"p{j}": t for j, t in enumerate(ins)}, made))
        outputs += made
    return wf.expand()


@pytest.mark.parametrize("seed", range(12))
def test_ready_set_hands_out_what_the_plan_scan_finds(seed):
    """Tokens arriving in random order: each ``take`` gives exactly the
    not-yet-started invocations whose inputs all exist, in plan order."""
    plan = _random_plan(seed)
    rng = random.Random(seed)
    done = {"ext0", "ext1"}
    ready = ReadySet(plan, done)
    started, finished = [], set()
    while len(finished) < len(plan.steps):
        got = ready.take()
        want = [p for p, inv in plan.steps.items() if p not in started
                and all(t in done for t in inv.inputs.values())]
        assert got == want
        assert got == plan.fireable(done, started)
        started += got
        path = rng.choice([p for p in started if p not in finished])
        finished.add(path)
        for token in plan.steps[path].outputs:
            done.add(token)
            ready.add(token)
