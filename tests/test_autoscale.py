"""Autoscaler (PR 9 tentpole): elastic replicas over the unified
DeploymentPlane — scale-up from queue pressure, graceful drain,
preemptible (spot) revocation into the journal recovery path, and the
off-switch identity (no ``autoscale:`` block == the exact static pool).
"""
import json
import threading
import time

import pytest

from repro.configs import recovery_demo
from repro.core import (AutoscaleConfig, AutoscalePolicy, Autoscaler,
                        CheckpointConfig, DeploymentManager, DeploymentPlane,
                        FaultConfig, ModelSpec, Scheduler, SchedulerSnapshot,
                        StreamFlowExecutor, WorkflowService,
                        load_streamflow_file, replica_base)
from repro.core.scheduler import POLICIES
from repro.core.service import DeploymentPool
from repro.core.streamflow_file import Binding
from repro.core.workflow import Step, Workflow

MODELS = {"site": ModelSpec("site", "local",
                            {"services": {"svc": {"replicas": 2}}})}
BIND = [Binding("/", "site", "svc")]


def _models():
    return {"site": ModelSpec("site", "local",
                              {"services": {"svc": {"replicas": 2}}})}


def _wide_wf(n=12, sleep_s=0.03):
    """n independent slow steps: queue pressure on a small site."""
    wf = Workflow("wide")
    for i in range(n):
        def fn(inputs, ctx, i=i):
            time.sleep(sleep_s)
            return {f"out{i}": inputs["x"] + i}
        wf.add_step(Step(f"/work{i}", fn, {"x": "x"}, (f"out{i}",)))
    return wf


def _autoscaler(config=None, *, grace=None, models=None):
    dm = DeploymentManager(models or _models(), grace_period_s=grace)
    sched = Scheduler(POLICIES["data_locality"]())
    cfg = config or AutoscaleConfig(models={
        "site": AutoscalePolicy(min=1, max=3, target_queue_depth=1)})
    return Autoscaler(cfg, dm, sched), dm, sched


# -------------------------------------------------- SchedulerSnapshot (sat. 2)

def test_snapshot_is_frozen_and_typed():
    s = Scheduler(POLICIES["data_locality"]())
    snap = s.export_state()
    assert isinstance(snap, SchedulerSnapshot)
    with pytest.raises(Exception):
        snap.jobs = {}


def test_snapshot_to_dict_preserves_journaled_shape():
    """Without queue pressure or drains, to_dict() emits EXACTLY the
    historical two-key journal shape (the byte-identity guarantee)."""
    s = Scheduler(POLICIES["data_locality"]())
    s.register_resource("r0", "site", "svc", 4, 8.0)
    d = s.export_state().to_dict()
    assert sorted(d) == ["jobs", "resources"]
    assert d["resources"]["r0"] == {"model": "site", "service": "svc",
                                    "jobs": []}
    # dict-style indexing still works for historical consumers
    assert s.export_state()["resources"]["r0"]["model"] == "site"


def test_snapshot_carries_queue_depth_and_drains():
    s = Scheduler(POLICIES["data_locality"]())
    s.note_queue([("j1", "svc", ["site"]), ("j2", "svc", ["site"])])
    s.set_draining("site~1")
    snap = s.export_state()
    assert snap.queue_depth == {"site": 2}
    assert snap.service_queue_depth == {"svc": 2}
    assert snap.draining == ("site~1",)
    d = snap.to_dict()
    assert d["queue"]["models"] == {"site": 2}
    assert d["draining"] == ["site~1"]


def test_note_queue_namespaced_replacement():
    s = Scheduler(POLICIES["data_locality"]())
    s.note_queue([("a/j1", "svc", ["site"])], ns="a/")
    s.note_queue([("b/j1", "svc", ["site"])], ns="b/")
    assert s.export_state().queue_depth == {"site": 2}
    s.note_queue([], ns="a/")             # run a's report empties
    assert s.export_state().queue_depth == {"site": 1}


def test_draining_resources_take_no_placements():
    s = Scheduler(POLICIES["data_locality"]())
    s.register_resource("r0", "site", "svc", 4, 8.0)
    s.register_resource("r1", "site~1", "svc", 4, 8.0)
    s.set_draining("site~1")
    from repro.core.scheduler import JobDescription, Requirements
    job = JobDescription("j", Requirements(1, 1), {}, "svc")
    got = s.schedule(job, ["r1"], {})
    assert got is None                    # only the drained replica offered
    assert s.schedule(job, ["r0", "r1"], {}) == "r0"


# ------------------------------------------- DeploymentPlane protocol (sat. 1)

def test_protocol_unifies_both_managers():
    dm = DeploymentManager(_models())
    pool = DeploymentPool(_models())
    assert isinstance(dm, DeploymentPlane)
    assert isinstance(pool.lease_manager(), DeploymentPlane)


def test_non_pooled_lease_is_a_real_refcount():
    dm = DeploymentManager(_models(), grace_period_s=0.0)
    dm.lease("site")
    assert dm.lease_count("site") == 1
    assert dm.maybe_undeploy_idle() == []
    dm.release("site")
    assert "site" in dm.maybe_undeploy_idle()


def test_evict_idle_shim_warns():
    pool = DeploymentPool(_models())
    with pytest.warns(DeprecationWarning, match="maybe_undeploy_idle"):
        pool.evict_idle()


def test_drain_flag_survives_undeploy():
    dm = DeploymentManager(_models())
    dm.deploy("site")
    dm.drain("site", preempt=True)
    dm.undeploy("site")
    assert dm.is_draining("site")         # fault path must not resurrect
    dm.undrain("site")
    assert not dm.is_draining("site")


def test_replicas_of_lists_base_plus_live_clones():
    dm = DeploymentManager(_models())
    spec = dm.spec_of("site")
    dm.register(ModelSpec("site~1", spec.type, dict(spec.config)))
    dm.deploy("site~1")
    assert dm.replicas_of("site") == ["site", "site~1"]
    assert replica_base("site~1") == "site"
    dm.undeploy("site~1")
    assert dm.replicas_of("site") == ["site"]


# --------------------------------------------------------- config parsing

def test_autoscale_config_parsing():
    cfg = AutoscaleConfig.from_dict({
        "cooldown_s": 2, "models": {"site": {"min": 1, "max": 4,
                                             "target_queue_depth": 3,
                                             "preemptible": True}}})
    pol = cfg.models["site"]
    assert (pol.min, pol.max, pol.preemptible) == (1, 4, True)
    assert AutoscaleConfig.from_dict(None) is None
    assert AutoscaleConfig.from_dict({}) is None
    assert AutoscaleConfig.from_dict({"enabled": False,
                                      "models": {"site": {}}}) is None
    with pytest.raises(ValueError, match="unknown key"):
        AutoscaleConfig.from_dict({"modles": {}})
    with pytest.raises(ValueError, match="exceeds max"):
        AutoscaleConfig.from_dict({"models": {"site": {"min": 3, "max": 1}}})


def test_streamflow_file_autoscale_block_round_trips(tmp_path):
    doc = {
        "version": "v1.0",
        "models": {"site": {"type": "local",
                            "config": {"services": {"svc": {"replicas": 1}}}}},
        "tools": {"probe": {"outputs": {"ping": "int"}}},
        "workflows": {"w": {"type": "declarative",
                            "steps": {"/probe": {"tool": "probe"}},
                            "bindings": [{"step": "/probe",
                                          "target": {"model": "site",
                                                     "service": "svc"}}]}},
        "autoscale": {"cooldown_s": 1,
                      "models": {"site": {"min": 1, "max": 2}}},
    }
    cfg = load_streamflow_file(doc)
    assert cfg.autoscale["models"]["site"]["max"] == 2
    ex = StreamFlowExecutor.from_config(cfg)
    assert ex.autoscaler is not None
    assert ex.autoscaler.config.models["site"].max == 2


# --------------------------------------------------------------- control loop

def test_scale_up_on_queue_pressure_and_max_clamp():
    scaler, dm, sched = _autoscaler()
    sched.note_queue([(f"j{i}", "svc", ["site"]) for i in range(8)])
    scaler.tick()
    assert scaler.replicas("site") == ["site~1"]
    assert dm.is_deployed("site~1")
    assert dm.lease_count("site~1") == 1          # pinned against eviction
    # replica resources registered with the scheduler
    assert any(r.model == "site~1" for r in sched.resources.values())
    scaler.tick()
    scaler.tick()
    scaler.tick()
    assert scaler.live_count("site") == 3          # max=3 clamps
    assert scaler.scale_up_events == 2


def test_cooldown_damps_scaling():
    cfg = AutoscaleConfig(cooldown_s=60.0, models={
        "site": AutoscalePolicy(min=1, max=4, target_queue_depth=1)})
    scaler, dm, sched = _autoscaler(cfg)
    sched.note_queue([(f"j{i}", "svc", ["site"]) for i in range(9)])
    scaler.tick()
    scaler.tick()
    assert scaler.scale_up_events == 1             # second blocked by cooldown


def test_min_floor_ignores_cooldown():
    cfg = AutoscaleConfig(cooldown_s=60.0, models={
        "site": AutoscalePolicy(min=3, max=4)})
    scaler, dm, sched = _autoscaler(cfg)
    scaler.tick()
    assert scaler.live_count("site") == 3


def test_max_total_replicas_caps_fleet():
    cfg = AutoscaleConfig(max_total_replicas=1, models={
        "site": AutoscalePolicy(min=1, max=5, target_queue_depth=1)})
    scaler, dm, sched = _autoscaler(cfg)
    sched.note_queue([(f"j{i}", "svc", ["site"]) for i in range(20)])
    for _ in range(4):
        scaler.tick()
    assert scaler.total_extra_replicas() == 1


def test_external_sites_never_scale():
    models = {"hpc": ModelSpec("hpc", "local",
                               {"services": {"svc": {"replicas": 1}}},
                               external=True)}
    cfg = AutoscaleConfig(models={"hpc": AutoscalePolicy(min=2, max=4)})
    scaler, dm, sched = _autoscaler(cfg, models=models)
    scaler.tick()
    assert scaler.total_extra_replicas() == 0


def test_scale_down_drains_then_finalizes():
    scaler, dm, sched = _autoscaler()
    sched.note_queue([(f"j{i}", "svc", ["site"]) for i in range(8)])
    scaler.tick()
    rep = scaler.replicas("site")[0]
    sched.note_queue([])                           # pressure gone
    scaler.tick()                                  # drain decision
    assert dm.is_draining(rep) and sched.is_draining(rep)
    scaler.tick()                                  # quiet -> finalize
    assert not dm.is_deployed(rep)
    assert scaler.replicas("site") == []
    assert not any(r.model == rep for r in sched.resources.values())
    assert dm.is_draining(rep)                     # flag outlives teardown
    assert scaler.scale_down_events == 1


def test_preempt_revokes_immediately():
    scaler, dm, sched = _autoscaler()
    sched.note_queue([(f"j{i}", "svc", ["site"]) for i in range(8)])
    scaler.tick()
    rep = scaler.replicas("site")[0]
    scaler.preempt(rep)
    assert not dm.is_deployed(rep)
    assert dm.is_draining(rep)
    assert scaler.preempt_events == 1
    with pytest.raises(KeyError):
        scaler.preempt("site")                     # base is not a replica


def test_fresh_suffix_per_scale_up():
    """A re-grown replica gets a new name: stale drain flags from the
    previous generation can never block the new site."""
    scaler, dm, sched = _autoscaler()
    sched.note_queue([(f"j{i}", "svc", ["site"]) for i in range(8)])
    scaler.tick()
    scaler.preempt("site~1")
    scaler.tick()
    assert scaler.replicas("site") == ["site~2"]
    assert not dm.is_draining("site~2")


# --------------------------------------------------------- executor end-to-end

def test_executor_scales_up_and_completes():
    ex = StreamFlowExecutor(
        _models(), fault=FaultConfig(speculative=False),
        autoscale={"models": {"site": {"min": 1, "max": 3,
                                       "target_queue_depth": 1}}})
    res = ex.run(_wide_wf(), BIND, {"x": 1})
    assert len(res.outputs) == 12
    assert ex.autoscaler.scale_up_events > 0
    used = {e.model for e in res.events if e.status == "completed"}
    assert any("~" in m for m in used), f"no replica ever ran work: {used}"
    assert res.wasted_invocations == 0


def test_topology_clone_inherits_base_links():
    from repro.core.topology import MANAGEMENT, TopologyGraph
    g = TopologyGraph()
    g.add_site("site", mgmt_latency_s=0.5, mgmt_bandwidth_mbps=100.0)
    g.add_site("other")
    g.add_link("site", "other", latency_s=0.2)
    g.clone_site("site", "site~1")
    assert g.mgmt_link("site~1").latency_s == 0.5
    assert g.link("site~1", "other").latency_s == 0.2
    assert g.link("other", "site~1").latency_s == 0.2


def test_off_switch_identity(tmp_path):
    """No ``autoscale:`` block == the static pool's behaviour: the same
    journaled transitions, deployments and outputs (modulo wall-clock
    timestamps and which of the site's replicas a free slot gave)."""
    def run(tag, autoscale):
        jp = tmp_path / f"{tag}.jsonl"
        ex = StreamFlowExecutor(
            _models(), fault=FaultConfig(speculative=False),
            checkpoint=CheckpointConfig(journal_path=str(jp)),
            autoscale=autoscale)
        res = ex.run(recovery_demo.build_workflow(
            n_blocks=3, block_rows=16, rounds=2), BIND, {"seed": 3})
        lines, data = [], set()
        with open(jp) as f:
            for line in f:
                rec = json.loads(line)
                rec.pop("t", None)
                rec.pop("resource", None)
                rec.pop("dst_resource", None)
                if rec["kind"] in ("transfer", "token"):
                    # how often a token is copied between two replicas of
                    # one site follows the replica a slot gave: keep each
                    # token, site, path and tag once
                    data.add(json.dumps(rec, sort_keys=True))
                    continue
                lines.append(json.dumps(rec, sort_keys=True))
        timeline = [(m, e) for m, e, *_ in res.deployment_timeline]
        return sorted(lines), data, timeline, sorted(res.outputs)

    a = run("absent", None)
    b = run("disabled", {"enabled": False, "models": {"site": {"max": 2}}})
    assert a == b


# ----------------------------------------------- preemption + recovery (sat. 4)

def test_resume_after_preempt_reruns_only_lost_work(tmp_path):
    """Preempt a replica mid-run, crash the driver, resume: completed
    invocations never re-execute; only work lost on the revoked site
    (plus the never-run frontier) does."""
    jp = str(tmp_path / "preempt.jsonl")
    wf_args = dict(n=10, sleep_s=0.02)
    ex = StreamFlowExecutor(
        _models(), fault=FaultConfig(speculative=False),
        checkpoint=CheckpointConfig(journal_path=jp, include_payloads=True),
        autoscale={"models": {"site": {"min": 1, "max": 3,
                                       "target_queue_depth": 1}}})
    state = {"preempted": False}

    def hook(tick, completed):
        sc = ex.autoscaler
        if not state["preempted"] and sc.replicas("site") \
                and len(completed) >= 2:
            state["preempted"] = True
            sc.preempt(sc.replicas("site")[0])
            raise KeyboardInterrupt("driver dies mid-preempt")
    ex.tick_hook = hook
    with pytest.raises(KeyboardInterrupt):
        ex.run(_wide_wf(**wf_args), BIND, {"x": 1})
    assert state["preempted"], "preemption never triggered"

    from repro.core import ExecutionJournal
    st = ExecutionJournal.replay(jp)
    pre_completed = set(st.completed_steps)
    assert pre_completed
    assert st.preempted_models            # the planned preempt is journaled

    ex2 = StreamFlowExecutor(
        _models(), fault=FaultConfig(speculative=False),
        checkpoint=CheckpointConfig(journal_path=jp, include_payloads=True))
    res = ex2.resume(jp, workflow=_wide_wf(**wf_args), inputs={"x": 1})
    rerun = {e.step for e in res.events if e.status == "completed"}
    assert rerun.isdisjoint(pre_completed), \
        f"completed invocations re-ran: {rerun & pre_completed}"
    assert len(res.outputs) == 10


def test_preempt_mid_step_counts_wasted_work():
    """A replica revoked with work in flight: the dead attempt retries on
    a surviving site (never the revoked one) and is accounted wasted."""
    ex = StreamFlowExecutor(
        _models(), fault=FaultConfig(speculative=False),
        autoscale={"models": {"site": {"min": 1, "max": 2,
                                       "target_queue_depth": 1}}})
    state = {"preempted": False}

    def hook(tick, completed):
        sc = ex.autoscaler
        reps = sc.replicas("site")
        if not state["preempted"] and reps \
                and ex.scheduler.running_on(reps[0]):
            state["preempted"] = True
            sc.preempt(reps[0])
    ex.tick_hook = hook
    res = ex.run(_wide_wf(n=10, sleep_s=0.05), BIND, {"x": 1})
    assert len(res.outputs) == 10
    if state["preempted"]:
        assert res.wasted_invocations >= 1
        assert res.wasted_seconds > 0


# ------------------------------------------------- scale-down races (sat. 4)

def test_hammer_drain_vs_lease_admission():
    """Drain/undrain + idle eviction racing lease/job cycles: every
    started job lands on a live deployment, no exceptions leak."""
    dm = DeploymentManager(_models(), grace_period_s=0.0)
    errors = []
    stop = threading.Event()

    def worker():
        try:
            for _ in range(150):
                dm.lease("site")
                dm.job_started("site")
                if not dm.is_deployed("site"):
                    errors.append("job started on dead site")
                dm.job_finished("site")
                dm.release("site")
        except Exception as e:                     # noqa: BLE001
            errors.append(repr(e))

    def churner():
        while not stop.is_set():
            dm.drain("site")
            dm.undrain("site")
            dm.maybe_undeploy_idle()

    threads = [threading.Thread(target=worker) for _ in range(4)]
    ch = threading.Thread(target=churner)
    ch.start()
    [t.start() for t in threads]
    [t.join() for t in threads]
    stop.set()
    ch.join()
    assert errors == []


def test_service_autoscales_under_concurrent_submissions():
    """Pool-level autoscaler + concurrent tenants: scale events happen on
    the shared manager while runs lease the same sites, and every run
    completes."""
    svc = WorkflowService(
        _models(), fault=FaultConfig(speculative=False),
        deadlock_timeout_s=2.0,
        autoscale={"interval_s": 0.01,
                   "models": {"site": {"min": 1, "max": 3,
                                       "target_queue_depth": 1}}})
    assert svc.autoscaler is not None
    rids = [svc.submit(_wide_wf(n=6, sleep_s=0.02), BIND, {"x": i},
                       tenant=f"t{i % 2}") for i in range(4)]
    for rid in rids:
        info = svc.wait(rid, timeout=60)
        assert info.state == "COMPLETE", info
    svc.close()


def test_service_without_autoscale_unchanged():
    svc = WorkflowService(_models(), fault=FaultConfig(speculative=False))
    assert svc.autoscaler is None
    rid = svc.submit(_wide_wf(n=4, sleep_s=0.0), BIND, {"x": 1})
    assert svc.wait(rid, timeout=30).state == "COMPLETE"
    svc.close()
