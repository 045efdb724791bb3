"""Port/Token dataflow API: scatter/gather expansion, per-invocation
execution and placement, config-driven scatter blocks, and partial-scatter
crash recovery."""
import os
from collections import Counter

import pytest

from repro.core import (Binding, ExecutionJournal, FaultConfig, ModelSpec,
                        ScatterSpreadPolicy, Scheduler, Step,
                        StreamFlowExecutor, StreamFlowFileError, Workflow,
                        invocation_base, load_streamflow_file,
                        parse_token_ref, start_external_site,
                        stop_external_site, token_ref)
from repro.core.scheduler import JobDescription, Requirements
from repro.configs.paper_pipeline import streamflow_doc_scatter_hybrid

SCATTER_WF_ARGS = dict(train_steps=1, rows_per_sample=8, seq_len=32,
                       vocab=128, d_model=32)


# ------------------------------------------------------------------ token refs

def test_token_ref_roundtrip():
    assert token_ref("shard") == "shard"
    assert token_ref("shard", (3,)) == "shard[3]"
    assert token_ref("shard", (1, 2)) == "shard[1.2]"
    for ref in ("shard", "shard[3]", "shard[1.2]"):
        port, tag = parse_token_ref(ref)
        assert token_ref(port, tag) == ref
    # legacy flat token names never parse as tagged
    assert parse_token_ref("model3") == ("model3", ())
    assert parse_token_ref("weird]") == ("weird]", ())
    assert invocation_base("/count@3") == "/count"
    assert invocation_base("/count") == "/count"


# ------------------------------------------------------------------- expansion

def _scatter_wf(n=3):
    wf = Workflow("s")
    wf.add_step(Step("/src", lambda i, c: {"xs": list(range(10, 10 * n + 1,
                                                            10))},
                     {"seed": "seed"}, ("xs",), streams={"xs": n}))
    wf.add_step(Step("/sq", lambda i, c: {"ys": i["x"] * i["x"]},
                     {"x": "xs"}, ("ys",), scatter=("x",)))
    wf.add_step(Step("/sum", lambda i, c: {"total": sum(i["y"])},
                     {"y": "ys"}, ("total",), gather=("y",)))
    return wf


def test_expand_scalar_workflow_is_identity_shaped():
    wf = Workflow("d")
    wf.add_step(Step("/a", lambda i, c: {"t1": 1}, {}, ("t1",)))
    wf.add_step(Step("/b", lambda i, c: {"t2": 2}, {"x": "t1"}, ("t2",)))
    plan = wf.expand()
    assert sorted(plan.steps) == ["/a", "/b"]
    assert plan.steps["/b"].inputs == {"x": "t1"}
    assert plan.steps["/b"].outputs == ("t2",)
    assert plan.final_outputs() == ["t2"]
    assert plan.external_inputs() == []


def test_expand_scatter_gather_geometry():
    plan = _scatter_wf(3).expand()
    assert sorted(plan.steps) == ["/sq@0", "/sq@1", "/sq@2", "/src", "/sum"]
    assert plan.scatter_widths() == {"/sq": 3}
    assert plan.steps["/src"].outputs == ("xs[0]", "xs[1]", "xs[2]")
    assert plan.steps["/sq@1"].inputs == {"x": "xs[1]"}
    assert plan.steps["/sq@1"].outputs == ("ys[1]",)
    assert plan.steps["/sum"].inputs == {f"y[{k}]": f"ys[{k}]"
                                         for k in range(3)}
    assert plan.successors("/src") == ["/sq@0", "/sq@1", "/sq@2"]
    assert plan.predecessors("/sum") == ["/sq@0", "/sq@1", "/sq@2"]
    assert plan.external_inputs() == ["seed"]
    assert plan.final_outputs() == ["total"]


def test_expand_fireable_is_per_invocation():
    plan = _scatter_wf(3).expand()
    assert plan.fireable(["seed"], []) == ["/src"]
    # one element ready => exactly that invocation fires, not the group
    assert plan.fireable(["seed", "xs[1]"], ["/src"]) == ["/sq@1"]
    have = ["seed"] + [f"xs[{k}]" for k in range(3)] \
        + [f"ys[{k}]" for k in range(3)]
    assert plan.fireable(have, ["/src", "/sq@0", "/sq@1", "/sq@2"]) \
        == ["/sum"]


def test_nested_scatter_tags():
    wf = Workflow("n")
    wf.add_step(Step("/src", None, {}, ("a",), streams={"a": 2}))
    wf.add_step(Step("/mid", None, {"a": "a"}, ("b",), scatter=("a",),
                     streams={"b": 2}))
    wf.add_step(Step("/leaf", None, {"b": "b"}, ("c",), scatter=("b",)))
    plan = wf.expand()
    assert "/leaf@1.0" in plan.steps
    assert plan.steps["/leaf@1.0"].inputs == {"b": "b[1.0]"}
    assert plan.scatter_widths() == {"/mid": 2, "/leaf": 4}


def test_undeclared_stream_consumption_rejected():
    wf = Workflow("bad")
    wf.add_step(Step("/src", None, {}, ("xs",), streams={"xs": 2}))
    wf.add_step(Step("/use", None, {"x": "xs"}, ("y",)))
    with pytest.raises(ValueError, match="scatter .*or gather"):
        wf.expand()


def test_scatter_over_scalar_port_rejected():
    wf = Workflow("bad")
    wf.add_step(Step("/src", None, {}, ("x",)))
    wf.add_step(Step("/use", None, {"x": "x"}, ("y",), scatter=("x",)))
    with pytest.raises(ValueError, match="scalar"):
        wf.expand()


def test_zip_width_mismatch_rejected():
    wf = Workflow("bad")
    wf.add_step(Step("/a", None, {}, ("xs",), streams={"xs": 2}))
    wf.add_step(Step("/b", None, {}, ("zs",), streams={"zs": 3}))
    wf.add_step(Step("/use", None, {"x": "xs", "z": "zs"}, ("y",),
                     scatter=("x", "z")))
    with pytest.raises(ValueError, match="zip"):
        wf.expand()


def test_step_decl_errors():
    with pytest.raises(ValueError, match="not an input slot"):
        Step("/a", None, {}, ("y",), scatter=("nope",))
    with pytest.raises(ValueError, match="both scatter and gather"):
        Step("/a", None, {"x": "xs"}, ("y",), scatter=("x",), gather=("x",))
    with pytest.raises(ValueError, match="width"):
        Step("/a", None, {}, ("y",), streams={"y": -1})
    with pytest.raises(ValueError, match="width"):
        Step("/a", None, {}, ("y",), streams={"y": True})
    Step("/a", None, {}, ("y",), streams={"y": 0})   # empty streams are legal
    with pytest.raises(ValueError, match="not an .*output"):
        Step("/a", None, {}, ("y",), streams={"z": 2})
    with pytest.raises(ValueError, match="may not contain"):
        Step("/a@1", None, {})


def test_stream_length_mismatch_raises_at_runtime():
    wf = Workflow("short")
    wf.add_step(Step("/src", lambda i, c: {"xs": [1]},   # declares 2, emits 1
                     {}, ("xs",), streams={"xs": 2}))
    wf.add_step(Step("/use", lambda i, c: {"y": i["x"]}, {"x": "xs"},
                     ("y",), scatter=("x",)))
    ex = StreamFlowExecutor(
        {"m": ModelSpec("m", "local", {"services": {"s": {"replicas": 2}}})},
        fault=FaultConfig(speculative=False, max_retries=0))
    with pytest.raises(RuntimeError):
        ex.run(wf, [Binding("/", "m", "s")], {})


# ------------------------------------------------------------------- execution

def _pool(n=4):
    return {"m": ModelSpec("m", "local",
                           {"services": {"s": {"replicas": n}}})}


def test_scatter_gather_runs():
    ex = StreamFlowExecutor(_pool(), fault=FaultConfig(speculative=False))
    res = ex.run(_scatter_wf(3), [Binding("/", "m", "s")], {"seed": 0})
    assert res.outputs["total"] == 100 + 400 + 900
    done = [e.step for e in res.events if e.status == "completed"]
    assert sorted(done) == ["/sq@0", "/sq@1", "/sq@2", "/src", "/sum"]


def test_final_stream_port_collects_into_list():
    wf = Workflow("s")
    wf.add_step(Step("/src", lambda i, c: {"xs": [1, 2, 3]}, {}, ("xs",),
                     streams={"xs": 3}))
    wf.add_step(Step("/sq", lambda i, c: {"ys": i["x"] ** 2},
                     {"x": "xs"}, ("ys",), scatter=("x",)))
    ex = StreamFlowExecutor(_pool(), fault=FaultConfig(speculative=False))
    res = ex.run(wf, [Binding("/", "m", "s")], {})
    assert res.outputs["ys"] == [1, 4, 9]      # tag order, not finish order


def test_scattered_fn_sees_its_tag():
    seen = []
    wf = Workflow("t")
    wf.add_step(Step("/src", lambda i, c: {"xs": [0, 0, 0]}, {}, ("xs",),
                     streams={"xs": 3}))

    def fn(inputs, ctx):
        seen.append(ctx["tag"])
        return {"y": ctx["tag"][0]}
    wf.add_step(Step("/s", fn, {"x": "xs"}, ("y",), scatter=("x",)))
    ex = StreamFlowExecutor(_pool(), fault=FaultConfig(speculative=False))
    res = ex.run(wf, [Binding("/", "m", "s")], {})
    assert sorted(seen) == [(0,), (1,), (2,)]
    assert res.outputs["y"] == [0, 1, 2]


def test_multi_target_binding_spreads_across_sites():
    # 6 invocations, 2 slots per site: placements must use BOTH sites
    wf = Workflow("w")
    wf.add_step(Step("/src", lambda i, c: {"xs": list(range(6))}, {},
                     ("xs",), streams={"xs": 6}))

    def slow(inputs, ctx):
        import time
        time.sleep(0.05)
        return {"y": inputs["x"]}
    wf.add_step(Step("/work", slow, {"x": "xs"}, ("y",), scatter=("x",)))
    models = {
        "hpc": ModelSpec("hpc", "local",
                         {"services": {"c": {"replicas": 2}}}),
        "cloud": ModelSpec("cloud", "local",
                           {"services": {"r": {"replicas": 2}}}),
    }
    b = [Binding("/", "hpc", "c", (("cloud", "r"),))]
    ex = StreamFlowExecutor(models, fault=FaultConfig(speculative=False))
    res = ex.run(wf, b, {})
    assert res.outputs["y"] == list(range(6))
    used = {e.model for e in res.events
            if e.status == "completed" and e.step.startswith("/work")}
    assert used == {"hpc", "cloud"}


def test_scatter_spread_policy_balances_groups():
    s = Scheduler(ScatterSpreadPolicy())
    for i in range(3):
        s.register_resource(f"a{i}", "site_a", "svc", 2, 4)
        s.register_resource(f"b{i}", "site_b", "svc", 2, 4)
    avail = [f"a{i}" for i in range(3)] + [f"b{i}" for i in range(3)]
    placed = []
    for k in range(6):
        job = JobDescription(f"/w@{k}", Requirements(1, 1), {}, "svc",
                             group="/w", tag=(k,))
        placed.append(s.schedule(job, avail, {}))
    models = Counter("site_a" if r.startswith("a") else "site_b"
                     for r in placed)
    assert models == {"site_a": 3, "site_b": 3}


# ---------------------------------------------------- the paper pipeline, wide

@pytest.mark.slow
def test_paper_pipeline_scatter_32_samples_end_to_end():
    """Acceptance: the §5 pipeline via ``scatter:`` over 32 samples runs
    and spreads invocations across both sites."""
    doc = streamflow_doc_scatter_hybrid(n_samples=32, hpc_replicas=6,
                                        cloud_replicas=6, **SCATTER_WF_ARGS)
    cfg = load_streamflow_file(doc)
    entry = cfg.workflows["single-cell"]
    assert entry.workflow.expand().scatter_widths() == {
        "/count": 32, "/seurat": 32, "/singler": 32}
    ex = StreamFlowExecutor.from_config(cfg,
                                        fault=FaultConfig(speculative=False))
    res = ex.run(entry.workflow, entry.bindings, inputs={"seed": 0})
    assert res.outputs["summary"]["n_samples"] == 32
    assert len(res.outputs["stats"]) == 32
    count_sites = {e.model for e in res.events
                   if e.status == "completed"
                   and e.step.startswith("/count")}
    assert len(count_sites) >= 2               # one scatter, many sites


def test_scatter_block_from_yaml_drives_plain_builder():
    # the builder's own declarations aside, the scatter: block alone must
    # be able to mark slots — here it re-declares them (idempotent merge)
    doc = streamflow_doc_scatter_hybrid(n_samples=4, **SCATTER_WF_ARGS)
    cfg = load_streamflow_file(doc)
    wf = cfg.workflows["single-cell"].workflow
    assert wf.steps["/count"].scatter == ("shard",)
    assert wf.steps["/aggregate"].gather == ("labels",)


def test_binding_with_both_target_and_targets_rejected():
    doc = streamflow_doc_scatter_hybrid(n_samples=2, **SCATTER_WF_ARGS)
    doc["workflows"]["single-cell"]["bindings"][1]["target"] = {
        "model": "occam", "service": "cellranger"}
    with pytest.raises(StreamFlowFileError, match="not both"):
        load_streamflow_file(doc)


def test_scatter_block_rejects_unknown_step_and_slot():
    doc = streamflow_doc_scatter_hybrid(n_samples=2, **SCATTER_WF_ARGS)
    doc["workflows"]["single-cell"]["scatter"][0]["step"] = "/nope"
    with pytest.raises(StreamFlowFileError, match="unknown step"):
        load_streamflow_file(doc)
    doc = streamflow_doc_scatter_hybrid(n_samples=2, **SCATTER_WF_ARGS)
    doc["workflows"]["single-cell"]["scatter"][0]["over"] = ["nope"]
    with pytest.raises(StreamFlowFileError, match="no input slot"):
        load_streamflow_file(doc)


def test_schema_validates_scatter_block_keywords():
    doc = streamflow_doc_scatter_hybrid(n_samples=2, **SCATTER_WF_ARGS)
    doc["workflows"]["single-cell"]["scatter"][0]["over"] = []   # minItems
    with pytest.raises(StreamFlowFileError, match="at least 1"):
        load_streamflow_file(doc)
    doc = streamflow_doc_scatter_hybrid(n_samples=2, **SCATTER_WF_ARGS)
    doc["workflows"]["single-cell"]["scatter"][0]["step"] = "count"  # pattern
    with pytest.raises(StreamFlowFileError, match="pattern"):
        load_streamflow_file(doc)
    doc = streamflow_doc_scatter_hybrid(n_samples=2, **SCATTER_WF_ARGS)
    doc["checkpoint"] = {"journal_path": "x.jsonl", "max_payload_bytes": 0}
    with pytest.raises(StreamFlowFileError, match="minimum"):
        load_streamflow_file(doc)                                # minimum
    doc = streamflow_doc_scatter_hybrid(n_samples=2, **SCATTER_WF_ARGS)
    doc["workflows"]["single-cell"]["bindings"][1]["targets"] = []
    with pytest.raises(StreamFlowFileError, match="at least 1"):
        load_streamflow_file(doc)


# ------------------------------------------------------- partial-scatter crash

class _Crash(BaseException):
    pass


@pytest.fixture
def scatter_external_sites():
    doc = _external_doc("unused")
    for name, m in doc["models"].items():
        start_external_site(name, m["type"], m["config"])
    yield
    stop_external_site()


def _external_doc(journal_path, n_samples=8):
    doc = streamflow_doc_scatter_hybrid(n_samples=n_samples, hpc_replicas=3,
                                        cloud_replicas=3, **SCATTER_WF_ARGS)
    # external local sites: the user-managed deployments that outlive the
    # driver, which is what resume() re-attaches to
    doc["models"]["occam"]["type"] = "local"
    for m in doc["models"].values():
        m["external"] = True
    doc["checkpoint"] = {"journal_path": str(journal_path)}
    return doc


def test_mid_scatter_crash_resume_reruns_only_lost_invocations(
        tmp_path, scatter_external_sites):
    """Acceptance: resume after a mid-scatter crash re-runs only the lost
    invocations; journaled element tokens are trusted after Connector
    verification."""
    jp = tmp_path / "journal.jsonl"
    doc = _external_doc(jp)
    cfg = load_streamflow_file(doc)
    ex = StreamFlowExecutor.from_config(cfg,
                                        fault=FaultConfig(speculative=False))

    def hook(tick, completed):
        if len(completed) >= 5:
            raise _Crash()
    ex.tick_hook = hook
    entry = cfg.workflows["single-cell"]
    with pytest.raises(_Crash):
        ex.run(entry.workflow, entry.bindings, inputs={"seed": 0})

    state = ExecutionJournal.replay(str(jp))
    journaled = state.completed_steps
    assert len(journaled) >= 5
    assert any("@" in p for p in journaled)    # a partial scatter, really
    # element tokens journal with their scatter tags
    tagged = {t for t in state.token_tags if parse_token_ref(t)[1]}
    assert tagged and all(
        state.token_tags[t] == parse_token_ref(t)[1] for t in tagged)
    assert state.scatter_widths == {"/count": 8, "/seurat": 8,
                                    "/singler": 8}

    ex2 = StreamFlowExecutor.from_config(load_streamflow_file(doc),
                                         fault=FaultConfig(speculative=False))
    res = ex2.resume()                 # workflow + bindings from the WAL
    rerun = {e.step for e in res.events if e.status == "completed"}
    assert not rerun & journaled       # zero re-executed invocations
    plan = cfg.workflows["single-cell"].workflow.expand()
    assert rerun == set(plan.steps) - journaled
    assert res.outputs["summary"]["n_samples"] == 8


def test_journal_only_resume_with_config_driven_scatter(
        tmp_path, scatter_external_sites):
    # declare_scatter=False: the builder emits only stream widths, every
    # scatter/gather declaration lives in the YAML scatter: block.  A
    # journal-only resume must rebuild the SCATTERED workflow (the block
    # is journaled with the builder reference), or check_structure would
    # refuse the scalar plan
    jp = tmp_path / "journal.jsonl"
    doc = _external_doc(jp)
    doc["workflows"]["single-cell"]["config"]["args"][
        "declare_scatter"] = False
    cfg = load_streamflow_file(doc)
    wf = cfg.workflows["single-cell"].workflow
    assert wf.steps["/count"].scatter == ("shard",)   # block applied
    assert wf.builder_info["scatter"]                 # ...and journaled
    ex = StreamFlowExecutor.from_config(cfg,
                                        fault=FaultConfig(speculative=False))

    def hook(tick, completed):
        if len(completed) >= 4:
            raise _Crash()
    ex.tick_hook = hook
    with pytest.raises(_Crash):
        ex.run(wf, cfg.workflows["single-cell"].bindings,
               inputs={"seed": 0})
    journaled = ExecutionJournal.replay(str(jp)).completed_steps
    assert journaled

    ex2 = StreamFlowExecutor.from_config(load_streamflow_file(doc),
                                         fault=FaultConfig(speculative=False))
    res = ex2.resume()                 # workflow rebuilt purely from WAL
    rerun = {e.step for e in res.events if e.status == "completed"}
    assert not rerun & journaled
    assert res.outputs["summary"]["n_samples"] == 8


def test_resume_rejects_changed_scatter_width(tmp_path,
                                              scatter_external_sites):
    from repro.core import JournalError
    jp = tmp_path / "journal.jsonl"
    doc = _external_doc(jp)
    cfg = load_streamflow_file(doc)
    ex = StreamFlowExecutor.from_config(cfg,
                                        fault=FaultConfig(speculative=False))
    def hook(tick, completed):
        if len(completed) >= 2:
            raise _Crash()
    ex.tick_hook = hook
    entry = cfg.workflows["single-cell"]
    with pytest.raises(_Crash):
        ex.run(entry.workflow, entry.bindings, inputs={"seed": 0})
    # a 16-wide plan renames invocations and refs: resuming it against the
    # 8-wide journal must fail loudly, not skip the wrong invocations
    wide = load_streamflow_file(_external_doc(jp, n_samples=16))
    ex2 = StreamFlowExecutor.from_config(wide,
                                         fault=FaultConfig(speculative=False))
    with pytest.raises(JournalError, match="structure"):
        ex2.resume(workflow=wide.workflows["single-cell"].workflow)
