"""Resident entries: a token of device arrays stays on the device inside
a site's store, and becomes bytes only when something asks for bytes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CacheConfig, CheckpointConfig, ConnectorCopyKind,
                        DataManager, DeploymentManager, FaultConfig,
                        LocalConnector, ModelSpec, ObjectStore,
                        StreamFlowExecutor, content_digest, serialize,
                        start_external_site, stop_external_site, tracing)
from repro.core.persistence import ExecutionJournal
from repro.core.service import ServiceConfig, WorkflowService
from repro.core.streamflow_file import Binding
from repro.core.workflow import Step, Workflow


def _value():
    return {"w": jnp.arange(6.0).reshape(2, 3), "n": 3, "name": "m",
            "pair": (jnp.ones(4, jnp.int32), np.float32(0.5), None)}


def _spans(tmp_path, fn):
    """``fn()`` inside a profiler session; returns its result and spans."""
    with jax.profiler.trace(str(tmp_path / "trace")):
        with tracing.run_log() as log:
            out = fn()
        spans = log.spans()
    return out, spans


def test_round_trip_hands_the_same_arrays_in_fresh_containers(tmp_path):
    st = ObjectStore("site")
    v = _value()

    def round_trip():
        st.put_value("tok", v)
        return st.get_value("tok"), st.get_value("tok")
    (a, b), spans = _spans(tmp_path, round_trip)
    for got in (a, b):
        assert got is not v and got["pair"] is not v["pair"]
        assert got["w"] is v["w"] and got["pair"][0] is v["pair"][0]
        assert (got["n"], got["name"], got["pair"][2]) == (3, "m", None)
    assert a is not b
    names = [s.name for s in spans]
    assert "sf:encode" not in names and "sf:cas_put" not in names
    assert "sf:decode" not in names
    (hold,) = [s for s in spans if s.name == "sf:hold"]
    nbytes = 6 * 4 + 4 * 4 + 4
    assert hold.attrs == {"bytes": nbytes, "key": "tok"}
    assert (st.resident_puts, st.resident_bytes, st.materialized) \
        == (1, nbytes, 0)
    assert (st.bytes_in, st.bytes_out) == (nbytes, 2 * nbytes)
    assert st.unique_bytes() == 0


def test_copy_to_another_store_materializes_once(tmp_path):
    site = LocalConnector("s", {"services": {"x": {"replicas": 3}}})
    site.deploy()
    v = _value()
    src = site.store("s/x/0")
    src.put_value("tok", v)

    def copies():
        n = [site.copy("tok", "tok", ConnectorCopyKind.REMOTE_TO_REMOTE,
                       source_remote="s/x/0", dest_remote=f"s/x/{i}")
             for i in (1, 2)]
        mgmt = ObjectStore("management")
        site.copy("tok", "tok", ConnectorCopyKind.REMOTE_TO_LOCAL,
                  source_remote="s/x/0", local_store=mgmt)
        return n, mgmt
    (n, mgmt), spans = _spans(tmp_path, copies)
    raw = serialize(v)
    assert n == [len(raw)] * 2
    assert src.materialized == 1
    (enc,) = [s for s in spans if s.name == "sf:encode"]
    assert enc.attrs == {"bytes": len(raw), "lazy": True}
    for st in (src, site.store("s/x/1"), site.store("s/x/2"), mgmt):
        assert st.get("tok") == raw
        assert st.digest_of("tok") == content_digest(raw)
    # the copies are bytes; the source still hands out its arrays
    dst = site.store("s/x/1")
    assert dst.resident_puts == 0 and dst.get_value("tok")["w"] is not v["w"]
    assert src.get_value("tok")["w"] is v["w"]
    assert src.materialized == 1


def test_size_and_exists_answer_without_materializing():
    st = ObjectStore("site")
    v = _value()
    st.put_value("tok", v)
    before = (st.bytes_in, st.bytes_out)
    assert st.exists("tok") and st.paths() == ["tok"]
    assert st.size("tok") == 6 * 4 + 4 * 4 + 4
    assert st.materialized == 0 and (st.bytes_in, st.bytes_out) == before
    # the content-addressed probes see materialized entries only
    digest = content_digest(serialize(v))
    assert not st.has_digest(digest)
    assert st.digest_of("tok") == digest and st.has_digest(digest)
    assert st.materialized == 1 and (st.bytes_in, st.bytes_out) == before
    # once made, the bytes are what a copy would move
    assert st.size("tok") == len(serialize(v))


@pytest.mark.parametrize("value", [
    {"w": jnp.ones(3), "host": np.ones(3)},       # a numpy array leaf
    {"w": jnp.ones(3), "ids": {1, 2}},            # any other object leaf
    {"n": 3, "x": 2.5, "names": ["a", "b"]},      # scalars only
    np.arange(4.0),
    {1: jnp.ones(3), "meta": 2},                 # keys that do not sort
], ids=["numpy-leaf", "object-leaf", "scalars-only", "numpy-array",
        "unsortable-keys"])
def test_other_values_take_the_bytes_path(value):
    st = ObjectStore("site")
    st.put_value("tok", value)
    raw = serialize(value)
    assert st.resident_puts == 0 and st.resident_bytes == 0
    assert st.unique_bytes() == len(raw) == st.size("tok") == st.bytes_in
    assert st.digest_of("tok") == content_digest(raw)
    got = st.get_value("tok")
    assert got is not value
    assert serialize(got) == raw


def test_a_donated_leaf_is_refused_by_name():
    st = ObjectStore("occam:shared")
    v = _value()
    st.put_value("run/model[2]", v)
    v["w"].delete()
    for read in (st.get_value, st.get, st.digest_of):
        with pytest.raises(RuntimeError,
                           match=r"'occam:shared'.*'run/model\[2\]'"):
            read("run/model[2]")
    # demotion drops what can no longer be read
    assert st.demote() == 0 and not st.exists("run/model[2]")


def test_release_keeps_made_bytes_and_makes_none():
    st = ObjectStore("site")
    st.put_value("a/made", _value())
    st.put_value("a/held", _value())
    kept = _value()
    st.put_value("b/held", kept)
    raw = st.get("a/made")
    assert st.release("a/") == 1
    assert st.materialized == 1
    assert st.paths() == ["a/made", "b/held"]
    assert st.get("a/made") == raw and st.size("a/made") == len(raw)
    assert st.get_value("b/held")["w"] is kept["w"]


def test_a_later_put_replaces_a_resident_entry():
    st = ObjectStore("site")
    st.put_value("tok", _value())
    st.put("tok", serialize("bytes now"))
    assert st.get_value("tok") == "bytes now" and st.size("tok") > 0
    st.put_value("tok", {"a": jnp.zeros(2)})
    assert st.size("tok") == 8 and st.unique_bytes() == 0
    st.delete("tok")
    assert not st.exists("tok") and st.size("tok") == -1


def test_the_planner_elides_on_the_store_and_moves_bytes_across_sites():
    dm = DeploymentManager({
        "hpc": ModelSpec("hpc", "local", {
            "services": {"x": {"replicas": 2}}, "shared_store": True}),
        "cloud": ModelSpec("cloud", "local", {
            "services": {"y": {"replicas": 1}}})})
    hpc, cloud = dm.deploy("hpc"), dm.deploy("cloud")
    d = DataManager(dm)
    v = _value()
    src = hpc.store("hpc/x/0")
    src.put_value("tok", v)
    d.add_remote_path_mapping("hpc", "hpc/x/0", "tok")
    rec = d.transfer_sync("tok", "hpc", "hpc/x/1")
    assert rec.kind in ("elided", "staging") and rec.bytes == src.size("tok")
    assert src.materialized == 0
    rec = d.transfer_sync("tok", "cloud", "cloud/y/0")
    raw = serialize(v)
    assert rec.kind == "two-step" and rec.bytes == 2 * len(raw)
    assert src.materialized == 1
    dst = cloud.store("cloud/y/0")
    assert dst.get("tok") == raw and dst.resident_puts == 0
    assert d.token_size("tok") == src.size("tok")
    d.close()


# ------------------------------------------------------------ the engine
SITE = {"site": ModelSpec("site", "local", {
    "shared_store": True, "services": {"svc": {"replicas": 2}}})}
BIND = [Binding("/", "site", "svc")]


def _chain(seen=None):
    """/make puts device arrays; /use, on the same store, returns a host
    number, so the workflow's output is collected as bytes as ever."""
    def make(inputs, ctx):
        x = jnp.arange(8.0) * inputs["seed"]
        if seen is not None:
            seen["store"] = ctx["connector"].store(ctx["resource"])
            seen["x"] = x
        return {"arr": {"x": x, "seed": int(inputs["seed"])}}

    def use(inputs, ctx):
        if seen is not None:
            seen["same"] = inputs["arr"]["x"] is seen["x"]
        return {"total": float(inputs["arr"]["x"].sum())}
    wf = Workflow("resident-chain")
    wf.add_step(Step("/make", make, {"seed": "seed"}, ("arr",)))
    wf.add_step(Step("/use", use, {"arr": "arr"}, ("total",)))
    return wf


def test_a_batch_run_hands_arrays_over_and_its_teardown_encodes_nothing(
        tmp_path):
    seen = {}
    ex = StreamFlowExecutor(SITE, fault=FaultConfig(speculative=False))
    res, _ = _spans(tmp_path, lambda: ex.run(_chain(seen), BIND,
                                             {"seed": 3}))
    spans = res.spans
    assert res.outputs["total"] == float(np.arange(8.0).sum() * 3)
    assert seen["same"]
    assert [s.attrs["key"] for s in spans if s.name == "sf:hold"] == ["arr"]
    assert not [s for s in spans if s.name == "sf:encode"
                and s.attrs.get("lazy")]
    store = seen["store"]
    assert store.resident_puts == 1 and store.materialized == 0
    assert store.paths() == []          # cleared with its site


def test_a_retried_consumer_that_donated_its_input_gets_the_named_error():
    """A step must not donate its inputs: the retry reads the same stored
    arrays, and is refused by the token's name before its body runs."""
    bodies = []

    def make(inputs, ctx):
        return {"arr": {"x": jnp.arange(8.0) * inputs["seed"]}}

    def use(inputs, ctx):
        bodies.append(ctx["resource"])
        inputs["arr"]["x"].delete()         # what a donating jit does
        raise OSError("lost after donating")
    wf = Workflow("donating")
    wf.add_step(Step("/make", make, {"seed": "seed"}, ("arr",)))
    wf.add_step(Step("/use", use, {"arr": "arr"}, ("total",)))
    ex = StreamFlowExecutor(SITE, fault=FaultConfig(
        speculative=False, max_retries=1, backoff_s=0.0))
    with pytest.raises(RuntimeError, match="failed after retries") as info:
        ex.run(wf, BIND, {"seed": 1})
    assert len(bodies) == 1
    assert isinstance(info.value.__cause__, RuntimeError)
    assert "'site:shared'" in str(info.value.__cause__)
    assert "token 'arr'" in str(info.value.__cause__)
    assert "donated" in str(info.value.__cause__)


def _service(**kw):
    return WorkflowService(
        SITE, service=ServiceConfig(max_concurrent=1, pool_enabled=True,
                                    keepalive_s=60.0),
        max_workers=4, transfer_workers=2, deadlock_timeout_s=10.0, **kw)


def test_a_pooled_run_demotes_its_entries_at_its_end():
    """At its end a pooled run lets go of its device arrays and makes no
    bytes for them: no later run asks for a finished run's keys by path.
    Its bytes outputs stay, as ever."""
    seen = {}
    svc = _service()
    try:
        rid = svc.submit(_chain(seen), BIND, {"seed": 2})
        assert svc.wait(rid, timeout=60).state == "COMPLETE"
        store = seen["store"]
        assert store.resident_puts == 1 and store.materialized == 0
        assert f"{rid}/arr" not in store.paths()
        assert store.get_value(f"{rid}/total") == float(
            np.arange(8.0).sum() * 2)
    finally:
        svc.close()


def test_an_external_site_keeps_a_runs_tokens_as_bytes_after_it():
    """An external site outlives the run, and resume re-attaches to
    its tokens by path: at detach they are made into bytes."""
    cfg = {"shared_store": True, "services": {"svc": {"replicas": 2}}}
    start_external_site("ext", "local", cfg)
    try:
        seen = {}
        ex = StreamFlowExecutor(
            {"ext": ModelSpec("ext", "local", cfg, external=True)},
            fault=FaultConfig(speculative=False))
        ex.run(_chain(seen), [Binding("/", "ext", "svc")], {"seed": 4})
        store = seen["store"]
        assert store.resident_puts == 1 and store.materialized == 1
        got = store.get_value("arr")
        assert got["x"] is not seen["x"]
        np.testing.assert_array_equal(got["x"], np.arange(8.0) * 4)
    finally:
        stop_external_site("ext")


def test_a_memo_hit_on_resident_outputs_still_verifies(tmp_path):
    svc = _service(cache=CacheConfig(
        index_path=str(tmp_path / "cache.jsonl")))
    try:
        seen = {}
        r1 = svc.submit(_chain(seen), BIND, {"seed": 5})
        first = svc.result(r1, timeout=60)
        # the memo record made the bytes; the run's end kept them
        key = f"{r1}/arr"
        assert seen["store"].materialized == 1
        assert seen["store"].get_value(key)["x"] is not seen["x"]
        r2 = svc.submit(_chain(), BIND, {"seed": 5})
        second = svc.result(r2, timeout=60)
        assert second.outputs == first.outputs
        assert [e.status for e in second.events] == ["memoized"] * 2
        assert svc.cache.hits >= 2
    finally:
        svc.close()



def test_journal_payloads_inline_small_resident_tokens_only(tmp_path):
    """A resident token over the journal's payload limit is not made into
    bytes just to be refused; one under it is journaled as its bytes."""
    seen = {}

    def make(inputs, ctx):
        seen["store"] = ctx["connector"].store(ctx["resource"])
        return {"big": jnp.zeros(4096), "small": jnp.arange(4.0)}

    def use(inputs, ctx):
        return {"total": float(inputs["big"].sum() + inputs["small"].sum())}
    wf = Workflow("journaled")
    wf.add_step(Step("/make", make, {"seed": "seed"}, ("big", "small")))
    wf.add_step(Step("/use", use, {"big": "big", "small": "small"},
                     ("total",)))
    journal = tmp_path / "journal.jsonl"
    ex = StreamFlowExecutor(SITE, fault=FaultConfig(speculative=False),
                            checkpoint=CheckpointConfig(
                                journal_path=str(journal), fsync=False,
                                include_payloads=True,
                                max_payload_bytes=1024))
    assert ex.run(wf, BIND, {"seed": 1}).outputs["total"] == 6.0
    assert seen["store"].materialized == 1          # "small" only
    payloads = ExecutionJournal.replay(str(journal)).payloads
    assert "big" not in payloads
    assert payloads["small"] == serialize(jnp.arange(4.0))
