"""Bring-up check: the workflow engine and a full-width model on one TPU.

    python chip_smoke.py

Everything runs in this one process, which holds the chip:

  1. the engine: the paper's §5 single-cell scatter workflow
     (``examples/singlecell_declarative.yaml``, 32 samples) through the
     checker, the analyzer and ``StreamFlowExecutor``, with ``/count`` and
     ``/seurat`` jitted on the TPU; checked against the same tool
     factories called directly;
  2. a server: ``repro.launch.serve`` at minicpm-2b's published widths
     (random weights from a seed); checked for complete, in-vocabulary
     and greedy-deterministic answers;
  3. a kernel: minicpm-2b prefill through the Pallas flash-attention
     kernel, checked against the reference attention path.

Every check raises, so a failure exits non-zero.  The last line of
standard output is ``{"ok": true, "device": {...}}``, printed only when
every phase passed.  There is no CPU fallback: without a TPU the script
exits non-zero before any phase runs.
"""
from __future__ import annotations

import importlib
import json
import os
import sys
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core import (StreamFlowExecutor, TokenAvailable,  # noqa: E402
                        WorkflowStarted, analyzer, deserialize,
                        load_streamflow_file)
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.steps import make_prefill_step  # noqa: E402

WORKFLOW_FILE = os.path.join(ROOT, "examples", "singlecell_declarative.yaml")
# /mkfastq + 32 x (/count, /seurat, /singler) + /aggregate
EXPECTED_INVOCATIONS = 98
N_SAMPLES = 32
SAMPLES = (0, N_SAMPLES - 1)        # compared against the plain reference
ENGINE_RTOL = 1e-3

ARCH = "minicpm-2b"
REQUESTS, SLOTS, PROMPT_LEN, GEN = 8, 4, 128, 16
PREFILL_BATCH, PREFILL_LEN = 4, 128
LOGIT_TOL = 2e-2                    # bf16 activations, 40 layers
SEED = 0


class CheckFailed(RuntimeError):
    pass


def check(cond, msg: str):
    if not cond:
        raise CheckFailed(msg)


def say(*parts):
    print(*parts, flush=True)


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); this check has no CPU fallback")
    return dev


def require_kernel(compiled_text: str):
    check("tpu_custom_call" in compiled_text,
          "pallas prefill compiled without a tpu_custom_call: no kernel ran")


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# --------------------------------------------------------------- phase 1
def _tool(cfg, name):
    impl = cfg.tools[name].implementation
    factory = getattr(importlib.import_module(impl["module"]), impl["factory"])
    return factory(**impl.get("args", {}))


def phase_engine():
    say("[phase 1] engine: single-cell scatter workflow")
    cfg = load_streamflow_file(WORKFLOW_FILE, check=True)
    report = analyzer.analyze(cfg)
    check(not report.errors(), f"analyzer errors: {report.errors()}")
    (name, entry), = cfg.workflows.items()

    ex = StreamFlowExecutor.from_config(cfg)
    # RunResult.outputs holds only the workflow's output ports (stats and
    # summary); a sample's clusters and labels live in the site stores
    # while the run is live.  A one-event buffer keeps the run from
    # finishing (and undeploying) ahead of this reader.
    stream = ex.run_stream(entry.workflow, entry.bindings, {"seed": SEED},
                           buffer=1)
    planned, seen = None, {}
    for ev in stream:
        if isinstance(ev, WorkflowStarted):
            planned = ev.invocations
        elif (isinstance(ev, TokenAvailable) and ev.tag
              and ev.port in ("clusters", "labels") and ev.tag[0] in SAMPLES):
            store = ex.deployment.get_connector(ev.model).store(ev.resource)
            seen[ev.token] = deserialize(store.get(ev.token))
    result = stream.result()

    # exactly-once: one completion per invocation, nothing failed or retried
    check(planned == EXPECTED_INVOCATIONS,
          f"plan has {planned} invocations, want {EXPECTED_INVOCATIONS}")
    bad = [(e.step, e.status, e.attempt) for e in result.events
           if e.attempt or e.status.startswith(("failed", "preempted"))]
    check(not bad, f"failed or retried attempts: {bad}")
    done = Counter(e.step for e in result.events if e.status == "completed")
    check(len(done) == EXPECTED_INVOCATIONS and set(done.values()) == {1},
          f"{len(done)} invocations completed, counts {set(done.values())}")
    site = {e.step: e.model for e in result.events if e.status == "completed"}

    summary = result.outputs["summary"]
    stats = result.outputs["stats"]
    check(summary["n_samples"] == N_SAMPLES,
          f"summary n_samples={summary['n_samples']}")
    losses = np.array([s["losses"] for s in stats], np.float64)
    check(np.isfinite(losses).all(), "non-finite training loss")

    # plain reference: the same tool factories, called directly, on the same
    # device; /count inside the site mesh when the engine ran it on the mesh
    # site, as MeshConnector.run does
    mesh_sites = {m for m, spec in cfg.models.items() if spec.type == "mesh"}
    mesh = jax.make_mesh((jax.device_count(), 1), ("data", "model"))
    shards = _tool(cfg, "mkfastq")({"seed": SEED}, {})["shard"]
    count, seurat, singler = (_tool(cfg, n)
                              for n in ("count", "seurat", "singler"))
    for i in SAMPLES:
        ctx = {"tag": (i,)}
        if site[f"/count@{i}"] in mesh_sites:
            with mesh:
                trained = count({"shard": shards[i]}, ctx)
        else:
            trained = count({"shard": shards[i]}, ctx)
        clusters = seurat({"shard": shards[i], "model": trained["model"]},
                          ctx)["clusters"]
        labels = singler({"clusters": clusters}, ctx)["labels"]
        got_c, got_l = seen[f"clusters[{i}]"], seen[f"labels[{i}]"]
        check(rel_err(stats[i]["losses"], trained["stats"]["losses"])
              <= ENGINE_RTOL, f"sample {i}: losses differ")
        check(rel_err(got_c["centroids"], clusters["centroids"])
              <= ENGINE_RTOL, f"sample {i}: centroids differ")
        check(np.array_equal(got_l["cluster_types"],
                             labels["cluster_types"]),
              f"sample {i}: labels differ")
        say(f"  sample {i}: /count on {site[f'/count@{i}']}, losses "
            f"{trained['stats']['losses']}, labels "
            f"{labels['cluster_types'].tolist()} match the reference")

    per_site = Counter((e.step.split("@")[0], e.model) for e in result.events
                       if e.status == "completed")
    say(f"  invocations: {sum(done.values())} completed, 0 failed, "
        f"0 retried (plan {planned})")
    for (step, model), n in sorted(per_site.items()):
        say(f"    {step:<11s} on {model:<11s} {n}")
    for kind, s in sorted(ex.data.transfer_summary().items()):
        say(f"  transfers {kind:<12s} n={int(s['n'])} bytes={int(s['bytes'])}")
    say(f"  summary: n_samples={summary['n_samples']} type_counts="
        f"{summary['type_counts'].tolist()}")


# --------------------------------------------------------------- phase 2
def phase_serve(cfg, argv):
    say(f"[phase 2] serve {cfg.name}: d_model={cfg.d_model} "
        f"layers={cfg.n_layers} vocab={cfg.vocab_size}")
    done = serve.main(argv)
    check(len(done) == REQUESTS, f"{len(done)}/{REQUESTS} requests returned")
    for r in done:
        toks = np.asarray(r.generated)
        check(len(toks) == GEN, f"request {r.rid}: {len(toks)} tokens")
        check(((toks >= 0) & (toks < cfg.vocab_size)).all(),
              f"request {r.rid}: token outside [0, {cfg.vocab_size})")

    # greedy determinism: the first batch again, same seed, same shapes
    first = sorted(done, key=lambda r: r.rid)[:SLOTS]
    again = serve.serve(cfg, [serve.Request(r.rid, r.prompt, GEN)
                              for r in first],
                        slots=SLOTS, ctx_len=PROMPT_LEN + GEN, seed=SEED)
    by_rid = {r.rid: r.generated for r in again}
    for r in first:
        check(by_rid.get(r.rid) == r.generated,
              f"request {r.rid} not deterministic: {r.generated} vs "
              f"{by_rid.get(r.rid)}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say(f"  {len(done)}/{REQUESTS} requests x {GEN} tokens in vocab; "
        f"{len(first)} re-served identically")
    say(f"  peak_bytes_in_use={peak} ({peak / 2**30:.3f} GiB)"
        if peak is not None else "  peak_bytes_in_use: not reported")


# --------------------------------------------------------------- phase 3
def phase_kernel(cfg):
    say(f"[phase 3] {cfg.name} prefill B={PREFILL_BATCH} S={PREFILL_LEN}: "
        f"pallas vs reference attention")
    params = serve.make_params(cfg, SEED)
    tokens = jax.random.randint(jax.random.key(SEED + 1),
                                (PREFILL_BATCH, PREFILL_LEN), 0,
                                cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens}
    logits = {}
    for mode in ("pallas", "reference"):
        compiled = jax.jit(make_prefill_step(cfg, kernel_mode=mode)).lower(
            params, batch).compile()
        if mode == "pallas":
            require_kernel(compiled.as_text())
        logits[mode] = np.asarray(compiled(params, batch)[0], np.float32)
    p, r = logits["pallas"], logits["reference"]
    check(np.isfinite(p).all() and np.isfinite(r).all(), "non-finite logits")
    err = np.abs(p - r)
    within = np.mean(err <= LOGIT_TOL + LOGIT_TOL * np.abs(r))
    argmax_eq = np.mean(p.argmax(-1) == r.argmax(-1))
    say(f"  logits {p.shape}: max|d|={err.max():.6g} "
        f"rel_l2={rel_err(p, r):.6g} within {LOGIT_TOL} abs+rel: "
        f"{within:.6f}; argmax equal on {argmax_eq:.3f} of rows")
    check(within == 1.0 or argmax_eq >= 0.99,
          "pallas and reference prefill logits disagree")


def main():
    dev = require_tpu()
    cache_dir = setup_compile_cache()
    events = Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: events.update([event])
        if event.startswith("/jax/compilation_cache/") else None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}; compile cache {cache_dir}")

    phase_engine()
    cfg = get_arch(ARCH)
    phase_serve(cfg, ["--arch", ARCH, "--requests", str(REQUESTS),
                      "--slots", str(SLOTS), "--prompt-len", str(PROMPT_LEN),
                      "--gen", str(GEN), "--seed", str(SEED)])
    phase_kernel(cfg)
    say(f"compile cache: {events['/jax/compilation_cache/cache_hits']} hits, "
        f"{events['/jax/compilation_cache/cache_misses']} misses")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
