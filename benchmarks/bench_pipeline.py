"""The pipelined executor's makespan on the paper's topologies.

The executor schedules the whole ready queue per tick, issues transfers
asynchronously (token movement for step N+1 overlaps compute of step N)
and stages inputs onto slot-starved sites ahead of time.  This benchmark
measures the makespan on:

  fig8   — full-HPC (one shared-store site): no WAN hops to hide;
  fig9   — hybrid HPC+cloud with NO shared data space and a simulated WAN
           link between the sites and the management node, with fewer cloud
           slots than chains: the R3 two-step copies hide behind compute.

Also compares the queue-aware policies (backfill / locality_batch /
widest_first, beyond-paper) against plain data-locality.
"""
from __future__ import annotations

from benchmarks.common import WF_ARGS, run_doc, warmup
from repro.configs.paper_pipeline import (streamflow_doc_full_hpc,
                                          streamflow_doc_hybrid)

# WAN model for fig9: each management<->site hop costs 50 ms + payload time,
# so an R3 two-step copy (site -> mgmt -> site) costs >= 100 ms
LINK = {"link_latency_s": 0.05, "link_bandwidth_mbps": 200.0}
CLOUD_SLOTS = 2            # fewer cloud workers than chains => queue forms

QUEUE_POLICIES = ["data_locality", "backfill", "locality_batch",
                  "widest_first"]


def _fig8_doc():
    return streamflow_doc_full_hpc(**WF_ARGS)


def _fig9_doc():
    doc = streamflow_doc_hybrid(**WF_ARGS)
    for model in doc["models"].values():
        model["config"].update(LINK)
    doc["models"]["garr_cloud"]["config"]["services"]["r_env"][
        "replicas"] = CLOUD_SLOTS
    return doc


def _one(doc_fn, **kw) -> dict:
    ex, res, wall = run_doc(doc_fn(), **kw)
    rows = res.timeline_rows()
    span = max(r[3] for r in rows) - min(r[2] for r in rows)
    xfer = sum(r.seconds for r in ex.data.transfers)
    return {"wall_s": round(wall, 3), "makespan_s": round(span, 3),
            "transfer_s": round(xfer, 3), "dedup_hits": ex.data.dedup_hits}


def _median(runs) -> dict:
    runs = sorted(runs, key=lambda r: r["makespan_s"])
    return runs[len(runs) // 2]


def _compare(doc_fn, *, repeats: int = 3, **variants) -> dict:
    """Interleave the variants' runs (A,B,A,B,...) so CPU-state drift over
    the benchmark hits every variant equally; median-of-N per variant."""
    acc = {name: [] for name in variants}
    for _ in range(repeats):
        for name, kw in variants.items():
            acc[name].append(_one(doc_fn, **kw))
    return {name: _median(runs) for name, runs in acc.items()}


def run(verbose=True):
    warmup()
    rows = []
    for label, doc_fn in (("fig8", _fig8_doc), ("fig9", _fig9_doc)):
        got = _compare(doc_fn, pipelined={})
        for mode, r in got.items():
            rows.append({"topology": label, "mode": mode, **r})
    queue = _compare(_fig9_doc, repeats=1,
                     **{f"pipelined+{p}": {"policy": p}
                        for p in QUEUE_POLICIES[1:]})
    for mode, r in queue.items():
        rows.append({"topology": "fig9", "mode": mode, **r})

    if verbose:
        hdr = ["topology", "mode", "wall_s", "makespan_s", "transfer_s",
               "dedup_hits"]
        print(" | ".join(f"{h:>18s}" for h in hdr))
        for r in rows:
            print(" | ".join(f"{str(r[h]):>18s}" for h in hdr))
    return rows


def main(argv=None):
    run()


if __name__ == "__main__":
    main()
