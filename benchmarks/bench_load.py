"""Latency-under-load benchmark for the federation wire — the
``load_results`` payload block.

A thin sizing wrapper over the multi-process drivers in
``repro.launch.federate_load``: a real asyncio server process, N real
client processes on real sockets.  Two cells, gated by
``benchmarks/ci_gate.py`` against the committed baseline (HARD on
correctness, warn-only on timing — the repo-wide two-tier policy):

* ``wire-sync-equivalence`` — the DESIGN.md §6 anchor crossed over the
  wire: M=K / ``max_staleness=0`` / in-order localhost uploads must
  reproduce the sync twin's ``Federation.run()`` trajectory.
  ``final_param_dev`` hard-fails at the repo-wide 1e-5 bound — encode
  → TCP → decode must be numerically invisible at fp32.
* ``wire-load`` — >= 4 concurrent client processes hammering the
  single-aggregation-worker front-end while inference interleaves:
  p50/p95/p99 upload + infer RTT and aggregations/s are the SLO
  columns (warn-only trend); hard-fails on any rejection reason
  outside ``REJECT_REASONS``, zero aggregations, zero inference
  calls, or fewer than 4 processes.

Usage (what .github/workflows/ci.yml runs):

    PYTHONPATH=src python -m benchmarks.bench_load --quick \\
        --out experiments/bench_load_ci.json
    python -m benchmarks.ci_gate experiments/bench_load_ci.json \\
        benchmarks/baselines/BENCH_scenarios_ci.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax

from repro.api.spec import (DataSpec, ExecutionSpec, FederationSpec,
                            ModelSpec, ScheduleSpec, ServingSpec)
from repro.launch.federate_load import run_anchor, run_load


def base_wire_spec(*, vocab, topics, hidden, num_clients, docs, batch,
                   buffer_size, max_staleness) -> FederationSpec:
    # lr below the tiny-config divergence point (the bench_serve sizing
    # rule): the anchor compares absolute param devs
    return FederationSpec(
        name="bench-load",
        model=ModelSpec(vocab=vocab, topics=topics, hidden=hidden),
        data=DataSpec(num_clients=num_clients, docs_per_node=docs,
                      val_docs_per_node=8),
        schedule=ScheduleSpec(mode="buffered_async",
                              buffer_size=buffer_size,
                              max_staleness=max_staleness,
                              staleness_policy="polynomial"),
        execution=ExecutionSpec(exec_mode="loop", batch_size=batch,
                                learning_rate=2e-4),
        serving=ServingSpec(host="127.0.0.1", port=0,
                            wire_precision="fp32"))


def run_bench(args) -> dict:
    size = dict(vocab=64, topics=4, hidden=16, num_clients=8, docs=40,
                batch=16) if args.quick else \
        dict(vocab=200, topics=8, hidden=32, num_clients=12, docs=120,
             batch=32)
    sweeps = 2 if args.quick else 4
    anchor_sweeps = 2 if args.quick else 4
    procs = args.procs
    spec = base_wire_spec(**size, buffer_size=2,
                          max_staleness=2 * size["num_clients"])
    anchor = run_anchor(spec, sweeps=anchor_sweeps)
    anchor["cell"] = "wire-sync-equivalence"
    load = run_load(spec, procs=procs, sweeps=sweeps,
                    infer_every=3, infer_batch=4 if args.quick else 16)
    load["cell"] = "wire-load"
    results = [anchor, load]
    print(f"[wire-sync-equivalence] dev={anchor['final_param_dev']:.1e} "
          f"aggs={anchor['aggregations']} "
          f"upload_p50={anchor.get('upload_p50_s', float('nan')):.4f}s")
    print(f"[wire-load] procs={load['procs']} "
          f"{load['accepted']}/{load['uploads']} accepted "
          f"aggs/s={load['aggs_per_s']:.2f} "
          f"upload_p50={load.get('upload_p50_s', float('nan')):.4f}s "
          f"p99={load.get('upload_p99_s', float('nan')):.4f}s "
          f"infer_p50={load.get('infer_p50_s', float('nan')):.4f}s "
          f"rejections={load['rejections']}")
    # the parent never initializes a JAX backend: the server process is
    # the only one that may hold the accelerator (launch/federate_load.py)
    return {"setup": {"jax": jax.__version__,
                      "quick": bool(args.quick), "sweeps": sweeps,
                      "anchor_sweeps": anchor_sweeps, "procs": procs,
                      **size},
            "load_results": results}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI sizing (tiny model, 2 sweeps)")
    ap.add_argument("--procs", type=int, default=4,
                    help="concurrent client processes (the CI SLO cell "
                         "needs >= 4)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    payload = run_bench(args)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.out}")
    return payload


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
