#!/usr/bin/env python3
"""Find the FedBuff cell's knee: the highest upload rate (with the infer
stream riding along at its share) that the service sustains without a
growing backlog.

    python3 benchmarks/chip/sweep.py --workload fedbuff-upload-infer \\
        --seed 7 --seconds 10 --rates 20,40,60,80

One set-up, then one window per rate on the same service.  For each rate
it prints the offered and completed uploads per second, the upload and
infer tails, how late the generator sent, and the backlog's trend: the
median upload latency of the window's last third over its first third
(well above 1 means the queue grows through the window).  The cell's
rate is then fixed at about four fifths of the knee in its traffic file;
the benchmark itself never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
import run  # noqa: E402


def summarize(rate, sched, records, seconds, svc):
    lat, lag, failed = svc.tails(sched, records)
    ups = [(r["due"], r["recv"] - r["due"]) for r in records
           if r and r["kind"] == "upload" and r.get("ok")]
    done_in_window = sum(1 for r in records if r and r["kind"] == "upload"
                         and r.get("ok") and r["recv"] <= seconds)
    ups.sort()
    third = max(1, len(ups) // 3)
    first = statistics.median(x for _, x in ups[:third]) if ups else 0
    last = statistics.median(x for _, x in ups[-third:]) if ups else 0
    return {"rate": rate, "uploads": len(lat["upload"]),
            "completed_per_s": done_in_window / seconds,
            "upload_p50_ms": 1e3 * harness.percentile(lat["upload"], 50),
            "upload_p95_ms": 1e3 * harness.percentile(lat["upload"], 95),
            "infer_p95_ms": 1e3 * harness.percentile(lat["infer"], 95),
            "lag_p95_ms": 1e3 * harness.percentile(lag, 95),
            "failed": failed, "backlog_trend": last / max(first, 1e-9)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    args.trace = 0
    cell = harness.load_cell(args.workload)
    devices = harness.require_tpu(cell.chips)
    harness.enable_compile_cache()
    ctx = run.Context(cell, args, devices[0])
    svc = harness.load_module(HERE / "drivers" / "service.py", "svc")
    from repro.net import BackgroundServer
    from repro.serve import FederationService
    child = svc.Child()
    try:
        st = svc.setup(ctx, child, BackgroundServer, FederationService)
        try:
            for rate in [float(x) for x in args.rates.split(",")]:
                tr = dict(cell.traffic, upload_rate=rate)
                sched = svc.schedule(tr, cell.config, args.seconds,
                                     args.seed)
                records, _, aggs = svc.window(ctx, child, st, sched)
                row = summarize(rate, sched, records, args.seconds, svc)
                row["aggregations"] = aggs
                print(json.dumps(row), flush=True)
        finally:
            st["bg"].stop()
    finally:
        child.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
