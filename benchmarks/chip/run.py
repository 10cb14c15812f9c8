#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic
mix, limits and per-layer metric readers are files under
``benchmarks/chip`` found by name (``harness.py``).  The run fails, and
prints no result, without a TPU, with fewer chips than the cell asks
for, or on a device kind missing from ``peaks.json``.  Set-up (corpus,
weights, build, compile, the checked first steps) ends where the window
starts; ``--seconds`` of work follow; then the plain reference decides
``correct``.  The last stdout line is the result object; the compared
numbers with their limits are the last stderr lines and the last key of
that object, after ``window_compiles``, the count of compiles inside the
window (0 in a sound run).  ``--trace 1`` records the window with the
profiler and reports the per-layer metrics instead of the end-to-end
ones; a metric whose reader finds nothing is left out, with a line on
stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
from harness import BenchError, log  # noqa: E402


class Context:
    """What a driver gets: the cell, the arguments, the clocks, and the
    trace and memory hooks."""

    def __init__(self, cell, args, device, plant=None):
        self.cell, self.args, self.device = cell, args, device
        self.phases = harness.Phases(T_START)
        # Python and JAX imports and the accelerator runtime's start
        self.phases.mark("backend")
        self.clock = harness.CompileClock()
        self.plant = plant
        self.setup_s = None

    def setup_done(self) -> None:
        self.setup_s = self.phases.since_start()

    def start_trace(self) -> str:
        import jax
        d = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(d)
        return d

    def stop_trace(self, d: str) -> str:
        import jax
        jax.profiler.stop_trace()
        return d

    def memory_peak(self) -> int:
        stats = self.device.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))


class MetricContext:
    """What a per-layer reader gets."""

    def __init__(self, cell, outcome, summary, peaks):
        self.cell, self.outcome, self.trace, self.peaks = \
            cell, outcome, summary, peaks
        self.counters = outcome.counters


def per_layer(cell, outcome, summary, peaks):
    out = {}
    for i, m in enumerate(cell.per_layer):
        reader = harness.load_module(
            harness.find(cell.dirs, "metrics", f"{m['name']}.py"),
            f"metric_{i}")
        value = reader.read(MetricContext(cell, outcome, summary, peaks))
        if value is None:
            log(f"metric {m['name']}: nothing to read in this run; left "
                "out of the result")
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(args, *, root=harness.ROOT, dirs=None, plant=None,
            devices=None) -> dict:
    """One run of a cell; returns the result object.  The tests pass
    ``devices`` to skip the look for a chip, a fixture ``root`` and
    ``dirs`` searched before this directory, and ``plant`` to break the
    program underneath."""
    cell = harness.load_cell(args.workload, root,
                             list(dirs or []) + [HERE])
    if devices is None:
        devices = harness.require_tpu(cell.chips)
    peaks = harness.peak_of(devices[0].device_kind, cell.dirs)
    harness.enable_compile_cache(root)
    ctx = Context(cell, args, devices[0], plant=plant)
    driver = harness.load_module(
        harness.find(cell.dirs, "drivers", f"{cell.traffic['driver']}.py"),
        f"driver_{cell.traffic['driver']}")
    outcome = driver.run(ctx)
    result = {"correct": harness.judge(outcome.checks),
              "attempted": int(outcome.attempted),
              "failed": int(outcome.failed)}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(outcome.memory_peak_bytes)}
    if args.trace:
        import tracefile
        summary = tracefile.summarize(outcome.trace_path)
        shutil.rmtree(outcome.trace_path, ignore_errors=True)
        result["metrics"] = per_layer(cell, outcome, summary, peaks)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    else:
        metrics = {m["name"]: {"value": float(outcome.end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": float(ctx.setup_s), "unit": "s"}
        result["metrics"] = metrics
        result["device"] = device
    # nothing may compile inside the window; a run that did says so here
    result["window_compiles"] = int(outcome.counters["window_compiles"])
    result["checks"] = outcome.checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args)
    except BenchError as e:
        log(f"run.py: {e}")
        return 2
    except Exception:                     # no result line on any fault
        traceback.print_exc()
        return 1
    log(f"window_compiles = {result['window_compiles']}")
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
