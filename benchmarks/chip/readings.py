#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip at its size.

    python3 benchmarks/chip/readings.py --workload <cell> \\
        --seeds 11,12,13 --modes program,control,unchanged,half_batch

For each seed and mode it prints one JSON line with every number the
cell's ``correct`` compares:

* ``program``  — the program as the benchmark runs it (a sound run);
* ``control``  — the plain reference in bfloat16, one precision below
  the configuration's float32, put in the program's place;
* a fault of ``faults.py`` planted in the program (``unchanged``,
  ``half_batch``, ``answer_altered``).

The rounds cell needs no window: its numbers come from the checked first
rounds.  The service cell runs a window of ``--seconds`` at the cell's
own load for each seed, and reads the program and the control from the
same answers.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import faults  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402


def rounds_readings(ctx, mode, plant=None):
    import jax.numpy as jnp
    drv = harness.load_module(HERE / "drivers" / "rounds.py", "rounds")
    cell, seed = ctx.cell, ctx.args.seed
    st = drv.build(cell, seed, plant=plant)
    try:
        if mode != "control":
            drv.checked_rounds(st, cell.traffic["checked_rounds"])
        st.pop("fed")
        gc.collect()
        return drv.readings(cell, st, dtype=jnp.bfloat16
                            if mode == "control" else None)
    finally:
        if st.get("undo"):
            st["undo"]()


def service_readings(ctx, modes, plant=None):
    import jax.numpy as jnp
    from repro.net import BackgroundServer
    from repro.serve import FederationService
    svc = harness.load_module(HERE / "drivers" / "service.py", "svc")
    cell, args = ctx.cell, ctx.args
    ctx.plant = plant
    child = svc.Child()
    try:
        st = svc.setup(ctx, child, BackgroundServer, FederationService)
        try:
            sched = svc.schedule(cell.traffic, cell.config, args.seconds,
                                 args.seed)
            records, _, _ = svc.window(ctx, child, st, sched)
            version, live = st["service"].fetch_model()
            live = svc.host(live)
        finally:
            st["bg"].stop()
    finally:
        child.close()
    st.pop("service")
    gc.collect()
    kw = dict(cfg=cell.config, tr=cell.traffic, p0=st["p0"],
              pool=st["pool"], weight=st["weight"],
              records=st["warm"] + records, version=version, live=live,
              pages=st["pages"], seed=args.seed)
    return {m: svc.readings(**kw, dtype=jnp.bfloat16 if m == "control"
                            else None) for m in modes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="program,control")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    args.trace = 0
    cell = harness.load_cell(args.workload)
    devices = harness.require_tpu(cell.chips)
    harness.enable_compile_cache()
    kind = cell.traffic["driver"]
    modes = args.modes.split(",")
    for seed in [int(s) for s in args.seeds.split(",")]:
        args.seed = seed
        ctx = run.Context(cell, args, devices[0])
        plain = [m for m in modes if m in ("program", "control")]
        planted = [m for m in modes if m not in plain]
        out = {}
        if kind == "rounds":
            for m in plain:
                out[m] = rounds_readings(ctx, m)
            for m in planted:
                out[m] = rounds_readings(ctx, m, faults.FAULTS[kind][m])
        else:
            if plain:
                out.update(service_readings(ctx, plain))
            for m in planted:
                out[m] = service_readings(
                    ctx, ["program"], faults.FAULTS[kind][m])["program"]
        for m, r in out.items():
            print(json.dumps({"seed": seed, "mode": m,
                              **{k: v for k, v in r.items()
                                 if k != "detail"},
                              "detail": r["detail"]}, default=str),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
