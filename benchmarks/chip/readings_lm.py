#!/usr/bin/env python3
"""The readings a language-model cell's limits are set from, on the chip.

    python3 benchmarks/chip/readings_lm.py --workload <cell> \\
        --seeds 11,12 --modes program,control

For each seed and mode it prints one JSON line with every number the
cell's ``correct`` compares, from the checked first rounds
(``drivers/lm_rounds.py``; no window):

* ``program``  — the program as the benchmark runs it (a sound run);
* ``control``  — the plain reference with bfloat16 parameters, updates
  and activations, one precision below the configuration's, put in the
  program's place.
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

import harness  # noqa: E402


def main(argv=None) -> int:
    import jax.numpy as jnp
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="program,control")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.require_tpu(cell.chips)
    harness.enable_compile_cache()
    drv = harness.load_module(HERE / "drivers" / "lm_rounds.py", "lm_rounds")
    for seed in [int(s) for s in args.seeds.split(",")]:
        for mode in args.modes.split(","):
            st = drv.build(cell, seed)
            if mode == "program":
                drv.checked_rounds(st, cell.traffic["checked_rounds"])
            st.pop("fed")
            gc.collect()
            r = drv.readings(cell, st, dtype=jnp.bfloat16
                             if mode == "control" else None)
            print(json.dumps({"seed": seed, "mode": mode, **r},
                             default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
