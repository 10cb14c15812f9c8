"""Whole runs on the CPU at a tiny size, with the look for a chip skipped:
a sound run comes out correct; the program broken underneath, or the
reference in bfloat16 put in its place (the control), does not."""
import argparse

import jax
import jax.numpy as jnp
import pytest

import faults
import harness
import run

CHIP = harness.HERE
SEED = 2 ** 31 + 4242


def execute(root, workload, plant=None, seconds=2.0):
    args = argparse.Namespace(workload=workload, seed=SEED,
                              seconds=seconds, trace=0)
    return run.execute(args, root=root, dirs=[root], plant=plant,
                       devices=jax.devices())


def real_limits(cell):
    """The chip cell's own limits, applied to the fixture's numbers."""
    return harness.load_json(CHIP / "limits" / f"{cell}.json")


def test_sound_rounds_run_is_correct(fixture_root):
    res = execute(fixture_root, "tiny-sync")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"client_updates_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["window_compiles"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS["rounds"]))
def test_rounds_fault_is_not_correct(fixture_root, fault):
    undo = []

    def plant(fed):
        u = faults.FAULTS["rounds"][fault](fed)
        if u:
            undo.append(u)
    try:
        res = execute(fixture_root, "tiny-sync", plant)
    finally:
        for u in undo:
            u()
    assert not res["correct"], res["checks"]


def test_rounds_control_is_not_correct(fixture_root):
    drv = harness.load_module(CHIP / "drivers" / "rounds.py", "rounds_t")
    cell = harness.load_cell("tiny-sync", fixture_root,
                             [fixture_root, CHIP])
    st = drv.build(cell, SEED)
    st.pop("fed")
    r = drv.readings(cell, st, dtype=jnp.bfloat16)
    lim = real_limits("crossdevice-dp-topk")
    assert any(r[k] > lim[k] for k in lim), r


def test_sound_service_run_is_correct(fixture_root):
    res = execute(fixture_root, "tiny-fedbuff", seconds=3.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"upload_p95_ms", "infer_p95_ms",
                                   "setup_s"}
    assert res["failed"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS["service"]))
def test_service_fault_is_not_correct(fixture_root, fault):
    res = execute(fixture_root, "tiny-fedbuff",
                  faults.FAULTS["service"][fault], seconds=3.0)
    assert not res["correct"], res["checks"]


def test_service_control_is_not_correct(fixture_root):
    import readings
    cell = harness.load_cell("tiny-fedbuff", fixture_root,
                             [fixture_root, CHIP])
    args = argparse.Namespace(workload="tiny-fedbuff", seed=SEED,
                              seconds=3.0, trace=0)
    ctx = run.Context(cell, args, jax.devices()[0])
    out = readings.service_readings(ctx, ["program", "control"])
    lim = real_limits("fedbuff-upload-infer")
    assert all(out["program"][k] <= lim[k] for k in lim), out["program"]
    assert any(out["control"][k] > lim[k] for k in lim), out["control"]


def test_load_generator_never_imports_jax():
    svc = harness.load_module(CHIP / "drivers" / "service.py", "svc_t")
    child = svc.Child()
    child.send({"op": "prepare", "num_clients": 3, "pool": [],
                "pages": []})
    assert child.recv()["op"] == "prepared"
    assert child.close() is False
    assert child.proc.returncode == 0
