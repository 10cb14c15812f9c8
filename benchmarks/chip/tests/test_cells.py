import json
from types import SimpleNamespace

import pytest

import harness
import run

CHIP = harness.HERE


def test_every_cell_of_the_benchmark_has_its_files():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (CHIP / "drivers" / f"{cell.traffic['driver']}.py").exists()
        for m in cell.per_layer:
            assert (CHIP / "metrics" / f"{m['name']}.py").exists()
        for name, limit in cell.limits.items():
            assert limit > 0, name
    for c in bench["configs"]:
        assert (harness.ROOT / c["file"]).exists()


def test_a_cell_and_a_metric_found_by_name_alone(fixture_root):
    """The fixture's cell and its ``rounds_seen.train`` reader exist only
    as files of the fixture: adding them edits no file of the harness."""
    cell = harness.load_cell("tiny-sync", fixture_root,
                             [fixture_root, CHIP])
    names = [m["name"] for m in cell.per_layer]
    assert "rounds_seen.train" in names
    outcome = harness.Outcome(attempted=1, failed=0, end_to_end={},
                              checks={}, memory_peak_bytes=0,
                              counters={"rounds": 7, "flops_per_round": 1})
    trace = SimpleNamespace(busy_s=1.0, window_s=4.0, steps=[],
                            module_s={})
    got = run.per_layer(cell, outcome, trace, {"bf16_flops": 1.0})
    assert got["rounds_seen.train"] == {"value": 7.0, "unit": "rounds"}
    assert got["device_idle_share.train"]["value"] == 75.0
    assert "host_prep_ms_per_round.train" not in got   # nothing to read


def test_a_per_layer_metric_without_workloads_is_refused():
    with pytest.raises(harness.BenchError, match="names no workloads"):
        harness.reports({"name": "mfu.train",
                         "moves": "client_updates_per_s"}, "tiny-sync")
