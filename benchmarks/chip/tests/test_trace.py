from pathlib import Path

import pytest

import tracefile

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_gaps():
    busy = tracefile.union([(0, 2), (1, 3), (5, 6), (9, 12)], 0, 10)
    assert busy == [(0, 3), (5, 6), (9, 10)]
    assert tracefile.gaps(busy, 0, 10) == [(3, 5), (6, 9)]
    assert tracefile.gaps([], 0, 4) == [(0, 4)]


def test_names():
    assert tracefile.module_name("jit_agg(6825050033741016407)") == "jit_agg"
    assert tracefile.op_name(
        "%fusion.3 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop") \
        == "fusion.3"


def test_gap_named_by_innermost_span():
    spans = {n: [] for n in tracefile.SPANS}
    spans["window"] = [(0, 100)]
    spans["step"] = [(10, 60)]
    spans["upload"] = [(20, 30)]
    assert tracefile.innermost(spans, 25) == "upload"
    assert tracefile.innermost(spans, 50) == "step"
    assert tracefile.innermost(spans, 80) == "outside"


def test_reduce_a_recorded_tpu_trace():
    """A trace recorded on a TPU v5 lite: three ``step`` spans, each
    running ``jit__lambda`` (a matmul, about 22 us) then ``jit_agg``
    (a weighted sum, about 11 us)."""
    s = tracefile.reduce(*tracefile.load(str(DATA / "small_tpu.xplane.pb")))
    assert s.module_runs == {"jit__lambda": 3, "jit_agg": 3}
    assert s.module_s["jit__lambda"] == pytest.approx(3 * 22.5e-6, rel=0.05)
    assert s.module_s["jit_agg"] == pytest.approx(3 * 11.2e-6, rel=0.05)
    assert 0 < s.busy_s < s.window_s
    gaps = sum(sec for _, sec in s.idle_gaps)
    assert gaps <= s.window_s - s.busy_s + 1e-12
    assert {n for n, _ in s.idle_gaps} <= {"step", "outside"}
    assert len(s.steps) == 3
    for st in s.steps:
        assert st["program"] == "jit__lambda"
        assert 0 < st["prep_s"] < st["span_s"]
    names = [n for n, _ in s.device_ops]
    assert any(n.startswith("jit_agg/") for n in names)
    assert any(n.startswith("jit__lambda/") for n in names)
