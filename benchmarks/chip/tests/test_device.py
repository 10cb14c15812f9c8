
import pytest

import harness
import run


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.peak_of("TPU v99 imaginary")
    assert harness.peak_of("TPU v5 lite")["bf16_flops"] == 197e12


def test_no_result_without_a_tpu(capsys):
    rc = run.main(["--workload", "crossdevice-dp-topk", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert "correct" not in capsys.readouterr().out


def test_percentile_and_judge():
    assert harness.percentile([3, 1, 2, 4], 95) == 4
    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile([1.0, float("inf")], 95) == float("inf")
    assert harness.judge({"a": {"value": 1e-6, "limit": 1e-5}})
    assert not harness.judge({"a": {"value": float("nan"), "limit": 1.0}})
