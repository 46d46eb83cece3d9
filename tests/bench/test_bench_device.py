"""The benchmark runs on a TPU it knows, or not at all."""
from __future__ import annotations

import types

import pytest

from benchmarks.chip import peaks


def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_v5e_peaks_from_the_table():
    p = peaks.peaks_for(_dev("tpu", "TPU v5 lite"))
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "source" in p


@pytest.mark.parametrize("dev", [_dev("cpu", "cpu"), _dev("gpu", "H100"),
                                 _dev("tpu", "TPU v9 imaginary")],
                         ids=["cpu", "gpu", "unknown-tpu"])
def test_metric_path_refuses_other_devices(dev):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for(dev)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    from benchmarks.chip import manifest, run
    cell = manifest.load()["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 5),
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "no TPU" in out.err
