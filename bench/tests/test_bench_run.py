"""A whole run of a cell at the CPU's size, and the harness's refusals."""
import json
import time

import pytest

from bench import ROOT, harness
from bench.run import main, run_cell
from bench.tests.tiny import tiny_cell

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_sound_run_is_correct_and_reports_its_metrics():
    cell = tiny_cell("yi-6b.l16")
    out = run_cell(cell, 2**31 + 3, 1.0, False, t_start=time.monotonic(),
                   peaks=PEAKS)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 40 and out["failed"] == 0
    assert set(out["metrics"]) == set(cell.end_to_end)
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"]["logit_gap"]["value"] < 0.05
    json.dumps(out)


def test_refuses_to_run_without_a_tpu(capsys):
    assert main(["--workload", "qwen3-1.7b.chat.r80", "--seed", "1",
                 "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_every_cell_finds_its_files():
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        assert cell.end_to_end and cell.per_layer
        for name in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_metric(name))


def test_a_chip_missing_from_the_peak_table_is_an_error():
    from bench.run import peaks_of

    assert peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(LookupError):
        peaks_of("TPU v9 imaginary")
