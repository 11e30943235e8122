"""A traced run of each cell on the CPU, with the program's spans read."""
import math
import time

import pytest

from chipbench import peaks, program_spans, sweep
from chipbench.tests.test_rehearsal import SEED

METRICS = {
    "train-steady": ("train_host_ms_per_step", "train_sync_ms_per_step"),
    "fleet-chaos": ("fleet_host_ms_per_call", "fleet_draws_ms_per_call",
                    "fleet_loop_ms_per_call", "fleet_loop_entries_per_call"),
}


@pytest.fixture
def one_device_op(monkeypatch):
    """A CPU trace has no device plane: stand in one operation that ends
    with the window, so the readings cover all of it; and a CPU has no
    published peak for `train_mfu`."""
    monkeypatch.setattr(peaks, "peak",
                        lambda kind: {"bf16_flops_per_s": 1e12})
    real = sweep.read_xplane

    def read(path, span_names, program_names=()):
        _, spans, prog = real(path, span_names, program_names)
        w1 = next(b for n, _, b in spans if n == "window")
        return {"/device:CPU:0": [("%op", w1 - 1000, w1)]}, spans, prog

    monkeypatch.setattr(sweep, "read_xplane", read)


@pytest.mark.parametrize("workload", sorted(METRICS))
def test_each_cell_prints_its_program_span_metrics(tiny_tree, one_device_op,
                                                   workload):
    r = program_spans.run(tiny_tree, workload, SEED, 2.0, time.monotonic(),
                          require_tpu=False, log=lambda s: None)
    assert r["correct"], r["checks"]
    for name in METRICS[workload]:
        assert math.isfinite(r["metrics"][name]["value"]), name
        assert r["metrics"][name]["value"] > 0, name
    spans = r["program_spans"]
    if workload == "fleet-chaos":
        assert r["metrics"]["fleet_loop_entries_per_call"]["value"] >= 1
        assert spans["fleet.run_many"]["count"] == r["attempted"] // 32
        assert spans["fleet.run_many"]["n"] == r["attempted"]
    else:
        assert spans["train.step"]["count"] == r["attempted"]
        assert spans["train.data"]["tokens"] == r["attempted"] * 2 * 32
    # the one idle gap is named after a program span
    label, _ = r["breakdown"]["idle_gaps"][0]
    assert label in program_spans.PROGRAM_SPANS[
        "train" if workload == "train-steady" else "fleet"]
