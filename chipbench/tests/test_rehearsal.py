"""The harness end to end on the CPU at a tiny size."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import harness, trace
from chipbench.tests.conftest import REPO, edit_json

SEED = 2 ** 31 + 12345        # larger than 32 signed bits hold


def run(root, workload, trace_on=False, seconds=2.0, log=lambda s: None):
    return harness.run_cell(root, workload, SEED, seconds, trace_on,
                            time.monotonic(), require_tpu=False, log=log)


def test_cli_exits_nonzero_and_prints_nothing_off_a_tpu():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "train-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_cli_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(os.path.join(REPO, "chipbench"), tmp_path / "chipbench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "train-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""


CELLS = {
    "train-steady": ({"train_tokens_per_s"}, {"loss_gap", "grad_gap",
                                              "change_gap"}),
    "fleet-chaos": ({"fleet_trajectories_per_s"},
                    {"count_mismatch", "time_gap", "cost_gap"}),
}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_each_cell_runs_and_compares_on_the_cpu(tiny_tree, workload):
    """Qwen3's block at a tiny size, the fleet at the cells' own size."""
    lines = []
    r = run(tiny_tree, workload, log=lines.append)
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    # set-up's phases, in order, end where the window starts
    marks = [line.split() for line in lines if line.startswith("setup ")]
    assert [m[1] for m in marks[:2]] == ["import_jax", "devices"]
    assert marks[-1][1] == "window"
    times = [float(m[2]) for m in marks]
    assert times == sorted(times)
    assert abs(times[-1] - r["metrics"]["setup_s"]["value"]) < 0.05
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    metrics, checks = CELLS[workload]
    assert set(r["metrics"]) == metrics | {"setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert all(m["unit"] for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"
    assert set(r["checks"]) == checks


def test_new_config_traffic_and_metric_are_found_by_name(tiny_tree,
                                                         monkeypatch):
    """A cell made of new files only: a configuration, a traffic mix and
    a per-layer metric reader, each added beside the others, and the
    harness reads them by the names BENCHMARK.json gives."""
    d = os.path.join(tiny_tree, "chipbench")
    shutil.copy(os.path.join(d, "configs", "qwen3-1.7b-L5.json"),
                os.path.join(d, "configs", "dummy-config.json"))
    edit_json(os.path.join(d, "configs", "dummy-config.json"),
              name="dummy-config", num_hidden_layers=1)
    with open(os.path.join(d, "traffic", "dummy-mix.json"), "w") as f:
        json.dump({"driver": "train", "global_batch": 2, "seq_len": 16}, f)
    with open(os.path.join(d, "metrics", "dummy_steps.py"), "w") as f:
        f.write("def read(trace, facts, device):\n"
                "    return float(facts['steps'])\n")
    bench = os.path.join(tiny_tree, "BENCHMARK.json")
    b = json.load(open(bench))
    b["workloads"].append({"name": "dummy-cell", "config": "dummy-config",
                           "traffic": "dummy-mix", "chips": 1,
                           "why": "found by name"})
    b["per_layer"].append({"name": "dummy_steps", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "train loop", "moves":
                           "train_tokens_per_s",
                           "workloads": ["dummy-cell"]})
    for m in b["end_to_end"]:
        if "train-steady" in m.get("workloads", []):
            m["workloads"].append("dummy-cell")
    json.dump(b, open(bench, "w"))
    # a CPU trace has no device plane: stand in for its reduction
    fake = trace.Reduced(window_s=2.0, busy_s=1.0, top_ops=[("dot", 1.0)],
                         idle_gaps=[("train_call", 0.5)],
                         span_busy_s={"train_call": 1.0},
                         span_count={"train_call": 1})
    monkeypatch.setattr(harness.Cell, "reduced_trace", lambda self: fake)
    r = run(tiny_tree, "dummy-cell", trace_on=True)
    assert r["correct"], r["checks"]
    assert r["metrics"]["dummy_steps"]["value"] == r["attempted"]
    assert set(r["metrics"]) == {"dummy_steps"}
    assert r["breakdown"]["device_ops"] == [["dot", 1.0]]
