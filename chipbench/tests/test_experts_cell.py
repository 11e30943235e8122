"""The expert-parallel training cell, rehearsed on the CPU at a tiny size:
DeepSeek-V2's block with its latent attention, YaRN and a held share of
the experts, through the harness, the training driver and the reference."""
import json
import os
import time

import pytest

from chipbench import harness
from chipbench.tests.conftest import edit_json, load_json

SEED = 2 ** 31 + 4242         # larger than 32 signed bits hold
WORKLOAD = "train-experts-8k"
CONFIG = "deepseek-v2-lite-L5-ep8"

# DeepSeek-V2's block at a size a CPU test holds: 2 dense + 4 expert
# layers, 4 of 8 routed experts held, top-2, 1 shared; rotary scaling over
# an original context of 16, so YaRN's ramp splits the rotary dims at the
# traffic's 32 positions. Limits set from this size's own readings on
# eight seeds (program: loss 8.5e-4, grad 9.2e-3, change 3.5e-3 at most;
# float8 control loss 2.6e-3, grad 3.2e-2 and change 9.6e-3, half batch
# loss 3.5e-2, grad 9.8e-2 and change 0.16 at least, on three).
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 6,
        "first_k_dense_replace": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": 4, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "vocab_size": 256,
        "limits": {"loss_gap": 2.5e-3, "grad_gap": 1.6e-2, "change_gap": 0.05}}
TINY_GROUPS = {
    "expert_parallel": {"chips": 2, "router_experts": 8,
                        "first_held_expert": 0},
    "mla": {"kv_lora_rank": 16, "q_lora_rank": 0, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16},
    "moe": {"n_experts": 8, "n_held": 4, "first_held": 0, "top_k": 2,
            "n_shared_experts": 1, "expert_d_ff": 32},
}


@pytest.fixture
def experts_tree(tiny_tree):
    """The tiny tree with the expert configuration cut to its tiny size,
    the program block's groups with it."""
    path = os.path.join(tiny_tree, "chipbench", "configs", CONFIG + ".json")
    c = load_json(path)
    prog = c["program"]["model_config"]
    edit_json(path, **TINY,
              expert_parallel=TINY_GROUPS["expert_parallel"],
              rope_scaling=dict(c["rope_scaling"],
                                original_max_position_embeddings=16),
              program=dict(c["program"], model_config=dict(
                  prog, mla=TINY_GROUPS["mla"],
                  moe=dict(prog["moe"], **TINY_GROUPS["moe"]))))
    return tiny_tree


def test_cell_is_listed_with_its_metrics():
    from chipbench.tests.conftest import REPO
    b = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = {w["name"]: w for w in b["workloads"]}[WORKLOAD]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "steady-8k", 1)
    for m in b["end_to_end"] + b["per_layer"]:
        listed = WORKLOAD in m.get("workloads", [])
        assert listed == (m["name"] in (
            "train_tokens_per_s", "train_mfu", "train_step_device_ms",
            "device_idle_share.train")), m["name"]


def test_cell_runs_and_compares_on_the_cpu(experts_tree):
    lines = []
    r = harness.run_cell(experts_tree, WORKLOAD, SEED, 2.0, False,
                         time.monotonic(), require_tpu=False,
                         log=lines.append)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert set(r["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert "compilations inside the window: 0 (0.000 s)" in lines


def test_float8_control_and_half_batch_read_not_correct(experts_tree):
    cell = harness.Cell(experts_tree, WORKLOAD, 0, 0.0, False)
    out = cell.driver().readings(experts_tree, WORKLOAD, [SEED], [SEED],
                                 require_tpu=False, emit=lambda line: None)
    limits = cell.config["limits"]
    by = {line["reading"]: line for line in out}
    assert set(by) == {"program", "fp8", "half_batch"}
    assert all(by["program"][k] <= v for k, v in limits.items())
    for name in ("fp8", "half_batch"):
        assert any(by[name][k] > v for k, v in limits.items()), by[name]
