"""A copy of the benchmark at a tiny size, for runs on the CPU."""
import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# qwen3's block at a size a CPU test holds; limits set from this size's
# own readings (program: loss 1.4e-4, grad 2.5e-3 at most; float8 control
# 6e-4 and 1.9e-2 at least)
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512,
        "limits": {"loss_gap": 4e-4, "grad_gap": 1e-2, "change_gap": 0.3}}


def load_json(path):
    with open(path) as f:
        return json.load(f)


def edit_json(path, **kw):
    with open(path) as f:
        d = json.load(f)
    d.update(kw)
    with open(path, "w") as f:
        json.dump(d, f, indent=1)
    return d


@pytest.fixture
def tiny_tree(tmp_path):
    """BENCHMARK.json and chipbench/ copied, the program linked, every
    model configuration cut to the tiny size and every training mix to a
    few short sequences; the fleet runs at its cells' own size."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(os.path.join(REPO, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "src"), root / "src")
    for name in os.listdir(root / "chipbench" / "configs"):
        path = root / "chipbench" / "configs" / name
        if name.endswith(".json") and json.load(open(path)).get(
                "model_type") == "qwen3":
            edit_json(path, **TINY)
    for name in os.listdir(root / "chipbench" / "traffic"):
        path = root / "chipbench" / "traffic" / name
        driver = json.load(open(path))["driver"]
        if driver == "train":
            edit_json(path, global_batch=2, seq_len=32)
    return str(root)
