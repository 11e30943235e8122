"""Sharding-rule resolution + divisibility fallback properties, and an
in-process mini dry-run on a small forced-host-device mesh (subprocess)."""
import json
import os
import subprocess
import sys

import pytest
from hypothesis import given, settings, strategies as st

import jax
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as sh
from repro.launch.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mesh1():
    return make_mesh((1, 1), ("data", "model"))


def test_logical_spec_resolution():
    m = mesh1()
    spec = sh.logical_spec(("batch", "seq", "heads"), sh.MEGATRON_RULES, m)
    assert spec == P(("data",), None, "model")


def test_unknown_names_replicate():
    m = mesh1()
    assert sh.logical_spec(("nope", None), sh.MEGATRON_RULES, m) == P(None, None)


def test_duplicate_axis_not_reused():
    m = mesh1()
    spec = sh.logical_spec(("heads", "ff"), sh.MEGATRON_RULES, m)
    # both map to "model"; second must drop to None
    assert spec == P("model", None)


@given(st.integers(1, 64), st.integers(1, 64))
@settings(max_examples=40, deadline=None)
def test_divisible_spec_property(dim0, dim1):
    m = make_mesh((1, 1), ("data", "model"))
    spec = sh.divisible_spec(m, P("data", "model"), (dim0, dim1))
    # with 1-sized axes everything divides
    assert spec == P("data", "model")


def test_divisible_spec_drops_indivisible():
    # fake a 4x2 mesh via abstract mesh sizes using the real 1-device mesh is
    # impossible; emulate with AbstractMesh
    am = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
    spec = sh.divisible_spec(am, P("data", "model"), (6, 4))
    assert spec == P(None, "model")  # 6 % 4 != 0 -> drop data; 4 % 2 == 0
    spec2 = sh.divisible_spec(am, P(("data", "model"),), (8,))
    assert spec2 == P(("data", "model"))
    spec3 = sh.divisible_spec(am, P(("data", "model"),), (4,))
    assert spec3 == P("data")


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """Lower+compile the smoke config on an 8-device host mesh — the same
    code path as the production dry-run, in miniature."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, os.path.join(%r, "src"))
import jax, jax.numpy as jnp
from repro.configs import get_config, RunConfig, SHAPES
from repro.dist import sharding as sh
from repro.launch import steps as st
from repro.launch.mesh import make_mesh
from repro.models import api

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_config("qwen3-1.7b", smoke=True)
run = RunConfig(zero1=True)
step, _ = st.make_train_step(cfg, run)
with sh.use_sharding(mesh, sh.MEGATRON_RULES):
    state_specs = st.train_state_specs(cfg, run)
    state_sh = st.train_state_shardings(mesh, cfg, run)
    import jax as j
    b_specs = {"tokens": j.ShapeDtypeStruct((8, 64), jnp.int32),
               "labels": j.ShapeDtypeStruct((8, 64), jnp.int32)}
    b_sh = sh.tree_shardings(mesh, {"tokens": ("batch", "seq"),
                                    "labels": ("batch", "seq")},
                             sh.MEGATRON_RULES, b_specs)
    lowered = jax.jit(step, in_shardings=(state_sh, b_sh)).lower(
        state_specs, b_specs)
    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    print(json.dumps({"ok": True, "flops": float(ca.get("flops", 0))}))
""" % ROOT
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["flops"] > 0


def test_expert_dispatch_stays_on_each_batch_shard():
    """The expert layer sorts its assignments and runs its grouped matmuls
    on each data shard's own tokens: on four host devices, under every
    rule-set, loss, routing counters and gradients equal one device's, and
    no collective carries an expert-layer op."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json, re
sys.path.insert(0, os.path.join(%r, "src"))
import jax, jax.numpy as jnp
from repro.configs import TRAIN_4K, get_config
from repro.dist import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import api

cfg = get_config("granite-moe-3b-a800m", smoke=True).with_(dtype="float32")
params, _ = api.init(cfg, jax.random.PRNGKey(0))
batch = api.make_batch(cfg, TRAIN_4K, batch_override=4, seq_override=32)
# a new function for every context: jit's trace cache does not know it
def fns():
    fn = lambda p, b: api.loss_and_counters(p, cfg, b)
    return jax.jit(fn), jax.jit(lambda p, b: jax.grad(
        lambda p: fn(p, b)[0])(p))
f, grad = fns()
(loss, counters), g = f(params, batch), grad(params, batch)
out = []
for shape in ((4, 1), (2, 2)):
    mesh = make_mesh(shape, ("data", "model"))
    for name in ("MEGATRON_RULES", "DP_RULES", "EP_RULES", "DPEP_RULES"):
        with sh.use_sharding(mesh, getattr(sh, name)):
            f, grad = fns()
            text = f.lower(params, batch).compile().as_text()
            l2, c2 = f(params, batch)
            g2 = grad(params, batch)
        moe_collectives = sum(
            1 for line in text.splitlines()
            if re.search(r"= \S+ (all-gather|all-to-all|all-reduce|"
                         r"reduce-scatter|collective-permute)", line)
            and "moe." in line)
        out.append({"mesh": shape, "rules": name,
                    "loss": abs(float(l2 - loss)),
                    "counters": {k: int(v) for k, v in c2.items()}
                    == {k: int(v) for k, v in counters.items()},
                    "grad": max(float(jnp.max(jnp.abs(a - b))) for a, b in
                                zip(jax.tree.leaves(g2), jax.tree.leaves(g))),
                    "moe_collectives": moe_collectives})
print(json.dumps(out))
""" % ROOT
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for rec in json.loads(out.stdout.strip().splitlines()[-1]):
        assert rec["loss"] < 1e-5 and rec["grad"] < 1e-5, rec
        assert rec["counters"] and rec["moe_collectives"] == 0, rec
