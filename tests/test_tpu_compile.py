"""Compile the chip's programs for a described TPU v5e, without a chip.

The TPU compiler is installed beside the CPU backend, so XLA and Mosaic
refuse here what they would refuse on the chip: block shapes off the
(8, 128) tiling, operands Mosaic cannot take, programs that do not fit
16 GB. Every Pallas kernel compiles at real widths (the shapes
`chip_smoke.py` runs), and so do the train and decode steps of its
qwen3-1.7b configuration, from `jax.eval_shape` shapes. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers import
every test file.
"""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import event_select as es
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd_scan as ss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16e9      # one v5e chip


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMOKE = _chip_smoke()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described chip is written there but cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def _kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_compiles(one_chip):
    x = _on(one_chip, jax.ShapeDtypeStruct(SMOKE.RMS_SHAPE, jnp.bfloat16))
    scale = _on(one_chip, jax.ShapeDtypeStruct(SMOKE.RMS_SHAPE[-1:],
                                               jnp.float32))
    _kernel_compiles(functools.partial(rn.rmsnorm_fwd, interpret=False),
                     x, scale)


def test_flash_attention_fwd_and_bwd_compile(one_chip):
    b, s, h, kv, hd = SMOKE.ATTN
    q = _on(one_chip, jax.ShapeDtypeStruct((b, s, h, hd), jnp.bfloat16))
    k = _on(one_chip, jax.ShapeDtypeStruct((b, s, kv, hd), jnp.bfloat16))
    fwd = functools.partial(fa.flash_attention_fwd, interpret=False)
    _kernel_compiles(fwd, q, k, k)
    out, lse = _on(one_chip, jax.eval_shape(fwd, q, k, k))
    _kernel_compiles(functools.partial(fa.flash_attention_bwd,
                                       interpret=False),
                     q, k, k, out, lse, q)


def test_ssd_scan_compiles(one_chip):
    b, s, h, p, g, n, chunk = SMOKE.SSD
    sds = lambda shape, dt=jnp.bfloat16: _on(  # noqa: E731
        one_chip, jax.ShapeDtypeStruct(shape, dt))
    _kernel_compiles(functools.partial(ss.ssd_scan_fwd, chunk=chunk,
                                       interpret=False),
                     sds((b, s, h, p)), sds((b, s, h)),
                     sds((h,), jnp.float32), sds((b, s, g, n)),
                     sds((b, s, g, n)))


def test_event_select_compiles(one_chip):
    ev = _on(one_chip, jax.ShapeDtypeStruct(SMOKE.EVENTS, jnp.float32))
    _kernel_compiles(functools.partial(es.event_select_fwd,
                                       interpret=False), ev)


def test_chip_smoke_train_step_fits(one_chip):
    """The jitted step `Session.train` runs, at the depth and tokens per
    step chip_smoke.py trains with, fits one chip's memory."""
    from repro.core.trainer import TransientTrainer
    from repro.launch import steps as st

    cfg, run = SMOKE.model_config(), SMOKE.run_config()
    _, _, jit_step = TransientTrainer._build_step(cfg, run)
    state = _on(one_chip, st.train_state_specs(cfg, run))
    tok = jax.ShapeDtypeStruct((SMOKE.BATCH, SMOKE.SEQ), jnp.int32)
    batch = _on(one_chip, {"tokens": tok, "labels": tok})
    mem = jit_step.lower(state, batch).compile().memory_analysis()
    # the state is donated: its input buffers are reused as outputs
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


def test_chip_smoke_decode_step_fits(one_chip):
    """GatewayEngine's jitted iteration at chip_smoke.py's slots and
    context, on the trained model's f32 weights."""
    from repro.models import api
    from repro.serving.engine import build_step

    cfg = SMOKE.model_config()
    max_len = SMOKE.PROMPT + SMOKE.NEW_TOKENS
    state, axes = api.decode_state_specs(cfg, SMOKE.SLOTS, max_len)
    vec = lambda dt: jax.ShapeDtypeStruct((SMOKE.SLOTS,), dt)  # noqa: E731
    args = _on(one_chip, (api.param_shapes(cfg), state, vec(jnp.int32),
                          vec(jnp.int32), vec(jnp.bool_),
                          vec(jnp.float32),
                          jax.eval_shape(jax.random.PRNGKey, 0)))
    mem = build_step(cfg, axes).lower(*args).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


def test_fleet_engine_program_fits(one_chip, monkeypatch):
    """The jit fleet engine's while-loop program under chip_smoke.py's
    chaos scenario, at the width its parity check runs: f64 state, which
    the chip emulates, and no Mosaic call."""
    from repro.core.transient import fleet_jit

    build, seen = fleet_jit._compiled, {}

    class Entered(Exception):
        """Stops run_jit at its first loop entry, before anything runs."""

    def first_entry(*key):
        def call(st, ar):
            seen.update(fn=build(*key), st=st, ar=ar)
            raise Entered
        return call

    monkeypatch.setattr(fleet_jit, "_compiled", first_entry)
    _, simulate = SMOKE.fleet_simulate()
    with pytest.raises(Entered):
        simulate(samples=SMOKE.PARITY_N, engine="jit")
    with jax.enable_x64(True):
        st, ar = _on(one_chip, (seen["st"], seen["ar"]))
        compiled = seen["fn"].lower(st, ar).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


def test_qwen3_widths_are_published():
    """chip_smoke.py cuts only the depth of the published configuration."""
    cut, full = SMOKE.model_config(), get_config(SMOKE.ARCH)
    assert cut == full.with_(n_layers=cut.n_layers)
    assert (cut.d_model, cut.n_heads, cut.n_kv_heads, cut.head_dim,
            cut.d_ff, cut.vocab_size, cut.tie_embeddings) == (
        2048, 16, 8, 128, 6144, 151936, True)
