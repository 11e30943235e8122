"""Step factories: jit-able train_step / prefill_step / serve_step with
NamedShardings derived from the models' logical axes. Used by the launcher,
the multi-pod dry-run, and the examples.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.dist import sharding as sh
from repro.dist.compression import ErrorFeedback, payload_bytes
from repro.models import api
from repro.optim import clip_by_global_norm, cosine_warmup, make_optimizer


class TrainState(NamedTuple):
    params: Any
    opt: Any
    step: jnp.ndarray
    # error-feedback residual tree for grad compression (§VI-B); the empty
    # tuple is a leafless pytree, so uncompressed runs carry no extra state
    # and pre-compression checkpoints/specs stay structurally identical
    residual: Any = ()


# ---------------------------------------------------------------------------
# sharding derivation
# ---------------------------------------------------------------------------
def param_shardings(mesh, cfg: ModelConfig, rules=sh.MEGATRON_RULES):
    axes = api.param_axes(cfg)
    shapes = api.param_shapes(cfg)
    return sh.tree_shardings(mesh, axes, rules, shapes)


def _zero1(mesh, sharding: jax.sharding.NamedSharding, shape, rules):
    """Additionally shard the first unsharded divisible dim over 'data'
    (ZeRO-1: optimizer state partitioned across the data axis)."""
    if "data" not in mesh.axis_names:
        return sharding
    spec = list(sharding.spec) + [None] * (len(shape) - len(sharding.spec))
    used = {a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}
    if "data" in used:
        return sharding
    sizes = dict(mesh.shape)
    dsize = sizes["data"]
    for i, e in enumerate(spec):
        if e is None and shape[i] % dsize == 0 and shape[i] >= dsize:
            spec[i] = "data"
            return jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(*spec))
        if e is not None:
            axes = e if isinstance(e, tuple) else (e,)
            cur = 1
            for a in axes:
                cur *= sizes[a]
            if shape[i] % (cur * dsize) == 0:
                spec[i] = tuple(axes) + ("data",)
                return jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(*spec))
    return sharding


def opt_shardings(mesh, cfg: ModelConfig, run: RunConfig, p_shardings,
                  rules=sh.MEGATRON_RULES):
    """Optimizer-state shardings: mirror params, optionally ZeRO-1 over data.

    Opt state is {} (sgd) or {"m": params-like[, "v": params-like]}.
    """
    opt = make_optimizer(run.optimizer, run.lr, run.weight_decay,
                         master=run.master_weights)
    shapes = _live_param_shapes(cfg, run)
    opt_shape = jax.eval_shape(opt.init, shapes)
    if not opt_shape:
        return opt_shape

    def map_like(subtree):
        return jax.tree.map(
            lambda sdg, shp: (_zero1(mesh, sdg, shp.shape, rules)
                              if run.zero1 else sdg),
            p_shardings, subtree)

    return {k: map_like(v) for k, v in opt_shape.items()}


def batch_shardings(mesh, cfg: ModelConfig, shape: ShapeConfig,
                    rules=sh.MEGATRON_RULES):
    specs, axes = api.batch_specs(cfg, shape)
    return sh.tree_shardings(mesh, axes, rules, specs), specs


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------
def _live_param_shapes(cfg: ModelConfig, run: RunConfig):
    """Shapes of the LIVE params (bf16 when master_weights)."""
    shapes = api.param_shapes(cfg)
    if run.master_weights:
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
            shapes)
    return shapes


def make_train_step(cfg: ModelConfig, run: RunConfig, mesh=None,
                    rules=sh.MEGATRON_RULES):
    """Returns train_step(state, batch) -> (state, metrics).

    With ``run.grad_compression`` in {"bf16", "int8", "topk"}, the clipped
    gradients take the §VI-B wire round-trip before the optimizer sees
    them: the error-feedback residual carried in ``state.residual`` is
    folded in, the sum is quantize-decompressed, and the quantization
    error becomes the next step's residual. Metrics then include
    ``payload_bytes`` — the actual compressed push size the trainer
    reports on the event bus. A model with expert layers adds their
    counters (``layers.MOE_COUNTERS``) to the metrics.
    """
    lr = cosine_warmup(run.lr, run.warmup_steps, run.total_steps)
    opt = make_optimizer(run.optimizer, lr, run.weight_decay,
                         master=run.master_weights)
    ef = (ErrorFeedback(run.grad_compression)
          if run.grad_compression != "none" else None)

    def train_step(state: TrainState, batch):
        counters = {}
        if run.microbatch and run.microbatch > 1:
            n = run.microbatch
            split = jax.tree.map(
                lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:])
                if x.ndim >= 1 and x.shape[0] % n == 0 else x, batch)

            def micro(acc, mb):
                l, g = jax.value_and_grad(
                    lambda p: api.loss_fn(p, cfg, mb))(state.params)
                return (acc[0] + l / n,
                        jax.tree.map(lambda a, b: a + b / n, acc[1], g)), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (loss, grads), _ = jax.lax.scan(micro, (0.0, zeros), split)
        else:
            (loss, counters), grads = jax.value_and_grad(
                api.loss_and_counters, has_aux=True)(state.params, cfg, batch)

        grads, gnorm = clip_by_global_norm(grads, run.grad_clip)
        residual = state.residual
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm,
                   "step": state.step, **counters}
        if ef is not None:
            grads, residual = ef.roundtrip(grads, residual)
            metrics["payload_bytes"] = jnp.asarray(
                payload_bytes(grads, run.grad_compression), jnp.float32)
        new_params, new_opt = opt.update(grads, state.opt, state.params,
                                         state.step)
        return TrainState(new_params, new_opt, state.step + 1,
                          residual), metrics

    return train_step, opt


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        return api.prefill(params, cfg, batch)
    return prefill_step


def make_serve_step(cfg: ModelConfig):
    """One-token decode against a KV cache / SSM state."""
    def serve_step(params, state, tokens, index):
        logits, new_state = api.decode_step(params, cfg, state, tokens, index)
        return logits, new_state
    return serve_step


def init_residual(params, run: RunConfig):
    """Zero error-feedback residual when compression is on, else the empty
    (leafless) tree."""
    if run.grad_compression == "none":
        return ()
    return ErrorFeedback(run.grad_compression).init(params)


def init_train_state(cfg: ModelConfig, run: RunConfig, key=None) -> TrainState:
    params, _ = api.init(cfg, key)
    if run.master_weights:
        params = jax.tree.map(
            lambda p: p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p,
            params)
    lr = cosine_warmup(run.lr, run.warmup_steps, run.total_steps)
    opt = make_optimizer(run.optimizer, lr, run.weight_decay,
                         master=run.master_weights)
    return TrainState(params, opt.init(params), jnp.zeros((), jnp.int32),
                      init_residual(params, run))


def train_state_specs(cfg: ModelConfig, run: RunConfig):
    """(ShapeDtypeStruct tree, shardings fn) for AOT lowering without alloc."""
    pshapes = _live_param_shapes(cfg, run)
    lr = cosine_warmup(run.lr, run.warmup_steps, run.total_steps)
    opt = make_optimizer(run.optimizer, lr, run.weight_decay,
                         master=run.master_weights)
    opt_shapes = jax.eval_shape(opt.init, pshapes)
    res_shapes = ()
    if run.grad_compression != "none":
        res_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), pshapes)
    return TrainState(pshapes, opt_shapes,
                      jax.ShapeDtypeStruct((), jnp.int32), res_shapes)


def train_state_shardings(mesh, cfg: ModelConfig, run: RunConfig,
                          rules=sh.MEGATRON_RULES):
    ps = param_shardings(mesh, cfg, rules)
    os_ = opt_shardings(mesh, cfg, run, ps, rules)
    scalar = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    # the residual is params-shaped (f32), so it shards exactly like params
    rs = ps if run.grad_compression != "none" else ()
    return TrainState(ps, os_, scalar, rs)
