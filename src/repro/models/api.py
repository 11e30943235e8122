"""Unified model API: init / loss / prefill / decode + ShapeDtypeStruct input
specs for every (arch x shape) cell. This is the surface the launcher, dry-run,
tests and benchmarks program against.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist.sharding import constrain
from repro.models import encoder, hybrid, ssm_lm, transformer
from repro.models import layers as L


def _module(cfg: ModelConfig):
    if cfg.family == "ssm":
        return ssm_lm
    if cfg.family == "hybrid":
        return hybrid
    if cfg.family == "audio":
        return encoder
    return transformer  # dense | moe | vlm


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init(cfg: ModelConfig, key=None):
    """Returns (param_values, param_axes) pytrees."""
    key = key if key is not None else jax.random.PRNGKey(0)
    tree = _module(cfg).init_params(key, cfg)
    return L.split_params(tree)


def _shapes_and_axes(builder):
    """eval_shape a Param-tree builder without allocation; axes via side
    channel (they are static python metadata)."""
    box = {}

    def f():
        vals, axes = L.split_params(builder())
        box["axes"] = axes
        return vals

    shapes = jax.eval_shape(f)
    return shapes, box["axes"]


def param_axes(cfg: ModelConfig):
    """Axes pytree without materializing params."""
    return _shapes_and_axes(
        lambda: _module(cfg).init_params(jax.random.PRNGKey(0), cfg))[1]


def param_shapes(cfg: ModelConfig):
    return _shapes_and_axes(
        lambda: _module(cfg).init_params(jax.random.PRNGKey(0), cfg))[0]


# ---------------------------------------------------------------------------
# losses / steps
# ---------------------------------------------------------------------------
def cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def loss_and_counters(params, cfg: ModelConfig,
                      batch: Dict[str, jnp.ndarray]):
    """(loss, counters): the training loss, and the forward's integer
    counters (the expert layers' routing, `layers.MOE_COUNTERS`; {} for a
    model without expert layers)."""
    mod = _module(cfg)
    counters = {}
    if cfg.family == "audio":
        logits, aux = mod.forward(params, cfg, batch["features"])
    elif mod is transformer:
        logits, aux, counters = transformer.forward_with_counters(
            params, cfg, batch["tokens"], positions=batch.get("positions"))
    else:
        logits, aux = mod.forward(params, cfg, batch["tokens"])
    return cross_entropy(logits, batch["labels"]) + aux, counters


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    return loss_and_counters(params, cfg, batch)[0]


def forward(params, cfg: ModelConfig, *args, **kw):
    return _module(cfg).forward(params, cfg, *args, **kw)


def prefill(params, cfg: ModelConfig, batch):
    """Forward returning logits only (inference prefill)."""
    if cfg.family == "audio":
        logits, _ = _module(cfg).forward(params, cfg, batch["features"])
    elif cfg.family == "vlm":
        logits, _ = _module(cfg).forward(params, cfg, batch["tokens"],
                                         positions=batch.get("positions"))
    else:
        logits, _ = _module(cfg).forward(params, cfg, batch["tokens"])
    return logits


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16):
    """Returns (state_values, state_axes) for the decode carrier
    (KV cache / SSM state / both)."""
    if cfg.family == "ssm":
        tree = ssm_lm.init_state(cfg, batch, max_len, dtype)
    elif cfg.family == "hybrid":
        tree = hybrid.init_state(cfg, batch, max_len, dtype)
    elif cfg.family == "audio":
        raise ValueError("encoder-only arch has no decode state")
    else:
        tree = transformer.init_cache(cfg, batch, max_len, dtype)
    return L.split_params(tree)


def decode_state_specs(cfg: ModelConfig, batch: int, max_len: int,
                       dtype=jnp.bfloat16):
    builder = {
        "ssm": ssm_lm.init_state, "hybrid": hybrid.init_state,
    }.get(cfg.family, transformer.init_cache)
    return _shapes_and_axes(lambda: builder(cfg, batch, max_len, dtype))


def decode_step(params, cfg: ModelConfig, state, tokens, index):
    mod = _module(cfg)
    if cfg.family == "audio":
        raise ValueError("encoder-only arch has no decode step")
    return mod.decode_step(params, cfg, state, tokens, index)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation) per shape cell
# ---------------------------------------------------------------------------
def batch_specs(cfg: ModelConfig, shape: ShapeConfig
                ) -> Tuple[Dict[str, jax.ShapeDtypeStruct], Dict[str, tuple]]:
    """Train/prefill batch: (specs, logical_axes)."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if cfg.family == "audio":
        specs = {
            "features": jax.ShapeDtypeStruct((B, S, cfg.frontend_dim),
                                             jnp.bfloat16),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
        axes = {"features": ("batch", "seq", None), "labels": ("batch", "seq")}
    elif cfg.family == "vlm":
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "positions": jax.ShapeDtypeStruct((3, B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
        axes = {"tokens": ("batch", "seq"), "positions": (None, "batch", "seq"),
                "labels": ("batch", "seq")}
    else:
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
        axes = {"tokens": ("batch", "seq"), "labels": ("batch", "seq")}
    if shape.kind == "prefill":
        specs.pop("labels")
        axes.pop("labels")
    return specs, axes


def decode_batch_specs(cfg: ModelConfig, shape: ShapeConfig):
    """Decode step inputs: tokens (B,), index scalar."""
    B = shape.global_batch
    specs = {
        "tokens": jax.ShapeDtypeStruct((B,), jnp.int32),
        "index": jax.ShapeDtypeStruct((), jnp.int32),
    }
    axes = {"tokens": ("batch",), "index": ()}
    return specs, axes


def make_batch(cfg: ModelConfig, shape: ShapeConfig, key=None,
               batch_override: Optional[int] = None,
               seq_override: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Materialize a synthetic batch (small shapes / tests only)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    B = batch_override or shape.global_batch
    S = seq_override or shape.seq_len
    k1, k2 = jax.random.split(key)
    if cfg.family == "audio":
        return {
            "features": jax.random.normal(k1, (B, S, cfg.frontend_dim),
                                          jnp.bfloat16),
            "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size),
        }
    batch = {
        "tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size),
    }
    if cfg.family == "vlm":
        pos = jnp.broadcast_to(jnp.arange(S)[None, None], (3, B, S))
        batch["positions"] = pos
    return batch
