"""HuBERT-style encoder-only transformer. The wav2vec2 conv feature stem is a
STUB per the assignment: input_specs() supplies precomputed frame embeddings
(B, T, frontend_dim); here we project them, add a convolutional positional
embedding, and run bidirectional attention layers. Head predicts the masked
codebook targets (vocab=504)."""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain
from repro.models import layers as L
from repro.models.transformer import (_layer_apply, _remat, _stack, init_layer,
                                      scan_layers)

_CONV_POS_K = 31


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 4)
    blocks = [init_layer(keys[i], cfg, dense_ffn=False)
              for i in range(cfg.n_layers)]
    return {
        "frontend_proj": L._dense_init(keys[-1], (cfg.frontend_dim, cfg.d_model),
                                       (None, "embed")),
        "pos_conv": L._dense_init(keys[-2], (_CONV_POS_K, cfg.d_model),
                                  (None, "embed"),
                                  scale=1.0 / math.sqrt(_CONV_POS_K)),
        "layers": _stack(blocks),
        "final_norm": L.init_rmsnorm(cfg.d_model),
        "head": L._dense_init(keys[-3], (cfg.d_model, cfg.vocab_size),
                              ("embed", "vocab")),
    }


def forward(params, cfg: ModelConfig, features, positions=None,
            input_embeds=None):
    """features: (B, T, frontend_dim) precomputed frame embeddings (stub)."""
    x = features.astype(cfg.dtype) @ params["frontend_proj"].astype(cfg.dtype)
    x = constrain(x, "batch", "seq", "embed")
    B, T, d = x.shape
    # depthwise "same" conv positional embedding
    w = params["pos_conv"].astype(x.dtype)
    half = _CONV_POS_K // 2
    xp = jnp.pad(x, ((0, 0), (half, half), (0, 0)))
    pos = sum(xp[:, i:i + T] * w[i][None, None, :] for i in range(_CONV_POS_K))
    x = x + jax.nn.gelu(pos)

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

    def body(x, lp):
        x, aux, _, _ = _layer_apply(lp, cfg, x, positions, is_dense_ffn=False)
        return x, aux

    x, _ = scan_layers(body, x, params["layers"], cfg)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = x @ params["head"].astype(cfg.dtype)
    return constrain(logits, "batch", "seq", "vocab"), jnp.zeros((), jnp.float32)
